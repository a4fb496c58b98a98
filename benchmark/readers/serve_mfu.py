"""The serving step's share of the chip's peak over the whole window, in
percent: 2 x target matrix parameters x (prompt tokens admitted + output
tokens emitted) plus attention over the live context, over window and
peak.  Draft passes and rejected drafts are not useful work."""

from benchmark import counts


def read(ctx):
    run, peaks = ctx["run"], ctx["peaks"]
    if peaks is None or not run.get("window_s") or not run.get("tokens"):
        return None
    flops = counts.serve_flops(ctx["cell"].arch, run["prompt_tokens"],
                               run["tokens"], run["context_products"])
    return 100.0 * flops / run["window_s"] / (
        peaks["bf16_flops_per_s"] * ctx["cell"].chips)
