"""The serving step's share of the chip's peak over the whole window, in
percent: 2 x target matrix parameters x (prompt tokens admitted + output
tokens emitted) plus attention over the live context, over window and
peak.  Draft passes and rejected drafts are not useful work."""


def read(ctx):
    run, peaks, cell = ctx["run"], ctx["peaks"], ctx["cell"]
    serve_flops = getattr(cell.family.counts, "serve_flops", None)
    if (peaks is None or not run.get("window_s") or not run.get("tokens")
            or serve_flops is None):
        return None
    flops = serve_flops(cell.arch, run["prompt_tokens"],
                        run["tokens"], run["context_products"])
    return 100.0 * flops / run["window_s"] / (
        peaks["bf16_flops_per_s"] * cell.chips)
