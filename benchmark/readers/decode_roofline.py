"""One decode round's share of its roofline, in percent: the least time by
bytes (target weights once, draft weights once per chain step, K and V of
the live tokens once per model pass) or by FLOPs, whichever is larger, over
the median device time of the round's program."""

from benchmark import counts
from benchmark.trace_reduce import p50


def read(ctx, program):
    run, peaks, cell = ctx["run"], ctx["peaks"], ctx["cell"]
    spent = p50(ctx["trace"].program_seconds(program))
    round_cost = getattr(cell.family.counts, "decode_round_cost", None)
    if (peaks is None or not spent or not run.get("rounds")
            or round_cost is None):
        return None
    serving = cell.config["serving"]
    cost = round_cost(
        cell.arch, cell.family.draft(cell.arch, serving),
        int(serving["n_draft"]), run["mean_live_context"],
        run["row_rounds"] / run["rounds"])
    return 100.0 * counts.roofline_seconds(cost, peaks) / spent
