"""A reader the toy benchmark brings itself: the least bytes of one decode
round, counted by the cell's own architecture (``cell.family.counts``)."""


def read(ctx):
    run, cell = ctx["run"], ctx["cell"]
    round_cost = getattr(cell.family.counts, "decode_round_cost", None)
    if not run.get("rounds") or round_cost is None:
        return None
    serving = cell.config["serving"]
    cost = round_cost(
        cell.arch, cell.family.draft(cell.arch, serving),
        int(serving["n_draft"]), run["mean_live_context"],
        run["row_rounds"] / run["rounds"])
    return cost["bytes"]
