"""A quantity of the serving rounds' own device counters, as the program's
process-wide record holds them once the loop has closed
(``rocket_tpu.observe.trace.get_rounds``: rounds, drafted, accepted, routed
and held top-k slots, tokens each held expert received in each routed
layer).  ``what`` chooses:

- ``expert_tokens_per_round``: mean tokens one held expert gets a round;
- ``expert_load_max_over_mean``: the busiest held expert's tokens over the
  mean of its layer, the mean over the routed layers (1 = even);
- ``held_slot_share``: slots that fell on a held expert, in percent of the
  slots routed (``held / router`` of them where the router is even);
- ``accept_rate``: drafts accepted, in percent of those proposed.

Nothing where the program keeps no such record or counted nothing."""


def read(ctx, what):
    try:
        from rocket_tpu.observe.trace import get_rounds
    except ImportError:
        return None
    seen = get_rounds().snapshot()
    layers = [row for row in seen.get("expert_tokens") or [] if sum(row)]
    if what == "accept_rate":
        return 100.0 * seen["accepted"] / seen["drafted"] \
            if seen.get("drafted") else None
    if what == "held_slot_share":
        return 100.0 * seen["held_slots"] / seen["routed_slots"] \
            if seen.get("routed_slots") else None
    if not layers or not seen.get("rounds"):
        return None
    if what == "expert_tokens_per_round":
        experts = sum(len(row) for row in layers)
        return sum(map(sum, layers)) / experts / seen["rounds"]
    if what == "expert_load_max_over_mean":
        return sum(max(row) * len(row) / sum(row)
                   for row in layers) / len(layers)
    raise ValueError(f"round_counters knows no quantity {what!r}")
