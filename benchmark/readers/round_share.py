"""One of the serving rounds' device counters over another, in percent, as
the program's process-wide record holds them once the loop has closed
(``rocket_tpu.observe.trace.get_rounds``): ``selected_keys`` over
``live_keys`` is the share of the keys a query could see that an attention
which chooses its keys kept, summed over live rows, queries and selecting
layers.  Nothing where the program keeps no such record or counter, or
counted nothing."""


def read(ctx, num, den):
    try:
        from rocket_tpu.observe.trace import get_rounds
    except ImportError:
        return None
    seen = get_rounds().snapshot()
    if not seen.get(den) or seen.get(num) is None:
        return None
    return 100.0 * seen[num] / seen[den]
