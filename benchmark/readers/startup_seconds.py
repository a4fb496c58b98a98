"""Seconds the program's own start-up record books to the named phases
before the window opened (``rocket_tpu.observe.trace.get_startup``): a
phase's seconds leave out other phases recorded inside it, so the phases
add up.  A program without the record, or a run without the phase, reads
nothing."""

from benchmark.readers._program import window_open_ns


def read(ctx, phases):
    try:
        from rocket_tpu.observe.trace import get_startup
    except ImportError:
        return None
    seconds = get_startup().seconds(until_ns=window_open_ns(ctx))
    found = [seconds[p] for p in phases if p in seconds]
    return sum(found) if found else None
