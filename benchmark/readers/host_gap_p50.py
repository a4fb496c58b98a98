"""Median time, in milliseconds, from the return of the host's last
blocking device read (the end of the latest ``serve/fetch`` span) to the
opening of the next ``serve/dispatch``: the host's own view of the gap
between rounds."""

from bisect import bisect_right

from benchmark.readers._program import program_spans
from benchmark.trace_reduce import p50


def read(ctx):
    trace = ctx["trace"]
    ends = sorted(t for _n, _s, t in program_spans(trace, "serve/fetch"))
    gaps = []
    for _n, start, _t in program_spans(trace, "serve/dispatch"):
        i = bisect_right(ends, start)
        if i:
            gaps.append((start - ends[i - 1]) / 1e6)
    return p50(gaps)
