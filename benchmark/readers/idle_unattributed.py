"""Share of the traced stretch's idle time (the gaps between programs on
the device) whose middle no program span covers, in percent: idle the
program's own spans cannot name.  The spans that enclose a whole loop or
one turn of it (``looper/<tag>/iter``, ``serve/round``, the ``Looper``'s
own capsule span) tile the host's timeline and would name every gap, so
they do not count: only the spans inside a turn do (``<Capsule>.launch``,
``train/step_dispatch``, ``serve/fetch``, ``serve/dispatch``, ...)."""

import re
from bisect import bisect_right

from benchmark.readers._program import program_spans
from benchmark.trace_reduce import MODULES

ENCLOSING = re.compile(r"^(?:looper/.+/iter|serve/round|Looper\.\w+)$")


def read(ctx):
    trace = ctx["trace"]
    if not trace.planes:
        return None
    mods = sorted((e for e in trace.events
                   if e[0] == trace.planes[0] and e[1] == MODULES),
                  key=lambda e: e[3])
    gaps = [(a[3] + a[4], b[3]) for a, b in zip(mods, mods[1:])
            if b[3] > a[3] + a[4]]
    idle = sum(hi - lo for lo, hi in gaps)
    if idle <= 0:
        return None
    spans = [s for s in program_spans(trace) if not ENCLOSING.match(s[0])]
    starts = [s for _n, s, _t in spans]
    reach, far = [], float("-inf")     # latest end among spans started so far
    for _n, _s, t in spans:
        far = max(far, t)
        reach.append(far)
    bare = 0.0
    for lo, hi in gaps:
        mid = (lo + hi) / 2
        i = bisect_right(starts, mid)
        if i == 0 or reach[i - 1] < mid:
            bare += hi - lo
    return 100.0 * bare / idle
