"""What the readers of the program's own record share: its host-plane
spans in the trace, and the window's opening on the program's clock.

A program span is a ``TraceAnnotation`` the program opened through
``observe/trace.py``'s span primitive, so it is an event of ``/host:CPU``
(any line: the serving watchdog dispatches from a thread of its own)
whose name is a lowercase ``cat/name`` slug or ``<Capsule>.<event>``.  The
runtime's own TraceMes (``PjitFunction(...)``, ``Execute...``) are not.
"""

import re
from typing import List, Optional, Tuple

HOST_PLANE = "/host:CPU"
ANCHOR = "bench/anchor"
SPAN_NAME = re.compile(
    r"^(?:[a-z0-9_]+(?:/[A-Za-z0-9_.]+)+"
    r"|[A-Za-z_]\w*\.(?:setup|set|launch|reset|destroy))$")


def program_spans(trace, name: Optional[str] = None
                  ) -> List[Tuple[str, float, float]]:
    """``(name, start_ns, end_ns)`` of every program span in the trace, or
    of those called ``name``, by start."""
    out = [(e[2], e[3], e[3] + e[4]) for e in trace.events
           if e[0] == HOST_PLANE and e[2] != ANCHOR
           and (e[2] == name if name is not None else SPAN_NAME.match(e[2]))]
    return sorted(out, key=lambda s: s[1])


def window_open_ns(ctx) -> Optional[int]:
    """``perf_counter_ns`` at which the measured window opened."""
    from benchmark import harness

    setup_s = ctx["run"].get("setup_s")
    if setup_s is None:
        return None
    return int((harness.PROCESS_START + setup_s) * 1e9)
