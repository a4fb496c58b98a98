"""The round's state-update kernel's share of its roofline, in percent: over
every Mosaic call named ``ssm_decode_s<S>_r<rows>`` in the traced stretch
(the ``name=`` of the kernel's ``pl.pallas_call``, which XLA puts into the
instruction's name and which carries the pass's new tokens and the rows),
the architecture's ``ssm_kernel_cost(arch, S, rows)`` (max(FLOPs / peak,
bytes / bandwidth) of a call) over the calls' device time.  A program with no such
kernel, or an architecture with no such count, reads nothing."""

import re

from benchmark import counts
from benchmark.trace_reduce import OPS

NAME = re.compile(r"^%?[\w.\-]*ssm_decode_s(\d+)_r(\d+)[\w.\-]* = ")


def read(ctx):
    trace, peaks, cell = ctx["trace"], ctx["peaks"], ctx["cell"]
    kernel_cost = getattr(cell.family.counts, "ssm_kernel_cost", None)
    if peaks is None or not trace.planes or kernel_cost is None:
        return None
    spent, least = 0.0, 0.0
    for e in trace.events:
        if e[0] != trace.planes[0] or e[1] != OPS \
                or "custom-call" not in e[2]:
            continue
        m = NAME.match(e[2])
        if m:
            spent += e[4] / 1e9
            least += counts.roofline_seconds(
                kernel_cost(cell.arch, int(m.group(1)), int(m.group(2))),
                peaks)
    return 100.0 * least / spent if spent > 0 else None
