"""One flash kernel's share of its roofline, in percent:
the architecture's ``flash_kernel_cost`` entry for it (``fwd``, ``dq`` or
``dkv``: max(FLOPs / peak, bytes / bandwidth) over all layers of a step)
over the device time of the Mosaic calls of that name.  The name is the
``name=`` of the kernel's ``pl.pallas_call``, which XLA puts into the
instruction's name (``%flash_dq.7 = ... custom-call(...)``); a program
whose kernels carry no such name reads nothing."""

import re

from benchmark import counts
from benchmark.readers._common import find_program
from benchmark.trace_reduce import OPS


def read(ctx, kernel, name):
    trace, peaks, cell = ctx["trace"], ctx["peaks"], ctx["cell"]
    kernel_cost = getattr(cell.family.counts, "flash_kernel_cost", None)
    if peaks is None or not trace.planes or kernel_cost is None:
        return None
    rx = re.compile(r"^%?[\w.\-]*" + re.escape(name) + r"[\w.\-]* = ")
    spent = sum(e[4] / 1e9 for e in trace.events
                if e[0] == trace.planes[0] and e[1] == OPS
                and "custom-call" in e[2] and rx.match(e[2]))
    step = find_program(trace, "dominant")
    if step is None or spent <= 0:
        return None
    steps = len(trace.program_seconds(step))
    mix = cell.traffic
    cost = kernel_cost(cell.arch, mix["batch"], mix["seq"])[kernel]
    return 100.0 * counts.roofline_seconds(cost, peaks) * steps / spent
