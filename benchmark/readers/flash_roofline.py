"""The three flash kernels' share of their roofline, in percent: the sum
over forward, dq and dkv of max(FLOPs / peak, bytes / bandwidth), counted
from shapes, over the sum of the device time of the step's Mosaic calls."""

from benchmark import counts
from benchmark.readers._common import find_program

MOSAIC = r"tpu_custom_call"


def read(ctx):
    trace, peaks, cell = ctx["trace"], ctx["peaks"], ctx["cell"]
    spent = sum(trace.op_seconds(MOSAIC))
    name = find_program(trace, "dominant")
    kernel_cost = getattr(cell.family.counts, "flash_kernel_cost", None)
    if peaks is None or name is None or spent <= 0 or kernel_cost is None:
        return None
    steps = len(trace.program_seconds(name))
    mix = cell.traffic
    cost = kernel_cost(cell.arch, mix["batch"], mix["seq"])
    least = sum(counts.roofline_seconds(c, peaks) for c in cost.values())
    return 100.0 * least * steps / spent
