"""Median device time of one compiled program's runs, in milliseconds."""

from benchmark.readers._common import find_program
from benchmark.trace_reduce import p50


def read(ctx, program):
    name = find_program(ctx["trace"], program)
    if name is None:
        return None
    value = p50(ctx["trace"].program_seconds(name))
    return None if value is None else value * 1e3
