"""Helpers the readers share."""

from typing import Optional


def find_program(trace, program: str) -> Optional[str]:
    """``program`` itself, or for ``"dominant"`` the name of the program
    with the most device time in the trace (the train step in a training
    cell, whatever a refactor calls it)."""
    if program != "dominant":
        return program
    from collections import defaultdict

    from benchmark.trace_reduce import MODULES, program_name

    total = defaultdict(float)
    for e in trace.events:
        if e[1] == MODULES and trace.planes and e[0] == trace.planes[0]:
            total[program_name(e[2])] += e[4]
    return max(total, key=total.get) if total else None
