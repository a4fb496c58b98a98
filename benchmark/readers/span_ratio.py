"""Events of one program span over events of another in the traced
stretch (``serve/fetch`` per ``serve/round``, ...)."""

from benchmark.readers._program import program_spans


def read(ctx, num, den):
    n = len(program_spans(ctx["trace"], num))
    d = len(program_spans(ctx["trace"], den))
    if not n or not d:
        return None
    return n / d
