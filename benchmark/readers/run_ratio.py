"""One harness count over another (tokens per round and row, ...)."""


def read(ctx, num, den):
    n, d = ctx["run"].get(num), ctx["run"].get(den)
    if n is None or not d:
        return None
    return float(n) / float(d)
