"""A percentile of raw samples the harness kept for the whole window."""

from benchmark.harness import percentile


def read(ctx, key, q):
    return percentile(list(ctx["run"].get(key) or []), float(q))
