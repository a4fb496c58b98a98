"""Traffic kind ``open``: see :mod:`benchmark.kinds.serving`, which reads
the mix's ``kind`` to tell a closed loop (a new request queued as soon as one
completes) from an open one (Poisson arrivals at the mix's fixed rate, each
request timed from when it was due)."""

from benchmark.kinds.serving import check, drive  # noqa: F401
