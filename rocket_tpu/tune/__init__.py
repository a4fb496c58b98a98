"""What the programs use at start-up, and nothing else:

- :mod:`rocket_tpu.tune.compile_cache` — arms JAX's persistent
  compilation cache, counts its hits and misses for the start-up record,
  and serializes AOT executables where the backend supports it;
- :mod:`rocket_tpu.tune.warmup` — :class:`WarmupPlan`: explicit
  ``lower().compile()`` of the serving hot path's fixed-shape edges
  before the first request (and a built ``Module``'s train step),
  against that cache.

Nothing here searches, measures or keeps a record: the chip's numbers
come from ``benchmark/run.py``.
"""

from rocket_tpu.tune.compile_cache import (  # noqa: F401
    cache_dir,
    enable_compile_cache,
    hit_count,
)
from rocket_tpu.tune.warmup import (  # noqa: F401
    WarmupPlan,
    plan_for_batcher,
    warm_batcher,
    warm_module_step,
)
