"""TrainState — the explicit, immutable pytree that replaces mutable
framework objects.

In the reference, training state is scattered across mutable registries
inside the ``Accelerator`` (``_models``, ``_optimizers``, ``_schedulers``,
``_custom_objects`` — SURVEY §7.1; e.g. ``rocket/core/module.py:106``,
``optimizer.py:109``).  The TPU build makes it one functional pytree that a
jitted, donated-argument ``train_step(state, batch)`` threads through the
run — the shape XLA wants (static structure, buffer donation, no Python
mutation in the hot path).

Contents:

- ``step``        — effective optimizer-step counter (int32 scalar array).
- ``params``      — model parameters (possibly sharded via GSPMD).
- ``opt_state``   — optax optimizer state.
- ``rng``         — PRNG key threaded through stochastic layers (dropout).
- ``mutable``     — non-parameter model collections (e.g. BatchNorm
  ``batch_stats``); empty dict when unused.
- ``grad_accum``  — running gradient sum for micro-batching; ``None`` when
  ``gradient_accumulation_steps == 1`` (reference's ``accumulate()`` window,
  ``module.py:211``).
- ``micro``       — micro-step counter inside the accumulation window.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from flax import struct


@struct.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any
    rng: jax.Array
    mutable: Any = struct.field(default_factory=dict)
    grad_accum: Optional[Any] = None
    micro: Optional[jax.Array] = None

    @classmethod
    def create(
        cls,
        params: Any,
        tx: Any,
        rng: Optional[jax.Array] = None,
        mutable: Optional[Any] = None,
        gradient_accumulation_steps: int = 1,
    ) -> "TrainState":
        """Build an initial state from params + an optax transform.

        ``tx.init`` runs under ``jax.eval_shape``-compatible tracing, so this
        is safe to call inside ``jax.jit`` for sharded initialization.
        """
        opt_state = tx.init(params)
        if rng is None:
            rng = jax.random.PRNGKey(0)
        grad_accum = None
        micro = None
        if gradient_accumulation_steps > 1:
            grad_accum = jax.tree_util.tree_map(jnp.zeros_like, params)
            micro = jnp.zeros((), dtype=jnp.int32)
        return cls(
            step=jnp.zeros((), dtype=jnp.int32),
            params=params,
            opt_state=opt_state,
            rng=rng,
            mutable=mutable if mutable is not None else {},
            grad_accum=grad_accum,
            micro=micro,
        )


def param_count(params: Any) -> int:
    """Total number of parameters in a pytree."""
    return sum(
        int(x.size) for x in jax.tree_util.tree_leaves(params) if hasattr(x, "size")
    )


def abstract_state(
    init_fn: Callable[[], TrainState],
) -> TrainState:
    """Shape/dtype skeleton of a state without allocating it — used to derive
    shardings before real (possibly distributed) initialization."""
    return jax.eval_shape(init_fn)


def _leaf_device_bytes(leaf: Any, spec: Any, mesh: Any) -> int:
    """Per-device bytes of one leaf under a PartitionSpec: each dim is
    divided (ceil) by the product of its mesh-axis sizes."""
    import math

    shape = list(getattr(leaf, "shape", ()))
    itemsize = jnp.dtype(getattr(leaf, "dtype", jnp.float32)).itemsize
    axes = dict(mesh.shape)
    entries = tuple(spec) if spec is not None else ()
    for i, entry in enumerate(entries[: len(shape)]):
        names = (
            () if entry is None
            else (entry,) if isinstance(entry, str) else tuple(entry)
        )
        divisor = int(math.prod([axes.get(str(n), 1) for n in names] or [1]))
        shape[i] = -(-shape[i] // divisor)  # ceil
    return int(math.prod(shape or [1])) * itemsize


def memory_plan(
    abstract: TrainState,
    state_specs: TrainState,
    mesh: Any,
    zero_offload: bool = False,
) -> dict:
    """Per-device byte accounting of a TrainState under a spec tree.

    Returns ``{'param_bytes', 'opt_bytes', 'other_bytes', 'total_bytes',
    'host_opt_bytes'}`` — what the sharding plan says each device holds at
    steady state (arguments only; activations/temps are the compiler's
    side).  This is the number the ZeRO tests assert on.  ``state_specs`` already encodes the ZeRO stage: at stage
    2 the grad-accum buffers, and at stage 3 the params themselves, carry
    data-composed specs, so the per-stage memory formula (see the stage
    decision table in ``docs/performance.md``) falls out of the same spec
    arithmetic with no stage special-casing here.

    ``zero_offload=True`` moves the optimizer-state bytes to the host
    tier: ``opt_bytes`` drops out of the device ``total_bytes`` and is
    reported as ``host_opt_bytes`` instead (each host holds its shard-
    owners' opt state in RAM; the double-buffered prefetch transiently
    re-materializes one step's worth on device during the update).
    """
    from jax.sharding import PartitionSpec

    is_spec = lambda x: isinstance(x, PartitionSpec)

    def section_bytes(tree: Any, specs: Any) -> int:
        leaves = jax.tree_util.tree_leaves(tree)
        spec_leaves = jax.tree_util.tree_leaves(specs, is_leaf=is_spec)
        return sum(
            _leaf_device_bytes(leaf, spec, mesh)
            for leaf, spec in zip(leaves, spec_leaves)
        )

    param_bytes = section_bytes(abstract.params, state_specs.params)
    opt_bytes = section_bytes(abstract.opt_state, state_specs.opt_state)
    total_bytes = section_bytes(abstract, state_specs)
    other_bytes = total_bytes - param_bytes - opt_bytes
    host_opt_bytes = 0
    if zero_offload:
        host_opt_bytes = opt_bytes
        opt_bytes = 0
        total_bytes = param_bytes + other_bytes
    return {
        "param_bytes": param_bytes,
        "opt_bytes": opt_bytes,
        "other_bytes": other_bytes,
        "total_bytes": total_bytes,
        "host_opt_bytes": host_opt_bytes,
    }
