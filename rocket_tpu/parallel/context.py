"""Ambient mesh/rules context for activation sharding constraints.

Model code annotates *parameters* declaratively (``nn.with_partitioning``
logical names resolved by :class:`ShardingRules`), but *activations* need
in-line constraints (``with_sharding_constraint``) at the points where GSPMD
propagation would otherwise pick a bad layout (post-attention, post-MLP,
logits).  Those need the concrete mesh — which model code should not carry
around.  The Module capsule opens this context around ``apply`` (trace
time), and :func:`constrain` becomes a no-op when no mesh is active, so the
same model runs unsharded on one device (SURVEY §7.4: degrade gracefully).
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding

from rocket_tpu.parallel.sharding import DEFAULT_RULES, ShardingRules

_ACTIVE: contextvars.ContextVar[Optional[Tuple[Mesh, ShardingRules]]] = (
    contextvars.ContextVar("rocket_tpu_mesh_context", default=None)
)


@contextlib.contextmanager
def mesh_context(mesh: Mesh, rules: ShardingRules = DEFAULT_RULES):
    token = _ACTIVE.set((mesh, rules))
    try:
        yield
    finally:
        _ACTIVE.reset(token)


def current_mesh() -> Optional[Mesh]:
    ctx = _ACTIVE.get()
    return ctx[0] if ctx else None


def current_rules() -> ShardingRules:
    ctx = _ACTIVE.get()
    return ctx[1] if ctx else DEFAULT_RULES


def _manual_axes() -> frozenset:
    """Mesh axes currently under manual (shard_map) control at trace time,
    read off the abstract mesh."""
    am = jax.sharding.get_abstract_mesh()
    if am is None or am.empty:
        return frozenset()
    return frozenset(am.manual_axes)


def constrain(x: Any, *logical_axes: Optional[str]) -> Any:
    """Constrain an intermediate's sharding by logical axis names; identity
    when no mesh context is active (single-device runs, plain tests).

    Inside a ``shard_map``-manual region (e.g. the GPipe stage program,
    :func:`rocket_tpu.parallel.pipeline.gpipe`), mesh axes already under
    manual control are stripped from the spec — ``with_sharding_constraint``
    may only name non-manual axes there — degrading to identity when every
    requested axis is manual.  This lets the same model code run sequential,
    GSPMD-sharded, and pipelined without changes.
    """
    ctx = _ACTIVE.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    if mesh.devices.size == 1:
        return x
    spec = rules.spec(*logical_axes)
    manual = _manual_axes()
    if manual:
        entries = []
        for entry in spec:
            if entry is None:
                entries.append(None)
            elif isinstance(entry, tuple):
                kept = tuple(a for a in entry if a not in manual)
                entries.append(kept if kept else None)
            else:
                entries.append(entry if entry not in manual else None)
        if all(e is None for e in entries):
            return x
        spec = type(spec)(*entries)
    sharding = NamedSharding(mesh, spec)
    return jax.lax.with_sharding_constraint(x, sharding)
