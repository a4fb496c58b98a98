"""Backend-state helpers that touch no JAX backend themselves."""

from __future__ import annotations


def backends_initialized() -> bool:
    """True once this process has instantiated any XLA backend client.

    Touches NO jax backend state itself, so it is safe to consult before
    forking workers (notebook launch) or deciding a log rank.  Probes the
    private ``xla_bridge._backends`` registry; fails open (False) on
    private-API drift — callers treat that as "nothing initialized".
    """
    try:
        from jax._src import xla_bridge

        return bool(xla_bridge._backends)
    except Exception:
        return False


def safe_process_index() -> int:
    """The process index WITHOUT creating a backend as a side effect.

    Order of truth: the distributed runtime's process id when
    ``jax.distributed`` is up (multi-host: correct even before the first
    local backend exists), else the real ``jax.process_index()`` if a
    backend already exists, else 0 (single uninitialized process — the
    rank-0-like default).
    """
    try:
        from jax._src import distributed

        state = distributed.global_state
        if getattr(state, "coordinator_address", None):
            return int(state.process_id)
    except Exception:
        pass
    if not backends_initialized():
        return 0
    try:
        import jax

        return jax.process_index()
    except Exception:
        return 0
