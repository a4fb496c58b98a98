"""Emergency checkpoint tier — preemption-grade persistence (ISSUE 8).

A TPU preemption notice leaves seconds, not minutes: the durable Orbax
cadence (``Checkpointer(save_every=...)``) may be hundreds of steps stale,
and even the grace-window snapshot needs the step loop to reach the next
iteration boundary.  This module closes that gap with a two-phase design:

1. **Capture** (hot path, every ``emergency_every`` steps): stage the
   registered capsules' state as *host references*.  For ``jax.Array``
   leaves the device→host copy is started with ``copy_to_host_async()`` —
   the same zero-sync readback primitive the async metrics loop uses — and
   the arrays themselves are kept by reference.  No device sync, no jit
   retrace (``tests/test_overhead_counts.py::TestElasticGuard``).  When
   buffer donation is live (non-CPU backends: the next step's dispatch
   invalidates the old state's buffers) the staged leaves are materialized
   to numpy at capture instead — that is the one configuration where
   capture pays a sync, and why the donation capability gate keeps CPU
   test runs reference-only.
2. **Flush** (cold path, SIGTERM / preemption notice): write the staged
   snapshot to ``<project>/emergency/<iter:06d>/`` as a *minimal committed
   snapshot* — the same composite layout, manifest (mesh-stamped, so it is
   elastic-restorable), and commit marker as a durable save, plus an
   ``_EMERGENCY`` marker.  Synchronous and idempotent: one flush per
   staged capture, even if SIGTERM arrives twice.

``resume("auto")`` elects snapshots by (iter, mtime) across BOTH tiers
(:func:`~rocket_tpu.persist.integrity.latest_valid`), so the emergency
snapshot wins exactly when the durable checkpoint is stale — bounding the
work lost to a hard preemption at ≤1 step.
"""

from __future__ import annotations

import os
import shutil
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from rocket_tpu.persist import integrity
from rocket_tpu.utils.logging import get_logger

_logger = get_logger("emergency")

MARKER = integrity.EMERGENCY_MARKER


def _start_host_copies(tree: Any) -> None:
    """Kick off async device→host transfers for every jax.Array leaf —
    returns immediately; the copies drain in the background."""
    for leaf in jax.tree_util.tree_leaves(tree):
        start = getattr(leaf, "copy_to_host_async", None)
        if start is not None:
            try:
                start()
            except Exception:  # staging must never break the step loop
                pass


def _to_host(tree: Any) -> Any:
    """Materialize every leaf as host numpy (transfers already started by
    :func:`_start_host_copies` complete here, overlapped)."""

    def leaf(x: Any) -> Any:
        if isinstance(x, np.ndarray):
            return x
        if hasattr(x, "is_fully_addressable") and not x.is_fully_addressable:
            # Multi-host-sharded leaf this process cannot address in full:
            # keep the array ref — the collective orbax write at flush
            # time handles per-host shards.
            return x
        try:
            return np.asarray(x)
        except Exception:
            return x

    return jax.tree_util.tree_map(leaf, tree)


class EmergencyTier:
    """In-memory host snapshot, flushed to disk on preemption.

    Parameters
    ----------
    root:
        Project directory the flush writes under.
    dir_format:
        Snapshot path format below ``root`` (digit-named so the integrity
        scanner's election sees it).
    keep:
        Flushed emergency snapshots retained on disk (older ones pruned
        at the next flush).
    """

    def __init__(
        self,
        root: str,
        dir_format: str = "emergency/{:06d}",
        keep: int = 2,
        logger: Optional[Any] = None,
    ) -> None:
        if keep < 1:
            raise ValueError("keep must be >= 1")
        self._root = root
        self._format = dir_format
        self._keep = int(keep)
        self._logger = logger if logger is not None else _logger
        self._staged: Optional[Tuple[Dict[str, Any], int, Optional[int],
                                     Any, Any, Optional[int]]] = None
        self.captures = 0
        self.flushes = 0

    # -- hot path ------------------------------------------------------------

    def capture(
        self,
        items: Dict[str, Any],
        *,
        iter_idx: int,
        epoch_idx: Optional[int] = None,
        mesh: Any = None,
        rules: Any = None,
        zero_stage: Optional[int] = None,
    ) -> None:
        """Stage ``items`` (capsule-key → state pytree) for a later flush.

        Zero device syncs on the happy path: transfers are started async
        and the arrays held by reference.  Only when donation is live
        (non-CPU backend — the refs would die at the next step dispatch)
        are leaves materialized eagerly.
        """
        for tree in items.values():
            _start_host_copies(tree)
        if jax.default_backend() != "cpu":
            # Donation-capable backend: the staged refs are invalidated by
            # the next donated step dispatch — pin host copies now (the
            # async copies above overlap this sync across all leaves).
            items = {key: _to_host(tree) for key, tree in items.items()}
        self._staged = (items, int(iter_idx), epoch_idx, mesh, rules,
                        zero_stage)
        self.captures += 1

    @property
    def staged_iter(self) -> Optional[int]:
        return self._staged[1] if self._staged is not None else None

    def discard(self) -> None:
        """Drop the staged capture without writing (run teardown — the
        durable destroy-path snapshot supersedes it)."""
        self._staged = None

    # -- cold path -----------------------------------------------------------

    def flush(self, reason: str = "preemption") -> Optional[str]:
        """Write the staged capture as a minimal committed snapshot;
        returns its path, or ``None`` when nothing is staged (idempotent —
        a second SIGTERM finds the stage empty and does nothing)."""
        staged, self._staged = self._staged, None
        if staged is None:
            return None
        items, iter_idx, epoch_idx, mesh, rules, zero_stage = staged
        path = os.path.abspath(
            os.path.join(self._root, self._format.format(iter_idx))
        )
        try:
            host_items = {key: _to_host(tree) for key, tree in items.items()}
            self._write(path, host_items, iter_idx, epoch_idx, mesh, rules,
                        zero_stage)
        except Exception:
            # A failing flush must never mask the preemption path (the
            # grace-window durable save may still land).
            self._logger.warning(
                "emergency flush to %s failed", path, exc_info=True
            )
            return None
        self.flushes += 1
        self._logger.warning(
            "emergency snapshot (%s, iter %d) -> %s", reason, iter_idx, path
        )
        self._prune(keep_path=path)
        return path

    def _write(
        self,
        path: str,
        items: Dict[str, Any],
        iter_idx: int,
        epoch_idx: Optional[int],
        mesh: Any,
        rules: Any,
        zero_stage: Optional[int] = None,
    ) -> None:
        import orbax.checkpoint as ocp

        from rocket_tpu.persist.orbax_io import _to_saveable

        # Transient sync checkpointer — same reasoning as CheckpointIO's
        # restore path: the shared async one must not have its item keys
        # rebound, and a flush must be durable before the handler returns.
        with ocp.Checkpointer(ocp.CompositeCheckpointHandler()) as ckptr:
            ckptr.save(
                path,
                args=ocp.args.Composite(
                    **{
                        key: ocp.args.StandardSave(_to_saveable(tree))
                        for key, tree in items.items()
                    }
                ),
                force=True,
            )
        manifest = integrity.build_manifest(
            items, iter_idx=iter_idx, epoch_idx=epoch_idx,
            mesh=mesh, rules=rules, zero_stage=zero_stage,
        )
        if jax.process_index() == 0:
            with open(os.path.join(path, MARKER), "w") as fh:
                fh.write("")
            integrity.write_manifest(path, manifest)
            integrity.write_commit_marker(path)

    def _prune(self, keep_path: str) -> None:
        if jax.process_index() != 0:
            return
        parent = os.path.dirname(keep_path)
        dirs = integrity._snapshot_dirs(
            os.path.dirname(parent), os.path.basename(parent)
        )  # newest first
        for _, victim in dirs[self._keep:]:
            if os.path.abspath(victim) != os.path.abspath(keep_path):
                shutil.rmtree(victim, ignore_errors=True)


# -- active-tier registry (the SIGTERM orchestrator's flush hook) ------------

_ACTIVE: List[EmergencyTier] = []


def activate(tier: EmergencyTier) -> EmergencyTier:
    if tier not in _ACTIVE:
        _ACTIVE.append(tier)
    return tier


def deactivate(tier: EmergencyTier) -> None:
    try:
        _ACTIVE.remove(tier)
    except ValueError:
        pass


def active_tiers() -> List[EmergencyTier]:
    return list(_ACTIVE)


def flush_active(reason: str = "sigterm") -> List[str]:
    """Flush every active tier (the checkpoint SIGTERM orchestrator's
    second step); idempotent — flushed tiers have nothing staged."""
    written = []
    for tier in list(_ACTIVE):
        path = tier.flush(reason)
        if path is not None:
            written.append(path)
    return written
