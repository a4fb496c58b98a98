"""Host-RAM offload of ZeRO shard-owner optimizer state (engine tier).

ZeRO stages 1-3 already cut the per-chip optimizer state to ``O/N``; with
``zero_offload=True`` even that shard leaves HBM between steps.  The
discipline is the ``data/loader.py`` ``device_prefetch`` one applied to
opt state: a background thread runs the D2H writeback of step *k*'s
optimizer state and the H2D prefetch for step *k+1* while the main thread
dispatches step *k+1*'s forward/backward, so on the happy path the
transfer hides entirely behind compute and the ``offload_wait`` goodput
bucket stays near zero.

The Module drives it at each sync boundary::

    state = state.replace(opt_state=offloader.fetch(state.opt_state))
    state, logs = sync_step(state, batch)
    offloader.stash(state.opt_state)

``stash`` hands the fresh (device) opt state to the worker thread and
returns immediately; ``fetch`` joins the round trip — booking any wait
into the goodput ledger — and returns the device copy placed under the
plan's opt shardings.  Ordering makes donation safe even off-CPU: fetch
joins the previous round trip (D2H complete) before the next step can
donate the buffers the stash was reading.

The round trip is a pure memcpy pair (``jax.device_get`` →
``jax.device_put``): bitwise exact, and neither call is a ``jax.jit``
site, so the offload path adds zero trace-cache entries per step.

``synchronous=True`` is the serialized baseline: the same round trip,
run inline at ``fetch`` time.  No benchmark cell runs either (not
measured).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Optional

import jax

__all__ = ["ZeroOffloader"]


class ZeroOffloader:
    """Double-buffered host-RAM round trip for sharded optimizer state.

    Parameters
    ----------
    opt_shardings:
        Tree of :class:`jax.sharding.NamedSharding` matching the opt-state
        tree (``ShardingPlan.opt_shardings`` — the ZeRO shard domain); the
        H2D prefetch lands the state back exactly where the update step
        expects it.
    synchronous:
        Run the round trip inline at ``fetch`` instead of on the worker
        thread (the serialized baseline).
    """

    def __init__(self, opt_shardings: Any, synchronous: bool = False) -> None:
        self._opt_shardings = opt_shardings
        self._synchronous = bool(synchronous)
        self.rounds = 0
        self.total_wait = 0.0
        self._pending: Optional[Any] = None  # synchronous-mode stash
        self._ready: "queue.Queue" = queue.Queue(maxsize=1)
        self._work: "queue.Queue" = queue.Queue(maxsize=1)
        self._in_flight = False
        self._worker: Optional[threading.Thread] = None
        if not self._synchronous:
            self._worker = threading.Thread(
                target=self._run, name="zero-offload", daemon=True
            )
            self._worker.start()

    # -- round trip -----------------------------------------------------

    def _round_trip(self, opt_state: Any) -> Any:
        from rocket_tpu.observe.trace import get_tracer

        tracer = get_tracer()
        host = jax.device_get(opt_state)
        tracer.instant("offload/d2h", round=self.rounds)
        dev = jax.device_put(host, self._opt_shardings)
        jax.block_until_ready(dev)
        tracer.instant("offload/h2d", round=self.rounds)
        return dev

    def _run(self) -> None:
        while True:
            item = self._work.get()
            if item is None:
                return
            try:
                self._ready.put(self._round_trip(item))
            except Exception as exc:  # surfaced to the training thread
                self._ready.put(exc)

    # -- API ------------------------------------------------------------

    def stash(self, opt_state: Any) -> None:
        """Start the async D2H writeback + H2D prefetch of ``opt_state``.

        Returns immediately; the transfer overlaps whatever the caller
        dispatches next.  At most one round trip is in flight — the Module
        calls stash once per sync boundary, strictly after fetch.
        """
        if self._synchronous:
            self._pending = opt_state
            return
        if self._in_flight:
            raise RuntimeError(
                "ZeroOffloader.stash called with a round trip already in "
                "flight — fetch() must join it first"
            )
        self._work.put(opt_state)
        self._in_flight = True

    def fetch(self, fallback: Any) -> Any:
        """Join the in-flight round trip and return the prefetched device
        copy; ``fallback`` (the caller's current opt state) is returned
        untouched when nothing was stashed (first step of a run).

        Wait time — the prefetch failing to hide behind compute — is
        booked into the goodput ledger's ``offload_wait`` bucket (nested,
        like the other inside-the-dispatch-gap buckets).
        """
        from rocket_tpu.observe.ledger import get_goodput

        if self._synchronous:
            if self._pending is None:
                return fallback
            t0 = time.perf_counter()
            dev = self._round_trip(self._pending)
            self._pending = None
            dt = time.perf_counter() - t0
            self.rounds += 1
            self.total_wait += dt
            get_goodput().add("offload_wait", dt, nested=True)
            return dev
        if not self._in_flight:
            return fallback
        t0 = time.perf_counter()
        dev = self._ready.get()
        dt = time.perf_counter() - t0
        self._in_flight = False
        self.rounds += 1
        self.total_wait += dt
        get_goodput().add("offload_wait", dt, nested=True)
        if isinstance(dev, Exception):
            raise dev
        return dev

    def close(self) -> None:
        """Stop the worker thread (idempotent; pending work is joined)."""
        if self._worker is not None and self._worker.is_alive():
            if self._in_flight:
                self._ready.get()
                self._in_flight = False
            self._work.put(None)
            self._worker.join(timeout=5.0)
        self._worker = None
