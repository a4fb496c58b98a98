"""Profiling and throughput instrumentation.

The reference has none (SURVEY §5.1 — its only timing artifact is the tqdm
bar).  Here:

- :class:`Profiler` — a capsule that captures a ``jax.profiler`` trace
  (TensorBoard/Perfetto XPlane format) for a window of iterations, skipping
  warmup so compile time doesn't pollute the trace;
- :class:`Throughput` — per-iteration wall-clock + samples/sec (EMA),
  published to the loop status line and the tracker without ever forcing a
  device sync (wall-clock between launches measures the async dispatch
  pipeline's steady-state rate, which is the number that matters);
- :func:`annotate` — named trace spans for pipeline phases.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Any, Optional

import jax

from rocket_tpu.core.attributes import Attributes
from rocket_tpu.core.capsule import Capsule


class Profiler(Capsule):
    """Capture a profiler trace for iterations ``[start, start+count)`` of
    the first cycle it runs in.

    Output lands in ``<project>/logs/profile`` (or ``log_dir``) — open with
    TensorBoard's profile plugin or Perfetto.
    """

    def __init__(
        self,
        start: int = 10,
        count: int = 5,
        log_dir: Optional[str] = None,
        priority: int = 150,  # after compute, before Checkpointer
        logger: Optional[Any] = None,
    ) -> None:
        super().__init__(statefull=False, priority=priority, logger=logger)
        self._start = start
        self._count = count
        self._log_dir = log_dir
        self._iter = 0
        self._active = False
        self._done = False

    def setup(self, attrs: Optional[Attributes] = None) -> None:
        super().setup(attrs)
        if self._log_dir is None:
            base = self._runtime.logging_dir or "."
            self._log_dir = os.path.join(base, "profile")

    def launch(self, attrs: Optional[Attributes] = None) -> None:
        if self._done:
            return
        if not self._active and self._iter >= self._start:
            # '>=' not '==': a cycle boundary landing exactly on _start
            # (reset bumps nothing, but set/launch interleavings can skip
            # an iteration) must not silently lose the whole window.
            if self._runtime is not None and not self._runtime.is_main_process:
                # Non-main processes never capture — say so ONCE instead
                # of silently doing nothing every iteration (ISSUE 4
                # satellite), and mark done so the check stops.
                self._done = True
                self._logger.info(
                    "profiler: process %d is not the main process — "
                    "skipping trace capture", self._runtime.process_index,
                )
            else:
                try:
                    jax.profiler.start_trace(self._log_dir)
                except Exception:
                    # A failed start (e.g. a second start_trace elsewhere
                    # in the process) disables this Profiler instead of
                    # re-raising every remaining iteration.
                    self._done = True
                    self._logger.warning(
                        "profiler: start_trace failed — disabled",
                        exc_info=True,
                    )
                else:
                    self._active = True
                    self._logger.info(
                        "profiler trace started -> %s", self._log_dir
                    )
        elif self._active and self._iter >= self._start + self._count:
            self._stop()
        self._iter += 1

    def _stop(self) -> None:
        if not self._active:
            return
        # Flags first: whatever stop_trace does, this Profiler is finished
        # — a raising stop_trace must not leave _active=True (the next
        # reset/destroy would double-stop and mask the original error).
        self._active = False
        self._done = True
        try:
            jax.profiler.stop_trace()
        finally:
            self._logger.info("profiler trace written -> %s", self._log_dir)

    def reset(self, attrs: Optional[Attributes] = None) -> None:
        self._stop()

    def destroy(self, attrs: Optional[Attributes] = None) -> None:
        self._stop()
        super().destroy(attrs)


class Throughput(Capsule):
    """samples/sec + step wall-clock, EMA-smoothed, on the status line and
    tracker. Reads the batch's leading dim (global batch) from ``attrs.batch``.

    Under a non-blocking Looper (``readback_lag=k``), wall-clock between
    *dispatches* is the wrong denominator: the first k dispatches return in
    microseconds while the device is still filling the pipeline, so
    ``size/dt`` would report absurd rates for steps that have not finished.
    In lag mode samples are **counted at dispatch time** (every launch
    pushes the batch size onto an in-flight queue) but **timed against the
    lagged readback**: a window closes only when ``attrs.looper.
    lagged_logs`` lands — proof one more step actually completed — and the
    rate credits exactly that step's samples over the time since the
    previous readback.  Pipeline-fill dispatches therefore never inflate
    samples/sec, and nothing here syncs the device either way.  At cycle
    end the Looper drains its window into ``looper.drained_logs``; the
    steps still in flight are credited off it at ``reset`` so the count
    never silently drops the last k steps of a cycle.
    """

    def __init__(
        self,
        ema: float = 0.9,
        tag: str = "throughput",
        log_every: int = 50,
        priority: int = 300,  # after Module, before Tracker flush
        logger: Optional[Any] = None,
        clock: Optional[Any] = None,
    ) -> None:
        super().__init__(statefull=False, priority=priority, logger=logger)
        self._ema_factor = ema
        self._tag = tag
        self._log_every = log_every
        self._clock = clock or time.perf_counter  # injectable for tests
        self._last_time: Optional[float] = None
        self._ema: Optional[float] = None
        self._iter = 0          # within-cycle counter (log_every cadence)
        self._global_iter = 0   # record step: never resets, so a second
        # cycle's scalars don't overwrite the first's (last-write-wins in
        # TensorBoard) — the ImageLogger uses the same two-counter scheme
        self._last_dt: Optional[float] = None
        self._pending = False   # readings observed since the last record
        from collections import deque

        self._inflight: Any = deque()  # dispatched-not-yet-read-back sizes

    def set(self, attrs: Optional[Attributes] = None) -> None:
        # Full cycle-boundary reset — including ``_iter``: leaving it
        # nonzero skewed the ``log_every`` alignment of every later cycle
        # (a 30-iter cycle left ``_iter=30``; with ``log_every=50`` the
        # next cycle's first record then fired after 20 iterations and
        # drifted from there — ISSUE 4 satellite).
        self._last_time = None
        self._ema = None
        self._iter = 0
        self._last_dt = None
        self._pending = False
        self._inflight.clear()

    def launch(self, attrs: Optional[Attributes] = None) -> None:
        now = self._clock()
        looper = attrs.looper if attrs is not None else None
        lag = 0
        if looper is not None:
            lag = int(looper.get("readback_lag") or 0)
        if lag > 0:
            self._launch_lagged(attrs, looper, now)
            return
        if self._last_time is None:
            self._last_time = now
            return
        dt = now - self._last_time
        self._last_time = now
        batch = attrs.batch if attrs is not None else None
        size = _batch_size(batch)
        self._observe(attrs, looper, size, dt)

    def _launch_lagged(self, attrs: Attributes, looper: Any, now: float) -> None:
        """Lag-mode accounting: count at dispatch, time at readback."""
        size = _batch_size(attrs.batch)
        if size:
            self._inflight.append(size)
        if self._last_time is None:
            # The window opens at the FIRST dispatch: the device starts
            # working here, so the first readback's dt spans exactly one
            # completed step plus pipeline fill.
            self._last_time = now
            return
        if looper.get("lagged_logs") is None or not self._inflight:
            return  # nothing read back yet: count samples, don't time them
        dt = now - self._last_time
        self._last_time = now
        self._observe(attrs, looper, self._inflight.popleft(), dt)

    def _observe(
        self, attrs: Optional[Attributes], looper: Any, size: int, dt: float
    ) -> None:
        rate = size / dt if dt > 0 else 0.0
        self._ema = (
            rate
            if self._ema is None
            else self._ema_factor * self._ema + (1 - self._ema_factor) * rate
        )
        self._iter += 1
        self._global_iter += 1
        self._last_dt = dt
        self._pending = True
        if attrs is None:
            return
        if looper is not None and looper.state is not None:
            looper.state[self._tag] = f"{self._ema:,.0f}/s"
        if (
            attrs.tracker is not None
            and self._iter % self._log_every == 0
        ):
            self._record(attrs)

    def reset(self, attrs: Optional[Attributes] = None) -> None:
        looper = attrs.looper if attrs is not None else None
        drained = looper.get("drained_logs") if looper is not None else None
        if drained and self._inflight and self._last_time is not None:
            # Lag-mode cycle end: the Looper drained its readback window,
            # so the remaining in-flight steps are known complete — credit
            # their samples over the time since the last readback instead
            # of dropping them (which under-counted k steps every cycle).
            now = self._clock()
            size = 0
            for _ in range(min(len(drained), len(self._inflight))):
                size += self._inflight.popleft()
            if size and now > self._last_time:
                self._observe(attrs, looper, size, now - self._last_time)
            self._last_time = now
        # Cycle end: flush the sub-``log_every`` remainder so short loops
        # (repeats < log_every) still produce at least one throughput
        # scalar instead of none (ISSUE 4 satellite).
        if (
            self._pending
            and attrs is not None
            and attrs.tracker is not None
        ):
            self._record(attrs)

    def _record(self, attrs: Attributes) -> None:
        self._pending = False
        attrs.tracker.scalars.append(
            Attributes(
                step=self._global_iter,
                data={
                    f"{self._tag}/samples_per_sec": self._ema,
                    f"{self._tag}/step_ms": (self._last_dt or 0.0) * 1e3,
                },
            )
        )


def _batch_size(batch: Any) -> int:
    if batch is None:
        return 0
    leaves = jax.tree_util.tree_leaves(batch)
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape:
            return int(shape[0])
    return 0


@contextlib.contextmanager
def debug_mode(
    nans: bool = True,
    disable_jit: bool = False,
):
    """SURVEY §5.2 debug aid: NaN/Inf checking and optionally eager
    execution.  Use around ``launcher.launch()`` when hunting numerical or
    tracing bugs; combine with ``multihost.assert_equal`` for cross-host
    divergence checks."""
    stack = contextlib.ExitStack()
    if nans:
        jax.config.update("jax_debug_nans", True)
        stack.callback(lambda: jax.config.update("jax_debug_nans", False))
    if disable_jit:
        stack.enter_context(jax.disable_jit())
    try:
        yield
    finally:
        stack.close()
