"""Looper — the iteration loop over one cycle (train epoch / eval pass).

Capability parity: reference ``rocket/core/loop.py:25-323``:

- ``run_every`` gating: the cycle runs only when ``epoch % run_every == 0``
  (``loop.py:109-113``) — e.g. evaluate every 5th epoch;
- repeats inference from child ``Dataset`` totals (``loop.py:312-319``);
- the ``attrs.looper`` protocol: ``{repeats, state, terminate, tag,
  grad_enabled}`` published at ``set`` (``loop.py:152-158``), removed at
  ``reset`` (``loop.py:180``);
- per-iteration: clear ``attrs.batch``, dispatch to children in priority
  order, honor the termination vote (``loop.py:213-226``);
- no nested Loopers (``loop.py:287-292``);
- ``iter_idx`` in the checkpoint state (``loop.py:231-263``).

TPU-first: the reference toggles ``torch.set_grad_enabled`` around the body
(``loop.py:217``) — a global mutable switch.  Here train-vs-eval is a
*declarative* flag on the blackboard (``attrs.looper.grad_enabled``) that the
Module reads to pick its jitted train or eval step; nothing global mutates.
The tqdm status line reads device scalars lazily and refreshes every
``refresh_every`` iterations so progress display never stalls the async
dispatch queue.

**Non-blocking mode** (``readback_lag=k``, k >= 1): the loop becomes
dispatch-and-go.  Each iteration's ``attrs.step_logs`` scalars are staged
with ``copy_to_host_async`` (the DivergenceSentinel's delayed-read
discipline) into a window of k in-flight iterations; the value read back
each iteration is the one staged k iterations ago, whose transfer has long
landed.  That read doubles as the **bounded in-flight window**: it blocks
only when the host has run more than k steps ahead of the device, which is
exactly the backpressure that keeps the dispatch queue finite.  The lagged
host floats are published as ``attrs.looper.lagged_logs`` for observers
(Throughput credits completed steps off it; the status bar formats it) so
nothing calls ``block_until_ready`` mid-epoch — syncs happen only at epoch
boundaries (cycle reset), checkpoint points (the save's D2H copy), and stop
votes.  At cycle reset the window is *drained*, not dropped: the
not-yet-consumed tail is materialized (free — the boundary is a sync
point) and published as ``attrs.looper.drained_logs`` so the final k
steps' logs reach observers, and Throughput credits its remaining
in-flight steps off it instead of under-counting k steps per cycle.  The per-iteration **host dispatch gap** (host time spent outside
the backpressure wait — the time the chip could sit idle between steps) is
measured every iteration and exposed as :attr:`Looper.last_dispatch_gap_ms`.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Iterable, Optional

from rocket_tpu.core.attributes import Attributes
from rocket_tpu.core.capsule import Capsule
from rocket_tpu.core.dispatcher import Dispatcher
from rocket_tpu.observe.ledger import get_goodput, memory_watermarks
from rocket_tpu.observe.trace import get_startup, span

try:
    from termcolor import colored
except ImportError:  # pragma: no cover

    def colored(text: str, *args: Any, **kwargs: Any) -> str:
        return text


def _first_array(logs: Any, default: Any = None) -> Any:
    """A device leaf of ``step_logs`` whose readiness tells whether the
    step that produced it has finished; ``default`` when no step ran."""
    if logs is None:
        return default
    for value in dict(logs).values():
        if hasattr(value, "is_ready"):
            return value
    return default


def _is_ready(leaf: Any) -> bool:
    """Has the step that produced ``leaf`` finished?  Never blocks
    (``jax.Array.is_ready`` polls the buffer's event)."""
    try:
        return bool(leaf.is_ready())
    except Exception:
        return True  # a deleted buffer's step is long over


class _LagWindow:
    """A k-deep window of staged ``step_logs`` snapshots.

    ``push`` stages the current iteration's device scalars with
    ``copy_to_host_async`` (starting their D2H transfers immediately) and,
    once the window holds more than ``lag`` entries, materializes the
    OLDEST one to host floats.  Materializing blocks only if that step —
    dispatched ``lag`` iterations ago — has not finished yet, which is the
    loop's backpressure point; in steady state the transfer landed long ago
    and the floats are free (the sentinel's ``_stage_and_read`` pattern,
    widened from one scalar to the whole logs dict).
    """

    def __init__(self, lag: int) -> None:
        self.lag = max(1, int(lag))
        self._window: deque = deque()

    def __len__(self) -> int:
        return len(self._window)

    @staticmethod
    def _stage(logs: Any) -> dict:
        staged = {}
        for key, value in dict(logs).items():
            start = getattr(value, "copy_to_host_async", None)
            if start is not None:
                try:
                    start()
                except Exception:
                    pass  # already on host (numpy / python scalar)
            staged[key] = value
        return staged

    @staticmethod
    def _materialize(staged: dict) -> Attributes:
        out = Attributes()
        for key, value in staged.items():
            try:
                out[key] = float(value)  # free: transfer landed k steps ago
            except (TypeError, ValueError):
                out[key] = value  # host-side passthrough (bools, strings)
        return out

    def push(self, logs: Any) -> Optional[Attributes]:
        """Stage ``logs``; return the (k+1)-iterations-old snapshot as host
        floats once the window is full, else ``None`` (still filling)."""
        self._window.append(self._stage(logs))
        if len(self._window) <= self.lag:
            return None
        return self._materialize(self._window.popleft())

    def drain(self) -> list:
        """Epoch-boundary / stop-vote sync point: materialize every
        remaining snapshot (oldest first) and empty the window.  Blocking
        here is free — the caller drains only at a declared sync boundary,
        where the device is waited on anyway — and the window must not
        survive the boundary: the staged buffers may be donated away by
        the next cycle's first step (the same reason the sentinel drops
        its staged scalars at ``reset``)."""
        out = []
        while self._window:
            out.append(self._materialize(self._window.popleft()))
        return out


class Looper(Dispatcher):
    """Parameters
    ----------
    capsules:
        Children dispatched each iteration (Dataset, Module, Meter, Tracker,
        Checkpointer, ...).
    grad_enabled:
        ``True`` = training cycle, ``False`` = evaluation cycle (reference
        ``loop.py:70-89``).
    repeats:
        Iterations per cycle; ``None`` infers from child Dataset totals
        (reference ``loop.py:294-319``).
    run_every:
        Run the cycle only on epochs divisible by this (``loop.py:91-113``).
    tag:
        Progress-bar label (default TRAIN/EVAL by grad mode).
    readback_lag:
        ``k >= 1`` arms the non-blocking loop: loss/metric host readback is
        deferred by ``k`` iterations (the sentinel's delayed-read pattern)
        and at most ``k`` steps stay in flight (the lagged read is the
        backpressure bound).  ``0`` (default) is the synchronous loop.
        Results are bit-identical either way — only host-side readback
        timing changes, never the dispatched program or its order.
    """

    def __init__(
        self,
        capsules: Iterable[Capsule] = (),
        grad_enabled: bool = True,
        repeats: Optional[int] = None,
        run_every: int = 1,
        tag: Optional[str] = None,
        progress: bool = True,
        refresh_every: int = 10,
        readback_lag: int = 0,
        statefull: bool = True,
        priority: int = 1000,
        logger: Optional[Any] = None,
    ) -> None:
        super().__init__(
            capsules=capsules, statefull=statefull, priority=priority, logger=logger
        )
        self._grad_enabled = grad_enabled
        self._repeats = repeats
        self._explicit_repeats = repeats
        if run_every < 1:
            raise ValueError("run_every must be >= 1")
        self._run_every = run_every
        self._tag = tag or ("TRAIN" if grad_enabled else "EVAL")
        self._progress = progress
        self._refresh_every = max(1, refresh_every)
        if readback_lag < 0:
            raise ValueError("readback_lag must be >= 0")
        self._readback_lag = int(readback_lag)
        self._lag_window: Optional[_LagWindow] = None
        self._lagged_state: Optional[Attributes] = None
        self._gap_sum = 0.0
        self._gap_count = 0
        self._iter_idx = 0

    def guard(self) -> None:
        super().guard()
        for capsule in self._capsules:
            if isinstance(capsule, Looper):
                raise RuntimeError(
                    "nested Loopers are not allowed (reference loop.py:287-292)"
                )

    # -- cycle gating --------------------------------------------------------

    def run_if_needed(self, attrs: Optional[Attributes]) -> bool:
        epoch = 0
        if attrs is not None and attrs.launcher is not None:
            epoch = int(attrs.launcher.epoch_idx or 0)
        return epoch % self._run_every == 0

    def infer_repeats(self) -> Optional[int]:
        """Sum of child Dataset totals (reference ``loop.py:294-319``).
        ``None`` (= run until the stream's termination vote) when a child
        Dataset is streaming and so has no total."""
        from rocket_tpu.data.dataset import Dataset

        datasets = [c for c in self._capsules if isinstance(c, Dataset)]
        if not datasets:
            raise RuntimeError(
                f"Looper[{self._tag}]: repeats not given and no child Dataset "
                f"to infer them from"
            )
        totals = [c.total for c in datasets]
        if any(t is None for t in totals):
            return None  # streaming: iterate until exhaustion
        return sum(totals)

    # -- events --------------------------------------------------------------

    def set(self, attrs: Optional[Attributes] = None) -> None:
        attrs = attrs if attrs is not None else Attributes()
        if not self.run_if_needed(attrs):
            return
        if self._explicit_repeats is None:
            self._repeats = self.infer_repeats()
        attrs.looper = Attributes(
            repeats=self._repeats,
            state=Attributes(),
            terminate=False,
            tag=self._tag,
            grad_enabled=self._grad_enabled,
            # async-loop protocol: observers (Throughput, user capsules)
            # read the lag and, per iteration, the k-lagged host floats.
            readback_lag=self._readback_lag,
            lagged_logs=None,
            drained_logs=None,
        )
        self._lag_window = (
            _LagWindow(self._readback_lag) if self._readback_lag > 0 else None
        )
        self._lagged_state = None
        self._gap_sum = 0.0
        self._gap_count = 0
        super().set(attrs)

    def reset(self, attrs: Optional[Attributes] = None) -> None:
        if attrs is None or attrs.looper is None:
            return
        looper = attrs.looper
        if self._lag_window is not None:
            # Cycle-end sync point: drain the in-flight readback tail and
            # publish it BEFORE dispatching children's reset, so the final
            # steps' logs reach observers (Throughput credits the remaining
            # in-flight steps off it; trackers see the last losses) instead
            # of vanishing with the window.  The tail is the final
            # iteration's popped snapshot — published after the last
            # dispatch, so no launch ever consumed it — followed by the
            # window's remaining entries, oldest first; it is moved out of
            # ``lagged_logs`` so a reset-time consumer can't double-count.
            drained = []
            if looper.get("lagged_logs") is not None:
                drained.append(looper.lagged_logs)
                looper.lagged_logs = None
            drained += self._lag_window.drain()
            looper.drained_logs = drained or None
        super().reset(attrs)
        del attrs.looper
        self._iter_idx = 0
        self._lagged_state = None

    @property
    def last_dispatch_gap_ms(self) -> Optional[float]:
        """Mean host dispatch gap of the current/most recent cycle, in ms:
        host time per iteration spent dispatching capsules — i.e. outside
        the lag window's backpressure wait — which is the time the chip
        sits idle between steps.  ``None`` before the first iteration."""
        if self._gap_count == 0:
            return None
        return self._gap_sum / self._gap_count * 1e3

    def launch(self, attrs: Optional[Attributes] = None) -> None:
        attrs = attrs if attrs is not None else Attributes()
        if not self.run_if_needed(attrs):
            return
        if attrs.looper is None:
            self.set(attrs)
        looper = attrs.looper
        bar = self._status_bar(looper.repeats)
        window = self._lag_window
        # Goodput accounting, hoisted per cycle: per iteration the armed
        # path adds two clock reads, one non-blocking readiness probe, one
        # nested-seconds diff and two bucket adds — bounded by the same
        # <5% guard as tracing.
        goodput = get_goodput()
        gp_armed = goodput.armed
        gp_iters = 0
        nested0 = 0.0
        # A leaf of the previous iteration's step_logs: ready = that step
        # has finished and the device has run dry.  None before the first
        # step, when the device has nothing either.
        prev_leaf = None
        # The start-up line goes out when the first step's dispatch has
        # returned (once a process: log_once sees to that).
        startup = get_startup()
        try:
            # repeats=None: unbounded streaming cycle, ended by the child
            # Dataset's termination vote when the stream exhausts.
            while looper.repeats is None or self._iter_idx < looper.repeats:
                # The span is the whole iteration, bookkeeping included, so the
                # iterations tile the host's timeline in a profiler trace.
                with span(f"looper/{self._tag}/iter", iter=self._iter_idx):
                    gap_t0 = time.perf_counter()
                    if gp_armed:
                        nested0 = goodput.nested_seconds()
                        dry = prev_leaf is None or _is_ready(prev_leaf)
                    attrs.batch = None
                    # Cleared WITH the batch: an iteration where no step runs
                    # (dataset exhausted on a resumed epoch) must not re-expose
                    # the previous iteration's logs to observers downstream
                    # (trackers, sentinels) as if a step had happened.
                    attrs.step_logs = None
                    self._launch_children(attrs)
                    # Host dispatch gap: everything above ran without waiting
                    # on the device (in async mode); the backpressure wait
                    # below is device time and deliberately NOT counted.
                    gap = time.perf_counter() - gap_t0
                    self._gap_sum += gap
                    self._gap_count += 1
                    if not startup.logged and attrs.step_logs is not None:
                        startup.log_once(self._logger)
                    if window is not None:
                        looper.lagged_logs = None
                        if attrs.step_logs is not None:
                            popped = window.push(attrs.step_logs)
                            if popped is not None:
                                # In-flight bound: materializing the snapshot
                                # staged k iterations ago blocks only when the
                                # host is > k steps ahead of the device.
                                looper.lagged_logs = popped
                                self._lagged_state = popped
                    if gp_armed:
                        cycle_wall = time.perf_counter() - gap_t0
                        nested_delta = goodput.nested_seconds() - nested0
                        if window is not None:
                            # Lag mode: the dispatch gap is host-side (less
                            # what the nested buckets — compile, data-
                            # starved, checkpoint — already claimed in it);
                            # the remainder is the backpressure wait, i.e.
                            # the device productively stepping.
                            blocked, nested_in = gap, nested_delta
                        elif not dry:
                            # The previous step was still running when this
                            # iteration began: the device had work throughout.
                            blocked, nested_in = 0.0, 0.0
                        elif goodput.dispatched_at >= gap_t0:
                            # The device had run dry and waited until this
                            # iteration's step was handed to it.
                            blocked = goodput.dispatched_at - gap_t0
                            nested_in = goodput.nested_at_dispatch - nested0
                        else:
                            # Dry, and no step dispatched: it stayed dry.
                            blocked, nested_in = cycle_wall, nested_delta
                        goodput.add("host_blocked",
                                    max(0.0, blocked - nested_in))
                        goodput.add("productive", max(
                            0.0, cycle_wall - blocked
                            - (nested_delta - nested_in)))
                        gp_iters += 1
                        prev_leaf = _first_array(attrs.step_logs, prev_leaf)
                    self._iter_idx += 1
                    if looper.terminate or (
                        self._runtime is not None
                        and self._runtime.stop_training
                    ):
                        # cycle vote OR run-level stop (preemption or
                        # divergence abort cast by a capsule outside this
                        # cycle's protocol)
                        break
                    if bar is not None:
                        bar.update(1)
                        if self._iter_idx % self._refresh_every == 0:
                            # Async mode: the postfix formats the k-lagged host
                            # floats — a refresh must never sync mid-epoch.
                            bar.set_postfix(
                                self._format_state(looper.state)
                                if window is None
                                else self._format_lagged(looper.state)
                            )
        finally:
            if bar is not None:
                bar.set_postfix(self._format_state(looper.state))
                bar.close()
            if gp_armed and gp_iters:
                # Cycle-boundary telemetry (already a sync point): device
                # memory watermarks.
                memory_watermarks()
        attrs.batch = None
        attrs.step_logs = None

    # -- progress ------------------------------------------------------------

    def _status_bar(self, repeats: int):
        if not self._progress:
            return None
        if self._runtime is not None and not self._runtime.is_main_process:
            return None
        from tqdm import tqdm

        color = "green" if self._grad_enabled else "cyan"
        return tqdm(
            total=repeats,
            initial=self._iter_idx,
            desc=colored(self._tag, color),
            leave=True,
            dynamic_ncols=True,
        )

    def _format_lagged(self, state: Optional[Attributes]) -> dict:
        """Non-blocking postfix: host-native entries of the looper state
        (strings the Throughput meter writes, python floats) format as
        usual; device scalars are replaced by their k-lagged host floats
        from the lag window, or skipped while the window is still filling.
        Nothing here can stall the dispatch queue."""
        lagged = self._lagged_state
        out = {}
        for key, value in (state or {}).items():
            if isinstance(value, (str, int, float, bool)):
                try:
                    out[key] = f"{float(value):.4g}"
                except (TypeError, ValueError):
                    out[key] = str(value)
            elif lagged is not None and key in lagged:
                try:
                    out[key] = f"{float(lagged[key]):.4g}"
                except (TypeError, ValueError):
                    out[key] = str(lagged[key])
        return out

    @staticmethod
    def _format_state(state: Optional[Attributes]) -> dict:
        if not state:
            return {}
        out = {}
        # The float() calls below are the loop's only host-fetch boundary;
        # the span makes the (throttled) sync attributable in a profiler
        # timeline instead of smearing into the next dispatch.
        with span("looper/host_fetch"):
            for key, value in state.items():
                try:
                    out[key] = f"{float(value):.4g}"  # device sync, throttled
                except (TypeError, ValueError):
                    out[key] = str(value)
        return out

    # -- state ---------------------------------------------------------------

    def state_dict(self) -> Attributes:
        return Attributes(iter_idx=self._iter_idx)

    def load_state_dict(self, state: Attributes) -> None:
        if not state:
            return
        # Schema-tolerant: warn-and-default on keys an older checkpoint
        # lacks instead of KeyError-ing the resume (ISSUE 2 satellite).
        value = state.get("iter_idx")
        if value is None:
            self._logger.warning(
                "checkpoint has no 'iter_idx' (older schema?) — keeping %d",
                self._iter_idx,
            )
            return
        self._iter_idx = int(value)
