"""Goodput and retrace accounting — where the wall time actually went.

The tracer (``observe/trace.py``) answers "what happened, in order"; this
module answers the two production questions layered on top of it
(PAPERS.md: arxiv 2605.25645 frames serving health as goodput + compile
overhead; 2605.23066 does the same for checkpointing):

- :class:`RetraceLedger` — every jit dispatch edge in the repo is already
  funneled through a named chokepoint (``engine/step.py``'s
  ``_AnnotatedStep``, ``models/generate.py``'s ``_spec_*`` wrappers).
  :func:`ledger_call` wraps those edges: each call compares the
  executable's ``_cache_size()`` before/after, so every trace/compile is
  recorded (name, triggering arg shapes/dtypes, wall time) and — once an
  edge has gone warm — an UNEXPECTED retrace escalates into one
  :class:`~rocket_tpu.observe.recorder.FlightRecorder` dump naming the
  executable and the offending shapes.  This promotes the test-only
  "zero new jit traces" assertions into a runtime sentinel.
- :class:`GoodputLedger` — partitions run wall time into named buckets
  (productive step, compile, host-blocked, data-starved, checkpoint,
  watchdog rebuild, preemption loss).  Buckets plus the explicit
  ``unattributed`` remainder sum to the measured run window exactly;
  the Launcher persists the snapshot as ``<project>/goodput.json`` and
  prints the table at launch end.

Design constraints mirror the tracer's: the disarmed path is one set
lookup and one attribute check; the armed warm path adds two ``_cache_size()`` calls and
two clock reads per dispatch (<5% per train iter / serve round — enforced
by ``TestGoodputGuard``); shape stringification happens only on the cold
compile path.  Nothing here ever raises into the dispatch it wraps.

Device telemetry lives here too: :func:`memory_watermarks`
(``device.memory_stats()`` counters — a guarded no-op on CPU, which has
no memory stats to report).  Utilization is the benchmark's to compute
(``train_step_mfu``), from a device trace and counted FLOPs.

Start-up: the first call at each edge (trace + lower + compile or cache
read) is recorded by :func:`ledger_call` as a ``startup/first_dispatch``
phase of the process's start-up record (``observe/trace.py``), whether or
not the sentinel is armed: one set lookup per call on the disarmed path.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from rocket_tpu.observe.trace import get_startup, get_tracer

LOG = logging.getLogger("rocket_tpu.observe.ledger")


# ---------------------------------------------------------------------------
# Retrace ledger
# ---------------------------------------------------------------------------


def _arg_signature(args: tuple, kwargs: dict, limit: int = 64) -> str:
    """Shape/dtype string for the triggering arguments — cold path only
    (called once per compile, never on a warm dispatch)."""
    try:
        import jax

        leaves = jax.tree_util.tree_leaves((args, kwargs))
    except Exception:
        leaves = list(args) + list(kwargs.values())
    parts: List[str] = []
    for leaf in leaves[:limit]:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(f"{dtype}{list(shape)}")
        else:
            parts.append(type(leaf).__name__)
    if len(leaves) > limit:
        parts.append(f"...+{len(leaves) - limit}")
    return ",".join(parts)


@dataclass
class CompileRecord:
    """One observed trace/compile at a ledgered jit edge."""

    name: str
    signature: str
    wall_ms: float
    retrace: bool  # True = the edge was already warm (post-warmup)
    cache_hit: bool = False  # served from the persistent compile cache
    ts: float = field(default_factory=time.time)


_cc_state: Any = None


def _cc_hit_count() -> int:
    """Persistent-compile-cache hit counter, 0 when the tier is absent.
    Sampled on the dispatch hot path, so it must never raise and must be
    cheap: a lock-free dict read (the GIL makes the int read atomic; a
    one-tick-stale value only shifts which record a concurrent hit
    stamps, never loses it)."""
    global _cc_state
    if _cc_state is None:
        try:
            from rocket_tpu.tune import compile_cache

            _cc_state = compile_cache._state
        except Exception:
            return 0
    return int(_cc_state.get("hits", 0))


class RetraceLedger:
    """Watches the named jit edges for cache growth.

    Lifecycle of an edge: every dispatch that grows the executable cache
    is recorded as a :class:`CompileRecord`; the first dispatch that does
    NOT grow it marks the edge *warm*.  Cache growth on a warm edge is a
    retrace — expected for edges registered via :meth:`exempt` (batcher
    prefill/admit edges legitimately retrace per prompt length) or inside
    an :meth:`expect_compile` scope (the serve loop's deliberate inline
    n_draft compile), and a sentinel event otherwise: one tracer instant
    plus one flight-recorder dump per distinct (edge, signature), so an
    injected shape bug produces exactly one dump, not a dump per step.
    """

    def __init__(self, capacity: int = 256) -> None:
        self.armed = False
        self._records: deque = deque(maxlen=int(capacity))
        self._warm: set = set()
        self._exempt: set = set()
        self._expected: Dict[str, int] = {}
        self._dumped: set = set()
        self._lock = threading.Lock()
        self._recorder: Optional[Any] = None
        self.compiles = 0
        self.retraces = 0
        self.sentinel_dumps = 0
        self.cache_hits = 0

    # -- configuration --------------------------------------------------

    def exempt(self, *names: str) -> None:
        """Mark edges whose post-warmup retraces are legitimate (shape
        polymorphism by design, e.g. per-prompt-length prefill)."""
        self._exempt.update(names)

    def set_recorder(self, recorder: Optional[Any]) -> None:
        """Explicit dump sink; defaults to the process-global
        ``active_recorder()`` when unset."""
        self._recorder = recorder

    def expect_compile(self, name: str) -> "_ExpectCompile":
        """Scope in which a compile at ``name`` is deliberate (the serve
        loop growing its n_draft ladder inline).  Reentrant."""
        return _ExpectCompile(self, name)

    def reset(self) -> None:
        with self._lock:
            self._records.clear()
            self._warm.clear()
            self._dumped.clear()
            self._expected.clear()
            self.compiles = 0
            self.retraces = 0
            self.sentinel_dumps = 0
            self.cache_hits = 0

    # -- the dispatch wrapper (hot path when armed) ---------------------

    def call(self, fn: Callable, name: str, *args: Any, **kwargs: Any) -> Any:
        cache_size = getattr(fn, "_cache_size", None)
        if cache_size is None:
            return fn(*args, **kwargs)
        try:
            before = cache_size()
        except Exception:
            return fn(*args, **kwargs)
        hits_before = _cc_hit_count()
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        try:
            grew = cache_size() > before
        except Exception:
            return out
        if not grew:
            if name not in self._warm:
                self._warm.add(name)
            return out
        # Cold path from here down: a trace/compile happened.
        wall_s = time.perf_counter() - t0
        self._on_compile(name, args, kwargs, wall_s,
                         cache_hit=_cc_hit_count() > hits_before)
        return out

    def _on_compile(self, name: str, args: tuple, kwargs: dict,
                    wall_s: float, cache_hit: bool = False) -> None:
        sig = _arg_signature(args, kwargs)
        retrace = name in self._warm
        rec = CompileRecord(name, sig, wall_s * 1e3, retrace, cache_hit)
        tracer = get_tracer()
        with self._lock:
            self._records.append(rec)
            self.compiles += 1
            if retrace:
                self.retraces += 1
            if cache_hit:
                self.cache_hits += 1
        tracer.instant("ledger/compile", executable=name, shapes=sig,
                       wall_ms=rec.wall_ms, retrace=retrace,
                       cache_hit=cache_hit)
        tracer.counter("ledger/compiles", self.compiles, executable=name)
        get_goodput().add("compile", wall_s, nested=True)
        if not retrace:
            return
        if name in self._exempt or self._expected.get(name, 0) > 0:
            return
        self._sentinel(name, sig, rec)

    def _sentinel(self, name: str, sig: str, rec: CompileRecord) -> None:
        with self._lock:
            key = (name, sig)
            if key in self._dumped:
                return
            self._dumped.add(key)
            self.sentinel_dumps += 1
        recorder = self._recorder
        if recorder is None:
            from rocket_tpu.observe.recorder import active_recorder

            recorder = active_recorder()
        # The instant must land in the ring the dump will serialize, so
        # the flight artifact itself names the executable and shapes.
        tracer = recorder.tracer if recorder is not None else get_tracer()
        tracer.instant("ledger/retrace", executable=name, shapes=sig,
                       wall_ms=rec.wall_ms)
        LOG.warning(
            "unexpected post-warmup retrace of %s (shapes: %s, %.1fms)",
            name, sig, rec.wall_ms,
        )
        if recorder is None:
            return
        try:
            recorder.dump(f"retrace-{name}")
        except Exception:
            pass  # a failing dump must never fail the dispatch it observed

    # -- inspection -----------------------------------------------------

    def records(self) -> List[CompileRecord]:
        return list(self._records)

    def snapshot(self) -> Dict[str, float]:
        return {
            "compiles": float(self.compiles),
            "retraces": float(self.retraces),
            "sentinel_dumps": float(self.sentinel_dumps),
            "warm_edges": float(len(self._warm)),
            "cache_hits": float(self.cache_hits),
        }


class _ExpectCompile:
    """Reentrant scope marking compiles at one edge as deliberate."""

    __slots__ = ("_ledger", "_name")

    def __init__(self, ledger: RetraceLedger, name: str) -> None:
        self._ledger = ledger
        self._name = name

    def __enter__(self) -> "_ExpectCompile":
        exp = self._ledger._expected
        exp[self._name] = exp.get(self._name, 0) + 1
        return self

    def __exit__(self, *exc: Any) -> bool:
        exp = self._ledger._expected
        exp[self._name] = max(0, exp.get(self._name, 0) - 1)
        return False


_RETRACE = RetraceLedger()


def get_retrace_ledger() -> RetraceLedger:
    return _RETRACE


# Edges (or, for a shape-polymorphic edge, edge and shape) whose first
# call this process made during start-up.
_DISPATCHED: set = set()


def ledger_call(fn: Callable, name: str, *args: Any, _shape: Any = None,
                **kwargs: Any) -> Any:
    """The jit-edge chokepoint: dispatch ``fn`` under the retrace ledger.

    Until the start-up line is logged, the first call at an edge is
    start-up (trace, lower, compile or a cache read) and is recorded as
    ``startup/first_dispatch`` whether or not the ledger is armed; an
    edge that legitimately compiles once per shape
    (``generate/spec_admit``, per prompt length) passes that shape as
    ``_shape`` and is recorded once per shape.  Once the line is logged
    the record is closed: a later cold edge or shape is the ledger's
    business alone, and neither the record nor ``_DISPATCHED`` grows with
    the traffic.  Disarmed (the default) this costs one attribute check
    on top of the call after start-up, and one set lookup more during
    it; armed, it adds two cache-size reads and two clock reads on the
    warm path.  Every named dispatch edge in the repo routes through
    here.
    """
    startup = get_startup()
    if not startup.logged:
        key = name if _shape is None else (name, _shape)
        if key not in _DISPATCHED:
            _DISPATCHED.add(key)
            hits = _cc_hit_count()
            shape = {} if _shape is None else {"shape": _shape}
            phase = startup.phase("startup/first_dispatch", edge=name,
                                  **shape)
            with phase:
                try:
                    return _dispatch(fn, name, args, kwargs)
                finally:
                    phase.add(cache_hit=_cc_hit_count() > hits)
    return _dispatch(fn, name, args, kwargs)


def _dispatch(fn: Callable, name: str, args: tuple, kwargs: dict) -> Any:
    if not _RETRACE.armed:
        return fn(*args, **kwargs)
    return _RETRACE.call(fn, name, *args, **kwargs)


def expect_compile(name: str) -> _ExpectCompile:
    """``with expect_compile("generate/spec_round"): ...`` on the global
    ledger — the serve loop's deliberate inline-compile scope."""
    return _RETRACE.expect_compile(name)


# ---------------------------------------------------------------------------
# Goodput ledger
# ---------------------------------------------------------------------------


class GoodputLedger:
    """Partitions a run window into named wall-time buckets.

    Accounting identity: ``sum(buckets) + unattributed == total`` exactly
    (``unattributed`` is computed as the remainder at snapshot time), so
    the ISSUE's "buckets sum to wall time within 1%" check reduces to
    "unattributed stays small".

    ``productive`` is wall time during which the device had work;
    ``host_blocked`` is wall time during which it had run dry and waited
    for the host to hand it the next step.  In the default loop the
    ``Looper`` asks at the top of each iteration, without blocking,
    whether the previous step has finished: if so, the time from there
    until the step's dispatch returns (:meth:`mark_dispatch`) is
    ``host_blocked``, and the rest of the iteration ``productive``; if
    not, the whole iteration is ``productive``.  Under ``readback_lag``
    the backpressure wait is ``productive`` and the dispatch gap
    ``host_blocked``.

    Double-counting discipline: ``compile``, ``data_starved``,
    ``checkpoint``, and ``watchdog_rebuild`` seconds are *nested* inside
    the looper's iteration.  Each nested add also bumps a running
    ``nested_seconds`` counter; the Looper subtracts the per-cycle delta
    of that counter from what it books, so one second of compile is
    never also a second of host-blocked or productive.

    ``preemption_loss`` is a *reported* bucket, not a measured one: the
    elastic-resume path calls :meth:`note_preemption_loss` with the
    replayed-step estimate, because the time lost happened in a process
    that no longer exists.
    """

    BUCKETS: Tuple[str, ...] = (
        "productive", "compile", "host_blocked", "data_starved",
        "checkpoint", "watchdog_rebuild", "preemption_loss",
        "serve/kvstore/wire", "swap", "offload_wait",
    )
    NESTED: Tuple[str, ...] = (
        "compile", "data_starved", "checkpoint", "watchdog_rebuild",
        "serve/kvstore/wire", "swap", "offload_wait",
    )

    def __init__(self) -> None:
        self.armed = False
        self._lock = threading.Lock()
        self._t0: Optional[float] = None
        self._t_end: Optional[float] = None
        self._buckets: Dict[str, float] = {b: 0.0 for b in self.BUCKETS}
        self._nested = 0.0
        # perf_counter and nested_seconds at the return of the newest
        # step dispatch (engine/step.py stamps it; the Looper reads it)
        self.dispatched_at = 0.0
        self.nested_at_dispatch = 0.0

    # -- run window -----------------------------------------------------

    def start_run(self) -> None:
        """(Re)open the measured window; arms the ledger."""
        with self._lock:
            self._t0 = time.perf_counter()
            self._t_end = None
            self._buckets = {b: 0.0 for b in self.BUCKETS}
            self._nested = 0.0
        self.armed = True

    def end_run(self) -> None:
        """Close the window (idempotent); the snapshot total freezes."""
        with self._lock:
            if self._t0 is not None and self._t_end is None:
                self._t_end = time.perf_counter()

    # -- accounting (hot-ish path: once per cycle / save / stall) -------

    def add(self, bucket: str, seconds: float, nested: bool = False) -> None:
        if not self.armed or seconds <= 0.0:
            return
        with self._lock:
            self._buckets[bucket] = self._buckets.get(bucket, 0.0) + seconds
            if nested:
                self._nested += seconds

    def timed(self, bucket: str) -> "_TimedBucket":
        """``with goodput.timed("checkpoint"): ...`` — times the body into
        ``bucket`` (no-op when disarmed; nested-ness follows ``NESTED``)."""
        return _TimedBucket(self, bucket, bucket in self.NESTED)

    def mark_dispatch(self) -> None:
        """A step's dispatch has just returned: from here the device has
        work.  One clock read; called by every ``_AnnotatedStep``."""
        self.dispatched_at = time.perf_counter()
        self.nested_at_dispatch = self._nested

    def nested_seconds(self) -> float:
        """Running total of nested-bucket seconds — the Looper diffs this
        per cycle to de-overlap its dispatch gap."""
        return self._nested

    def note_preemption_loss(self, seconds: float,
                             steps_replayed: int = 0) -> None:
        """Report wall time lost to a preemption (steps replayed after an
        elastic resume, estimated by the restore path)."""
        self.add("preemption_loss", seconds)
        if steps_replayed:
            get_tracer().instant("goodput/preemption_loss",
                                 seconds=seconds,
                                 steps_replayed=steps_replayed)

    # -- inspection / persistence ---------------------------------------

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            if self._t0 is None:
                total = 0.0
            else:
                end = self._t_end if self._t_end is not None \
                    else time.perf_counter()
                total = max(0.0, end - self._t0)
            out = {f"{b}_s": v for b, v in self._buckets.items()}
        attributed = sum(out.values())
        out["unattributed_s"] = max(0.0, total - attributed)
        out["total_s"] = total
        out["goodput_frac"] = (
            out["productive_s"] / total if total > 0.0 else 0.0
        )
        return out

    def save(self, path: str) -> str:
        snap = self.snapshot()
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(snap, f, indent=2, sort_keys=True)
        return path

    def table(self) -> str:
        """Human-readable bucket table, largest first — what the Launcher
        logs at launch end."""
        snap = self.snapshot()
        total = snap["total_s"]
        lines = [f"goodput over {total:.2f}s "
                 f"({100.0 * snap['goodput_frac']:.1f}% productive):"]
        rows = [(b, snap[f"{b}_s"]) for b in self.BUCKETS]
        rows.append(("unattributed", snap["unattributed_s"]))
        for name, secs in sorted(rows, key=lambda r: -r[1]):
            if secs <= 0.0:
                continue
            pct = 100.0 * secs / total if total > 0.0 else 0.0
            lines.append(f"  {name:<16} {secs:10.3f}s  {pct:5.1f}%")
        return "\n".join(lines)


class _TimedBucket:
    __slots__ = ("_ledger", "_bucket", "_nested", "_t0")

    def __init__(self, ledger: GoodputLedger, bucket: str,
                 nested: bool) -> None:
        self._ledger = ledger
        self._bucket = bucket
        self._nested = nested

    def __enter__(self) -> "_TimedBucket":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> bool:
        self._ledger.add(self._bucket, time.perf_counter() - self._t0,
                         nested=self._nested)
        return False


_GOODPUT = GoodputLedger()


def get_goodput() -> GoodputLedger:
    """The process-wide goodput ledger instrumented code feeds."""
    return _GOODPUT


def goodput_dump_writer(dump_dir: str) -> None:
    """Recorder dump-writer hook: drop the current goodput snapshot into
    every flight dump (registered by the Launcher via
    ``observe.recorder.add_dump_writer`` — idempotent)."""
    _GOODPUT.save(os.path.join(dump_dir, "goodput.json"))


def arm_ledgers(recorder: Optional[Any] = None) -> None:
    """Arm both ledgers for a run (what ``Launcher.setup`` calls).

    Arming RESETS the retrace ledger (counts, warm set, dump dedup —
    ``exempt`` registrations survive): edge warm-state is keyed by NAME,
    so a second run in the same process compiling a fresh model under a
    name the previous run warmed must start cold, not read as a retrace.
    ``GoodputLedger.start_run`` resets its buckets for the same reason.
    """
    _RETRACE.reset()
    _DISPATCHED.clear()
    _RETRACE.armed = True
    if recorder is not None:
        _RETRACE.set_recorder(recorder)
    _GOODPUT.start_run()


def disarm_ledgers() -> None:
    _RETRACE.armed = False
    _RETRACE.set_recorder(None)
    _GOODPUT.end_run()
    _GOODPUT.armed = False


# ---------------------------------------------------------------------------
# Memory telemetry
# ---------------------------------------------------------------------------

def memory_watermarks(tracer: Optional[Any] = None) -> Dict[str, float]:
    """Per-device ``memory_stats()`` watermarks as ``device/mem_*``
    counters.  CPU backends report no memory stats — the contract there
    is *emit nothing*, never crash."""
    out: Dict[str, float] = {}
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return out
    for dev in devices:
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                    "largest_alloc_size"):
            if key in stats:
                out[f"device/mem_{key}/d{dev.id}"] = float(stats[key])
    if out:
        t = tracer if tracer is not None else get_tracer()
        for name, value in out.items():
            t.counter(name, value)
    return out
