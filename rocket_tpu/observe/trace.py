"""Structured host-side tracing — spans, counters, a bounded ring buffer,
Chrome-trace export, and cross-host timeline merge.

The profiler story so far captures DEVICE time (``Profiler`` wraps
``jax.profiler`` XPlane windows); this module captures the HOST side —
what the dispatch loop, the serve loop, and each capsule were doing, in
wall-clock order, in the seconds before something went wrong.  Production
TPU serving and MPMD-scale training both treat per-phase latency
attribution and cross-host timeline correlation as table stakes
(PAPERS.md: arxiv 2605.25645 §serving, 2412.14374 §debugging); the
reference rocket has neither.

Design constraints (ISSUE 4 tentpole):

- **lock-light**: events append to a ``collections.deque(maxlen=N)`` —
  a single bytecode-atomic operation under CPython, so the serve loop's
  caller thread and the watchdog worker thread can both record without a
  mutex on the hot path;
- **zero device syncs**: every stamp is ``time.perf_counter_ns()``; the
  only jax call on the recording path is the span's
  ``jax.profiler.TraceAnnotation``, which touches no device
  (``jax.process_index`` is consulted only at dump time, with a safe
  fallback);
- **one span primitive, on the profiler's clock**: :meth:`Tracer.span` is
  the only way the program opens a span.  While it is open it holds a
  ``TraceAnnotation`` of the same name, so every program span lands in
  the ``.xplane.pb``'s host plane — on the device trace's own clock —
  whenever a profiler session is running, and in the ring whenever the
  tracer is armed;
- **cheap when disarmed**: a span on a disabled tracer is the annotation
  alone (well under a microsecond with no session) — no clock read, no
  ring append;
- **bounded**: the ring keeps the last ``capacity`` events; a flight
  recorder dump is therefore always a recent-history window, never an
  unbounded log.

Multi-host correlation: each host's monotonic clock has an arbitrary
origin, so raw timestamps from two hosts cannot be compared.  The
Launcher calls :meth:`Tracer.set_anchor` immediately after a cross-host
barrier — every host stamps (wall time, monotonic time) at what is the
same instant up to barrier skew — and :func:`merge_traces` shifts each
per-host dump so the anchors coincide on the merged timeline
(``python -m rocket_tpu.observe.trace <dir>``).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import os
import threading
import time
import zlib
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from jax.profiler import TraceAnnotation

# Event layout (plain tuples — cheapest thing CPython can append):
#   (kind, name, ts_ns, dur_ns, tid, fields)
# kind: 'X' completed span, 'C' counter sample, 'I' instant / log event,
#       'H' health transition, 'F' flow (cross-process request arrow).
# ts_ns is perf_counter_ns at event start.
SPAN = "X"
COUNTER = "C"
INSTANT = "I"
HEALTH = "H"
FLOW = "F"


def _process_index() -> int:
    """Best-effort process index for dump labeling — never touched on the
    recording hot path, and never allowed to fail a dump."""
    try:
        import jax

        return int(jax.process_index())
    except Exception:
        return 0


class _Span:
    """One live span.  It holds a ``TraceAnnotation`` of its name while it
    is open (the profiler's host plane); with a ring (``buf``, the armed
    tracer's) it also stamps start at ``__enter__`` and appends a
    completed 'X' event at ``__exit__``.  An exception escaping the body
    is recorded in the span's fields (the flight recorder's most useful
    breadcrumb).  ``on_close(name, start_ns, end_ns, fields)`` is called
    at ``__exit__`` whether or not there is a ring: the start-up record's
    phases are spans with such a hook."""

    __slots__ = ("_buf", "_name", "_fields", "_t0", "_ann", "_on_close")

    def __init__(self, buf: Optional[deque], name: str,
                 fields: Dict[str, Any],
                 on_close: Optional[Callable[..., None]] = None) -> None:
        self._buf = buf
        self._name = name
        self._fields = fields
        self._on_close = on_close

    def __enter__(self) -> "_Span":
        self._ann = TraceAnnotation(self._name)
        self._ann.__enter__()
        if self._buf is not None or self._on_close is not None:
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> bool:
        if self._buf is not None or self._on_close is not None:
            end = time.perf_counter_ns()
            if exc_type is not None:
                self._fields["error"] = repr(exc)
            if self._buf is not None:
                self._buf.append(
                    (SPAN, self._name, self._t0, end - self._t0,
                     threading.get_ident(), self._fields)
                )
            if self._on_close is not None:
                self._on_close(self._name, self._t0, end, self._fields)
        self._ann.__exit__(exc_type, exc, tb)
        return False

    def add(self, **fields: Any) -> None:
        """Attach fields discovered mid-span (e.g. ``tripped=True``)."""
        self._fields.update(fields)


class Tracer:
    """Per-process ring buffer of typed trace events.

    Thread-safety: all mutation is a single ``deque.append`` (atomic under
    the GIL); snapshots (:meth:`events`) take a point-in-time ``list()`` of
    the deque, which is likewise safe against concurrent appends.
    """

    def __init__(self, capacity: int = 4096, enabled: bool = False) -> None:
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._buf: deque = deque(maxlen=self.capacity)
        self.enabled = bool(enabled)
        # (wall seconds, perf_counter_ns) stamped at the launch barrier —
        # the cross-host alignment point for merge_traces.
        self.anchor: Optional[Tuple[float, int]] = None
        # Free-form labels exported in the dump metadata — a fleet worker
        # stamps {"role", "replica", "pid"} here so the timeline stitcher
        # can match its ring to the supervisor's per-connection clock
        # offset without guessing from filenames.
        self.meta: Dict[str, Any] = {}

    # -- recording (hot path) -------------------------------------------

    def span(self, name: str, **fields: Any):
        """Context manager over a code region: a profiler annotation
        always, a ring event when armed — callers never branch on
        ``enabled`` themselves."""
        return _Span(self._buf if self.enabled else None, name, fields)

    def counter(self, name: str, value: float, **fields: Any) -> None:
        if not self.enabled:
            return
        fields[name.rsplit("/", 1)[-1]] = float(value)
        self._buf.append(
            (COUNTER, name, time.perf_counter_ns(), 0,
             threading.get_ident(), fields)
        )

    def instant(self, name: str, **fields: Any) -> None:
        if not self.enabled:
            return
        self._buf.append(
            (INSTANT, name, time.perf_counter_ns(), 0,
             threading.get_ident(), fields)
        )

    def health(self, name: str, state: str, **fields: Any) -> None:
        """Health-state transition (serve SERVING/DEGRADED/DRAINING)."""
        if not self.enabled:
            return
        fields["state"] = state
        self._buf.append(
            (HEALTH, name, time.perf_counter_ns(), 0,
             threading.get_ident(), fields)
        )

    def flow(self, name: str, phase: str, flow_id: int,
             cat: str = "request", **fields: Any) -> None:
        """Flow event tying cross-process segments of one request into a
        single Chrome-trace arrow chain.  ``phase`` is the Chrome flow
        phase: ``"s"`` start, ``"t"`` step, ``"f"`` finish.  ``flow_id``
        must be identical on every segment of the chain (derived from the
        request's trace_id)."""
        if not self.enabled:
            return
        fields["ph"] = phase
        fields["id"] = int(flow_id)
        fields["cat"] = cat
        self._buf.append(
            (FLOW, name, time.perf_counter_ns(), 0,
             threading.get_ident(), fields)
        )

    # -- control --------------------------------------------------------

    def set_anchor(self) -> Tuple[float, int]:
        """Stamp the cross-host alignment point.  Call IMMEDIATELY after a
        barrier so every host anchors the same instant (up to skew)."""
        self.anchor = (time.time(), time.perf_counter_ns())
        return self.anchor

    def resize(self, capacity: int) -> None:
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._buf = deque(self._buf, maxlen=self.capacity)

    def clear(self) -> None:
        self._buf.clear()

    # -- inspection / export -------------------------------------------

    def events(self) -> List[tuple]:
        """Point-in-time snapshot of the ring (oldest first)."""
        return list(self._buf)

    def to_chrome(self) -> Dict[str, Any]:
        """Export the ring as a Chrome-trace (catapult) document —
        loadable in Perfetto / ``chrome://tracing``.  Timestamps are
        microseconds of ``perf_counter``; :func:`merge_traces` rebases
        them onto a shared cross-host origin."""
        pid = _process_index()
        out: List[Dict[str, Any]] = []
        for kind, name, ts_ns, dur_ns, tid, fields in self.events():
            ev: Dict[str, Any] = {
                "name": name, "pid": pid, "tid": tid, "ts": ts_ns / 1e3,
            }
            if kind == SPAN:
                ev["ph"] = "X"
                ev["dur"] = dur_ns / 1e3
                ev["args"] = fields
            elif kind == COUNTER:
                ev["ph"] = "C"
                ev["args"] = fields
            elif kind == HEALTH:
                ev["ph"] = "i"
                ev["s"] = "p"  # process-scoped marker line
                ev["cat"] = "health"
                ev["args"] = fields
            elif kind == FLOW:
                args = dict(fields)
                ev["ph"] = args.pop("ph", "t")
                ev["id"] = args.pop("id", 0)
                ev["cat"] = args.pop("cat", "request")
                if ev["ph"] == "f":
                    # bind the finish to the enclosing slice, the
                    # chrome://tracing requirement for terminal arrows
                    ev["bp"] = "e"
                ev["args"] = args
            else:  # INSTANT
                ev["ph"] = "i"
                ev["s"] = "t"
                ev["args"] = fields
            out.append(ev)
        meta: Dict[str, Any] = {
            "process_index": pid,
            "capacity": self.capacity,
            "clock": "perf_counter_ns/1e3 (us)",
        }
        meta.update(self.meta)
        if self.anchor is not None:
            meta["anchor_wall_s"] = self.anchor[0]
            meta["anchor_perf_us"] = self.anchor[1] / 1e3
        if len(_STARTUP):
            # The process's start-up record rides in the metadata, not
            # among the events: a dump stays a recent-history window.
            meta["startup"] = _STARTUP.to_meta()
        return {
            "traceEvents": out,
            "displayTimeUnit": "ms",
            "metadata": meta,
        }

    def dump_json(self, path: str) -> str:
        """Write the ring as a Chrome-trace document.  The file appears
        whole or not at all (written aside, then renamed over ``path``):
        a process killed mid-dump leaves no half document behind."""
        doc = self.to_chrome()
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        partial = f"{path}.{os.getpid()}.partial"
        with open(partial, "w") as f:
            # default=str: span fields are arbitrary user values (rids,
            # enums) — a dump must never fail on an unserializable field.
            json.dump(doc, f, default=str)
        os.replace(partial, path)
        return path

    def tail_text(self, n: int = 48) -> str:
        """Human-readable last-``n`` events, newest last — the part of a
        flight-recorder dump you read before opening Perfetto."""
        lines = [_STARTUP.line()] if len(_STARTUP) else []
        for kind, name, ts_ns, dur_ns, tid, fields in self.events()[-n:]:
            stamp = f"{ts_ns / 1e9:14.6f}s"
            if kind == SPAN:
                body = f"span  {name}  {dur_ns / 1e6:9.3f}ms"
            elif kind == COUNTER:
                body = f"count {name}"
            elif kind == HEALTH:
                body = f"health {name} -> {fields.get('state')}"
            else:
                body = f"event {name}"
            extras = {k: v for k, v in fields.items() if k != "state"}
            suffix = f"  {extras}" if extras else ""
            lines.append(f"{stamp}  tid={tid}  {body}{suffix}")
        return "\n".join(lines) + ("\n" if lines else "")


# -- module-global tracer (what runtime.tracing arms) -----------------------

_GLOBAL = Tracer()


def get_tracer() -> Tracer:
    """The process-wide tracer instrumented code records into."""
    return _GLOBAL


def arm(capacity: Optional[int] = None) -> Tracer:
    """Enable the global tracer (idempotent; optionally resize)."""
    if capacity is not None and capacity != _GLOBAL.capacity:
        _GLOBAL.resize(capacity)
    _GLOBAL.enabled = True
    return _GLOBAL


def disarm() -> Tracer:
    _GLOBAL.enabled = False
    return _GLOBAL


def span(name: str, **fields: Any):
    """``with trace.span("phase", key=val): ...`` on the global tracer."""
    return _GLOBAL.span(name, **fields)


def counter(name: str, value: float = 1, **fields: Any) -> None:
    """Record a counter sample on the global tracer (no-op unless armed).
    The convenience for library code that wants one line, not a
    ``get_tracer()`` dance — e.g. ``ops.quant``'s fallback telemetry."""
    _GLOBAL.counter(name, value, **fields)


# -- start-up record ---------------------------------------------------------
#
# Start-up is a handful of events per process (imports, runtime, build, the
# first call at each jit edge, the serving warm start), so it is recorded
# whether or not any tracer is armed, in one small bounded process-wide
# record: the same record whatever tracer a ServingLoop was handed.  Each
# phase is also an ordinary span (annotation always, ring when armed).

STARTUP_PHASES: Tuple[Tuple[str, str], ...] = (
    ("startup/import", "import"),
    ("startup/runtime", "runtime"),
    ("startup/build", "build"),
    ("startup/first_dispatch", "first dispatch"),
    ("startup/serve_warm_start", "warm start"),
)


def _union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    total, hi = 0, None
    for lo, end in sorted(intervals):
        if hi is None or lo > hi:
            total += end - lo
            hi = end
        elif end > hi:
            total += end - hi
            hi = end
    return total


class StartupRecord:
    """Bounded record of start-up phases: ``(name, start_ns, dur_ns,
    fields)`` on ``perf_counter_ns``, oldest dropped first.  Start-up ends
    when its line is logged (:meth:`log_once`): from then on the record
    is closed and takes nothing more, so a compile in the middle of
    service (a new prompt length at ``generate/spec_admit``) is the
    retrace ledger's business and neither evicts nor inflates start-up."""

    def __init__(self, capacity: int = 256) -> None:
        self._events: deque = deque(maxlen=int(capacity))
        self.logged = False
        # persistent-compile-cache {"hits", "misses"} of the process when
        # the line was logged; None until then, or if they cannot be read
        self.cache: Optional[Dict[str, int]] = None

    def __len__(self) -> int:
        return len(self._events)

    def mark(self, name: str, start_ns: int, end_ns: int,
             **fields: Any) -> None:
        """Record a phase whose ends the caller stamped itself (a package
        ``__init__`` stamps its start before this module exists)."""
        if self.logged:
            return
        self._events.append(
            (name, int(start_ns), max(0, int(end_ns) - int(start_ns)),
             fields))

    def _close(self, name: str, start_ns: int, end_ns: int,
               fields: Dict[str, Any]) -> None:
        self.mark(name, start_ns, end_ns, **fields)

    def phase(self, name: str, **fields: Any) -> _Span:
        """``with startup.phase("startup/build"): ...`` — a span on the
        global tracer like any other, recorded here too when it closes."""
        return _Span(_GLOBAL._buf if _GLOBAL.enabled else None, name,
                     fields, on_close=self._close)

    def events(self) -> List[tuple]:
        return list(self._events)

    def clear(self) -> None:
        self._events.clear()
        self.logged = False
        self.cache = None

    def seconds(self, until_ns: Optional[int] = None) -> Dict[str, float]:
        """Seconds by phase name, summed so that they add up: each name's
        intervals count as their union (an import inside an import counts
        once), less the phases of other names recorded inside them (the
        warm start's seconds leave out the first dispatches it made).
        ``until_ns`` leaves out phases that started at or after it."""
        spans: Dict[str, List[Tuple[int, int]]] = {}
        for name, ts, dur, _fields in self._events:
            if until_ns is None or ts < until_ns:
                spans.setdefault(name, []).append((ts, ts + dur))
        out: Dict[str, float] = {}
        for name, own in spans.items():
            inside = [(lo, hi) for other, ivs in spans.items()
                      if other != name for lo, hi in ivs
                      if any(a <= lo and hi <= b for a, b in own)]
            out[name] = (_union_ns(own) - _union_ns(inside)) / 1e9
        return out

    def line(self) -> str:
        """The one line an operator reads: ``start-up: import 12.1 s,
        build 7.0 s, first dispatch 17.2 s, 182 cache hits, 0 misses``
        (the cache's counts as they stood when the line was logged)."""
        secs = self.seconds()
        parts = [f"{label} {secs[name]:.1f} s"
                 for name, label in STARTUP_PHASES if name in secs]
        parts += [f"{name} {s:.1f} s" for name, s in secs.items()
                  if name not in dict(STARTUP_PHASES)]
        cache = self.cache or _cache_counts()
        if cache is not None:
            parts.append(
                f"{cache['hits']} cache hits, {cache['misses']} misses")
        return "start-up: " + ", ".join(parts)

    def log_once(self, logger: logging.Logger) -> Optional[str]:
        """Log :meth:`line` the first time this is called in the process
        (the ``Looper`` after a ``Launcher``'s first step, ``ServingLoop``
        once it is built and warm) and close the record; later calls
        do nothing."""
        if self.logged:
            return None
        self.logged = True
        self.cache = _cache_counts()
        text = self.line()
        logger.info("%s", text)
        return text

    def to_meta(self) -> Dict[str, Any]:
        return {
            "seconds": self.seconds(),
            "cache": self.cache,
            "events": [{"name": n, "ts": ts / 1e3, "dur": dur / 1e3,
                        "args": f} for n, ts, dur, f in self._events],
        }


def _cache_counts() -> Optional[Dict[str, int]]:
    """The persistent compile cache's hits and misses so far, or None
    where nothing counts them (no ``Launcher`` or ``ServingLoop`` has
    installed the listeners) — never a failure of the code that asks."""
    try:
        from rocket_tpu.tune import compile_cache

        if not compile_cache.listening():
            return None
        hits, misses = compile_cache.hits_and_misses()
    except Exception:
        return None
    return {"hits": hits, "misses": misses}


_STARTUP = StartupRecord()


def get_startup() -> StartupRecord:
    """The process-wide start-up record."""
    return _STARTUP


class RoundRecord:
    """Process-wide totals of what the serving rounds count on the device:
    ``rounds``, ``drafted``, ``accepted``, ``routed_slots`` (top-k slots of
    the live rows' tokens, every routed layer), ``held_slots`` (those that
    fell on an expert held here) and ``expert_tokens`` (``[layer][held
    expert]`` tokens received; the target's routed layers, then the
    draft's).  A batcher adds what it fetched
    (``ContinuousBatcher.publish_counters``); a reader takes a
    :meth:`snapshot` after the loop that served is gone."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._totals: Dict[str, Any] = {}

    def add(self, counters: Dict[str, Any]) -> None:
        import numpy as np

        with self._lock:
            for name, value in counters.items():
                value = np.asarray(value, dtype=np.int64)
                held = self._totals.get(name)
                self._totals[name] = value if held is None \
                    or held.shape != value.shape else held + value

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {k: v.tolist() for k, v in self._totals.items()}

    def reset(self) -> None:
        with self._lock:
            self._totals = {}


_ROUNDS = RoundRecord()


def get_rounds() -> RoundRecord:
    """The process-wide record of the serving rounds' device counters."""
    return _ROUNDS


class RequestRecord:
    """Process-wide record of the requests serving loops terminated
    (``serve/complete``, ``serve/evict``), the newest ``capacity`` kept.
    An entry is a dict: ``rid``, ``outcome`` (``complete`` or ``evict``),
    ``first_s`` and ``end_s`` (the first token's harvest and the
    terminal, seconds on the loop's own clock), ``out`` (output tokens),
    ``stalled_turns`` (other requests' admission turns it sat through
    while decoding), ``e2e_ms`` and ``segments`` (ms by
    :data:`rocket_tpu.observe.critpath.SEGMENTS`, as critpath's rules
    split what that loop saw).  ``ServingLoop`` adds an entry at each
    terminal; a reader takes a :meth:`snapshot`, also after the loop is
    gone.  An entry holds about 750 bytes: the default keeps 12 MB."""

    def __init__(self, capacity: int = 16384) -> None:
        self._lock = threading.Lock()
        self._entries: deque = deque(maxlen=int(capacity))

    def add(self, entry: Dict[str, Any]) -> None:
        with self._lock:
            self._entries.append(entry)

    def snapshot(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._entries)

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()


_REQUESTS = RequestRecord()


def get_requests() -> RequestRecord:
    """The process-wide record of terminated requests' latency books."""
    return _REQUESTS


# -- distributed request tracing --------------------------------------------
#
# A TraceContext is stamped on a Request at submit and crosses every
# process boundary the request does (wire v3 SUBMIT/STEP/PAGES/
# NEW_WEIGHTS frames, KVPoolClient fetches) so supervisor, router,
# prefill, pool, and decode-worker events stitch into one timeline.
# Sampling is HEAD-sampled by a seeded hash of the rid — deterministic
# across processes, so every hop makes the same keep/drop decision
# without coordination — and promoted to sampled=True on bad outcomes
# (shed, deadline, preempt, watchdog trip, heal): the requests worth
# debugging are always fully traced.

_SAMPLING = {"rate": 1.0, "seed": 0}


def set_sampling(rate: float = 1.0, seed: int = 0) -> None:
    """Configure head-sampling for :meth:`TraceContext.make`: ``rate`` in
    [0, 1] is the fraction of requests whose flow events are emitted;
    ``seed`` varies which deterministic subset is picked."""
    _SAMPLING["rate"] = min(1.0, max(0.0, float(rate)))
    _SAMPLING["seed"] = int(seed)


def get_sampling() -> Tuple[float, int]:
    return float(_SAMPLING["rate"]), int(_SAMPLING["seed"])


@dataclasses.dataclass
class TraceContext:
    """Per-request distributed-tracing context (trace_id + parent span id
    + sampled flag).  Plain data — crosses the wire as a 3-tuple."""

    trace_id: str
    parent: str = ""
    sampled: bool = True

    @classmethod
    def make(cls, rid: Any, *, rate: Optional[float] = None,
             seed: Optional[int] = None) -> "TraceContext":
        """Deterministic context for ``rid``: the crc32 of ``seed:rid``
        decides sampling, so any process recomputing it (or a mid-upgrade
        v2 peer re-stamping a ctx-less frame) agrees on keep/drop."""
        if rate is None:
            rate = float(_SAMPLING["rate"])
        if seed is None:
            seed = int(_SAMPLING["seed"])
        h = zlib.crc32(f"{seed}:{rid}".encode())
        sampled = (h % 10_000) < rate * 10_000
        return cls(trace_id=f"{h:08x}-{rid}", parent="", sampled=sampled)

    @property
    def flow_id(self) -> int:
        """Stable integer id for Chrome flow events on this request."""
        return zlib.crc32(self.trace_id.encode())

    def child(self, parent: str) -> "TraceContext":
        return TraceContext(self.trace_id, parent, self.sampled)

    def to_wire(self) -> Tuple[str, str, bool]:
        return (self.trace_id, self.parent, bool(self.sampled))

    @classmethod
    def from_wire(cls, wire: Any) -> Optional["TraceContext"]:
        """Tolerant decode: a missing/garbled ctx (a v2 peer) is ``None``,
        never an exception — mid-upgrade fleets degrade to unsampled."""
        if not (isinstance(wire, (tuple, list)) and len(wire) == 3):
            return None
        trace_id, parent, sampled = wire
        if not isinstance(trace_id, str):
            return None
        return cls(trace_id, str(parent or ""), bool(sampled))


def instant(name: str, **fields: Any) -> None:
    """Instant-event convenience on the global tracer (no-op unless
    armed) — for code that records one marker, not a whole tracer."""
    _GLOBAL.instant(name, **fields)


def flow(name: str, phase: str, flow_id: int,
         cat: str = "request", **fields: Any) -> None:
    """Flow-event convenience on the global tracer (no-op unless armed)."""
    _GLOBAL.flow(name, phase, flow_id, cat, **fields)


class OffsetEstimator:
    """Per-connection clock-offset estimate from request/reply stamps.

    Each sample is ``(t0, tw, t1)``: supervisor ``perf_counter_ns``
    before send, the worker's ``perf_counter_ns`` stamped in the reply,
    and the supervisor's after receive.  Assuming symmetric transit, the
    worker clock read ``tw`` corresponds to supervisor instant
    ``(t0 + t1) / 2``, so ``offset = tw - (t0 + t1) / 2`` satisfies
    ``worker_clock ≈ supervisor_clock + offset`` — the NTP discipline,
    and the same shift-to-common-origin move :func:`merge_traces` makes
    with wall-clock anchors.  The estimate keeps the last ``window``
    samples and answers from the MINIMUM-RTT one: queueing delay only
    ever inflates RTT, so the tightest exchange bounds the error by
    rtt/2 and a refreshed window tracks slow drift between pings."""

    def __init__(self, window: int = 8) -> None:
        self._samples: deque = deque(maxlen=int(window))

    def add(self, t0_ns: int, tw_ns: int, t1_ns: int) -> None:
        rtt = int(t1_ns) - int(t0_ns)
        if rtt < 0:  # clock went backwards — unusable sample
            return
        offset = int(tw_ns) - (int(t0_ns) + int(t1_ns)) // 2
        self._samples.append((rtt, offset))

    def __len__(self) -> int:
        return len(self._samples)

    @property
    def offset_ns(self) -> Optional[int]:
        """worker_clock − supervisor_clock, from the min-RTT sample;
        ``None`` until the first sample lands."""
        if not self._samples:
            return None
        return min(self._samples)[1]

    @property
    def rtt_ns(self) -> Optional[int]:
        if not self._samples:
            return None
        return min(self._samples)[0]

    def snapshot(self) -> Dict[str, float]:
        """Flat floats for dumps/export: offset_us / rtt_us / samples."""
        out: Dict[str, float] = {"samples": float(len(self._samples))}
        if self._samples:
            rtt, offset = min(self._samples)
            out["offset_us"] = offset / 1e3
            out["rtt_us"] = rtt / 1e3
        return out


# -- latency histograms -----------------------------------------------------


class Histogram:
    """Bounded reservoir of float samples with nearest-rank percentiles.

    ``capacity`` bounds memory like the event ring does: long-running
    serve loops keep a sliding window of recent latencies, which is what
    an operator wants from ``trace/*`` scalars anyway.  ``count`` is
    lifetime-total (not window-bounded)."""

    def __init__(self, capacity: int = 2048) -> None:
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self._samples: deque = deque(maxlen=int(capacity))
        self.count = 0

    def record(self, value: float) -> None:
        self._samples.append(float(value))
        self.count += 1

    def __len__(self) -> int:
        return len(self._samples)

    def percentile(self, q: float) -> Optional[float]:
        """Nearest-rank percentile over the current window; ``None`` when
        empty (callers emit nothing rather than a fake zero)."""
        if not self._samples:
            return None
        ordered = sorted(self._samples)
        idx = int(round((q / 100.0) * (len(ordered) - 1)))
        return ordered[max(0, min(len(ordered) - 1, idx))]

    def summary(self, prefix: str) -> Dict[str, float]:
        """p50/p95/p99 + count, keyed ``<prefix>/p50`` etc.; empty dict
        when no samples yet."""
        if not self._samples:
            return {}
        return {
            f"{prefix}/p50": self.percentile(50),
            f"{prefix}/p95": self.percentile(95),
            f"{prefix}/p99": self.percentile(99),
            f"{prefix}/count": float(self.count),
        }

    def merge(self, other: "Histogram") -> None:
        """Fold another histogram's window (and lifetime count) into
        this one — fleet-wide percentile aggregation across replicas.
        Bounded by this histogram's own capacity like every record."""
        self._samples.extend(other._samples)
        self.count += other.count


# -- multi-host merge --------------------------------------------------------


def _iter_trace_files(trace_dir: str) -> Iterable[str]:
    for root, _dirs, files in os.walk(trace_dir):
        for name in sorted(files):
            if name.endswith(".json"):
                yield os.path.join(root, name)


def merge_traces(
    trace_dir: str, out_path: Optional[str] = None
) -> Dict[str, Any]:
    """Merge every per-host Chrome-trace dump under ``trace_dir`` into one
    aligned timeline.

    Alignment: host ``h``'s events carry that host's ``perf_counter``
    microseconds; its metadata carries the anchor pair stamped at the
    launch barrier.  On the merged timeline an event lands at::

        (ts - anchor_perf_us[h]) + (anchor_wall_s[h] - min_wall) * 1e6

    i.e. microseconds since the earliest host's barrier instant, so the
    barrier skew between hosts is the only residual error.  Dumps without
    an anchor (tracing armed outside a Launcher) are kept on their raw
    clock and flagged in the merged metadata.  Events get
    ``pid = process_index`` so Perfetto shows one lane group per host.
    """
    docs: List[Tuple[str, Dict[str, Any]]] = []
    for path in _iter_trace_files(trace_dir):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        if isinstance(doc, dict) and isinstance(doc.get("traceEvents"), list):
            docs.append((path, doc))
    if not docs:
        raise FileNotFoundError(
            f"no Chrome-trace JSON dumps found under {trace_dir!r}"
        )
    anchored = [
        d for _p, d in docs
        if d.get("metadata", {}).get("anchor_wall_s") is not None
    ]
    min_wall = min(
        (d["metadata"]["anchor_wall_s"] for d in anchored), default=None
    )
    merged: List[Dict[str, Any]] = []
    unanchored = []
    for path, doc in docs:
        meta = doc.get("metadata", {})
        pid = int(meta.get("process_index", 0))
        wall = meta.get("anchor_wall_s")
        perf_us = meta.get("anchor_perf_us")
        if wall is None or perf_us is None or min_wall is None:
            shift = 0.0
            unanchored.append(os.path.basename(path))
        else:
            shift = (wall - min_wall) * 1e6 - perf_us
        for ev in doc["traceEvents"]:
            ev = dict(ev)
            ev["pid"] = pid
            ev["ts"] = float(ev.get("ts", 0.0)) + shift
            merged.append(ev)
    merged.sort(key=lambda ev: ev["ts"])
    out: Dict[str, Any] = {
        "traceEvents": merged,
        "displayTimeUnit": "ms",
        "metadata": {
            "merged_from": len(docs),
            "hosts": sorted(
                {int(d.get("metadata", {}).get("process_index", 0))
                 for _p, d in docs}
            ),
            "unanchored_files": unanchored,
        },
    }
    if out_path is not None:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(out, f, default=str)
    return out


def _main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m rocket_tpu.observe.trace",
        description="Merge per-host flight-recorder dumps into one "
        "Perfetto-loadable timeline aligned at the launch barrier.",
    )
    parser.add_argument("trace_dir", help="directory of per-host dumps "
                        "(e.g. <project>/logs/flightrec)")
    parser.add_argument(
        "-o", "--out", default=None,
        help="output path (default: <trace_dir>/merged.json)",
    )
    args = parser.parse_args(argv)
    out_path = args.out or os.path.join(args.trace_dir, "merged.json")
    doc = merge_traces(args.trace_dir, out_path)
    print(
        f"merged {doc['metadata']['merged_from']} dump(s) from hosts "
        f"{doc['metadata']['hosts']} -> {out_path} "
        f"({len(doc['traceEvents'])} events)"
    )
    if doc["metadata"]["unanchored_files"]:
        print(
            "warning: unanchored (raw-clock) dumps: "
            + ", ".join(doc["metadata"]["unanchored_files"])
        )
    return 0


if __name__ == "__main__":  # pragma: no cover - exercised via subprocess
    raise SystemExit(_main())
