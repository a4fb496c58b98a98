"""Critical-path attribution — decompose each request's latency into
named segments and aggregate per SLO class.

A fleet-wide p99 regression is an ANSWERABLE question only when e2e
decomposes: did the tail wait in the admission queue, burn in prefill,
cross the handoff wire, stall on a pool fetch, sit parked under
preemption, or ride out a replica heal?  This module reads the trace
events the serving stack already emits (`docs/observability.md` names
each) and rebuilds, per request, the segment timeline:

==================  =========================================================
segment             measured from
==================  =========================================================
``queue_wait``      ``serve/admit`` span's ``queue_wait_ms`` arg
``route``           ``fleet/route`` instants' ``route_ms`` arg (summed)
``prefill``         ``fleet/prefill`` span duration plus the terminal's
                    ``prefill_ms``: from the instant the row's first
                    admission ends ``queue_wait`` to the harvest of its
                    first token, less its pool fetch (on a TPU an
                    admission's dispatch returns before its program has
                    run: the program's time is spent in the wait for
                    that first harvest, not in the ``serve/admit`` span)
``handoff_wire``    ``fleet/handoff`` / ``fleet/pool_handoff`` ``wire_ms``
``pool_fetch``      ``serve/pool_fetch`` span durations (summed)
``decode_rounds``   terminal instant ts − ``serve/first_token`` ts −
                    parked time − ``admit_stall``
``admit_stall``     the terminal's ``admit_stall_ms``: what the turns
                    that admitted OTHER requests cost this row while it
                    decoded, beyond a clean turn (``ServingLoop``'s book)
``preempt_parked``  Σ (``serve/resume`` ts − ``serve/preempt`` ts), and
                    a resumed admission's pool fetch
``heal``            ``fleet/requeued`` instants' ``heal_ms`` arg (summed)
``delivery``        ``fleet/delivered`` ts − terminal instant ts
==================  =========================================================

A terminal from a loop that keeps no book (no ``prefill_ms``: an older
worker's dump) is read by the earlier rules: ``prefill`` takes the first
``serve/admit`` span's duration, ``decode_rounds`` runs from that span's
end, and there is no ``admit_stall``.  :func:`replica_segments` is the
rules' one statement; ``ServingLoop`` applies it to its own clock's
instants for the process-wide record
(:func:`rocket_tpu.observe.trace.get_requests`), where the segments sum
to the request's e2e.

Terminal instants are ``serve/complete`` / ``serve/evict`` (they carry
``cls`` and ``e2e_ms``); ``serve/first_token`` supplies TTFT.  Segments
that never happened for a request are simply 0.0 — the decomposition is
a partition of observed time, not a schema every request must fill.

Aggregation (:class:`CritPathStats`) keeps per-class SUM-mergeable
floats only — ``<cls>/<segment>_ms_total``, ``<cls>/count``,
``<cls>/dominant_<segment>`` — so ``observe.export.merge_counters``
folds multi-host snapshots correctly (no ``/p50``-style keys, which
that merge treats as MAX).  :func:`register_critpath_source` exposes
the stats as the ``serve_critpath/*`` metrics source.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, List, Optional

SEGMENTS = (
    "queue_wait",
    "route",
    "prefill",
    "handoff_wire",
    "pool_fetch",
    "decode_rounds",
    "admit_stall",
    "preempt_parked",
    "heal",
    "delivery",
)

_TERMINALS = ("serve/complete", "serve/evict")


@dataclasses.dataclass
class RequestPath:
    """One request's latency decomposition (all segments in ms)."""

    rid: Any
    cls: str = "standard"
    trace_id: str = ""
    segments: Dict[str, float] = dataclasses.field(default_factory=dict)
    e2e_ms: float = 0.0
    ttft_ms: Optional[float] = None

    @property
    def dominant(self) -> str:
        """The segment that owns the largest share of this request's
        time — its critical path in one word."""
        if not self.segments:
            return "decode_rounds"
        return max(SEGMENTS, key=lambda s: self.segments.get(s, 0.0))

    @property
    def accounted_ms(self) -> float:
        return sum(self.segments.values())


# -- event normalization -----------------------------------------------------
#
# Two front doors, one analyzer: tracer rings hold tuples
# (kind, name, ts_ns, dur_ns, tid, fields); Chrome docs hold dicts with
# ts/dur in microseconds.  Both normalize to (name, ts_us, dur_us, args).


def _from_ring(events: Iterable[tuple]) -> List[tuple]:
    out = []
    for kind, name, ts_ns, dur_ns, _tid, fields in events:
        if kind in ("X", "I"):
            out.append((name, ts_ns / 1e3, dur_ns / 1e3, fields))
    return out


def _from_chrome(doc: Dict[str, Any]) -> List[tuple]:
    out = []
    for ev in doc.get("traceEvents", []):
        if ev.get("ph") not in ("X", "i"):
            continue
        out.append((
            ev.get("name", ""), float(ev.get("ts", 0.0)),
            float(ev.get("dur", 0.0)), ev.get("args", {}) or {},
        ))
    return out


def _rid_of(args: Dict[str, Any]) -> Optional[Any]:
    rid = args.get("rid")
    if rid is not None:
        return rid
    # pool-side events carry only trace_id ("<crc32:08x>-<rid>")
    tid = args.get("trace_id")
    if isinstance(tid, str) and "-" in tid:
        return tid.split("-", 1)[1]
    return None


def _ms(args: Dict[str, Any], key: str) -> float:
    try:
        return max(0.0, float(args.get(key, 0.0)))
    except (TypeError, ValueError):
        return 0.0


def replica_segments(*, queue_wait_ms: float, prefill_ms: float,
                     first_to_terminal_ms: float, pool_fetch_ms: float = 0.0,
                     parked_ms: float = 0.0,
                     admit_stall_ms: float = 0.0) -> Dict[str, float]:
    """What one serving loop saw of a request, by segment: the rules of
    the module's table for the loop's own measurements (ms), the fleet's
    segments 0.  ``prefill_ms`` runs from the end of ``queue_wait`` to
    the first token's harvest, less ``pool_fetch_ms``;
    ``first_to_terminal_ms`` from that harvest to the terminal."""
    segments = dict.fromkeys(SEGMENTS, 0.0)
    segments.update(
        queue_wait=queue_wait_ms, prefill=prefill_ms,
        pool_fetch=pool_fetch_ms, preempt_parked=parked_ms,
        admit_stall=admit_stall_ms,
        decode_rounds=max(
            0.0, first_to_terminal_ms - parked_ms - admit_stall_ms))
    return segments


def _analyze(norm: List[tuple]) -> List[RequestPath]:
    norm.sort(key=lambda e: e[1])
    paths: Dict[Any, RequestPath] = {}
    admit_end: Dict[Any, float] = {}       # first admit's end ts (us)
    admit_ms: Dict[Any, float] = {}        # first admit's duration (ms)
    first_at: Dict[Any, float] = {}        # first token's ts (us)
    preempt_at: Dict[Any, float] = {}      # open preempt's ts (us)
    resumed = set()                        # rids resumed at least once
    resume_fetch: Dict[Any, float] = {}    # pool fetches after a resume
    terminal_at: Dict[Any, float] = {}     # terminal instant ts (us)
    booked: Dict[Any, Dict[str, Any]] = {}  # terminal args with the book

    def path(rid: Any) -> RequestPath:
        if rid not in paths:
            paths[rid] = RequestPath(
                rid, segments={s: 0.0 for s in SEGMENTS})
        return paths[rid]

    for name, ts_us, dur_us, args in norm:
        rid = _rid_of(args)
        if rid is None:
            continue
        # rids cross the wire as strings; match them caselessly on type
        rid = str(rid)
        if name == "serve/submit":
            p = path(rid)
            p.cls = str(args.get("cls", p.cls))
            p.trace_id = str(args.get("trace_id", p.trace_id))
        elif name == "fleet/route":
            path(rid).segments["route"] += _ms(args, "route_ms")
        elif name == "fleet/prefill":
            path(rid).segments["prefill"] += dur_us / 1e3
        elif name in ("fleet/handoff", "fleet/pool_handoff"):
            path(rid).segments["handoff_wire"] += _ms(args, "wire_ms")
        elif name == "serve/pool_fetch":
            path(rid).segments["pool_fetch"] += dur_us / 1e3
            if rid in resumed:
                resume_fetch[rid] = resume_fetch.get(rid, 0.0) \
                    + dur_us / 1e3
        elif name == "serve/admit":
            p = path(rid)
            if rid not in admit_end:
                p.segments["queue_wait"] = _ms(args, "queue_wait_ms")
                admit_end[rid] = ts_us + dur_us
                admit_ms[rid] = dur_us / 1e3
        elif name == "serve/preempt":
            preempt_at[rid] = ts_us
        elif name == "serve/resume":
            resumed.add(rid)
            t0 = preempt_at.pop(rid, None)
            if t0 is not None:
                path(rid).segments["preempt_parked"] += \
                    max(0.0, ts_us - t0) / 1e3
        elif name == "fleet/requeued":
            path(rid).segments["heal"] += _ms(args, "heal_ms")
        elif name == "serve/first_token":
            path(rid).ttft_ms = _ms(args, "ttft_ms")
            first_at.setdefault(rid, ts_us)
        elif name in _TERMINALS:
            p = path(rid)
            p.cls = str(args.get("cls", p.cls))
            p.e2e_ms = _ms(args, "e2e_ms")
            terminal_at[rid] = ts_us
            if "prefill_ms" in args:
                booked[rid] = args
        elif name == "fleet/delivered":
            t_term = terminal_at.get(rid)
            if t_term is not None:
                path(rid).segments["delivery"] += \
                    max(0.0, ts_us - t_term) / 1e3

    for rid, p in paths.items():
        t_term = terminal_at.get(rid)
        t_admit = admit_end.get(rid)
        s = p.segments
        if t_term is not None and rid in booked:
            # the loop's book: its own prefill and stall, the rules of
            # replica_segments over the rest
            t_first = first_at.get(rid, t_admit)
            fetched = resume_fetch.get(rid, 0.0)
            local = replica_segments(
                queue_wait_ms=s["queue_wait"],
                prefill_ms=_ms(booked[rid], "prefill_ms"),
                first_to_terminal_ms=(
                    (t_term - t_first) / 1e3 if t_first is not None
                    else 0.0),
                pool_fetch_ms=s["pool_fetch"] - fetched,
                parked_ms=s["preempt_parked"] + fetched,
                admit_stall_ms=_ms(booked[rid], "admit_stall_ms"))
            for seg in ("prefill", "admit_stall"):
                s[seg] += local[seg]
            for seg in ("pool_fetch", "preempt_parked", "decode_rounds"):
                s[seg] = local[seg]
        elif t_term is not None and t_admit is not None:
            # an older loop's dump: the admit span IS the row's prefill
            # work (full prefill on a decode replica, KV import for a
            # handoff), decode runs from its end
            s["prefill"] += admit_ms[rid]
            s["decode_rounds"] = max(
                0.0, (t_term - t_admit) / 1e3 - s["preempt_parked"])
        if p.e2e_ms == 0.0:
            p.e2e_ms = p.accounted_ms
    return [p for p in paths.values() if terminal_at.get(p.rid) is not None]


def analyze_events(events: Iterable[tuple]) -> List[RequestPath]:
    """Decompose a tracer ring snapshot (``Tracer.events()`` tuples) into
    per-request paths.  Only requests that reached a terminal instant
    appear — a half-captured ring yields fewer paths, never wrong ones."""
    return _analyze(_from_ring(events))


def analyze_chrome(doc: Dict[str, Any]) -> List[RequestPath]:
    """Same decomposition over a Chrome-trace document — a flight dump
    or a stitched :mod:`rocket_tpu.observe.timeline` output."""
    return _analyze(_from_chrome(doc))


# -- aggregation / export ----------------------------------------------------


class CritPathStats:
    """Per-class segment totals + dominant-segment counts, snapshot as
    flat SUM-mergeable floats for ``observe.export``."""

    def __init__(self) -> None:
        self._totals: Dict[str, Dict[str, float]] = {}
        self._dominant: Dict[str, Dict[str, float]] = {}
        self._count: Dict[str, float] = {}
        self._e2e: Dict[str, float] = {}
        self._ttft: Dict[str, float] = {}

    def record(self, p: RequestPath) -> None:
        cls = p.cls or "standard"
        tot = self._totals.setdefault(cls, {s: 0.0 for s in SEGMENTS})
        for seg in SEGMENTS:
            tot[seg] += p.segments.get(seg, 0.0)
        dom = self._dominant.setdefault(cls, {})
        dom[p.dominant] = dom.get(p.dominant, 0.0) + 1.0
        self._count[cls] = self._count.get(cls, 0.0) + 1.0
        self._e2e[cls] = self._e2e.get(cls, 0.0) + p.e2e_ms
        if p.ttft_ms is not None:
            self._ttft[cls] = self._ttft.get(cls, 0.0) + p.ttft_ms

    def extend(self, paths: Iterable[RequestPath]) -> "CritPathStats":
        for p in paths:
            self.record(p)
        return self

    def snapshot(self) -> Dict[str, float]:
        """Flat floats: every key sums across hosts under
        ``merge_counters`` (totals, counts — no percentile keys)."""
        out: Dict[str, float] = {}
        for cls, n in self._count.items():
            out[f"{cls}/count"] = n
            out[f"{cls}/e2e_ms_total"] = self._e2e.get(cls, 0.0)
            if cls in self._ttft:
                out[f"{cls}/ttft_ms_total"] = self._ttft[cls]
            for seg in SEGMENTS:
                out[f"{cls}/{seg}_ms_total"] = \
                    self._totals.get(cls, {}).get(seg, 0.0)
            for seg, c in sorted(self._dominant.get(cls, {}).items()):
                out[f"{cls}/dominant_{seg}"] = c
        return out

    @property
    def classes(self) -> List[str]:
        return sorted(self._count)


def aggregate(paths: Iterable[RequestPath]) -> CritPathStats:
    """Fold request paths into fresh per-class stats."""
    return CritPathStats().extend(paths)


def register_critpath_source(stats: CritPathStats,
                             name: str = "serve_critpath") -> str:
    """Register ``stats`` as an ``observe.export`` source so ``/metrics``
    serves ``rocket_tpu_serve_critpath_*`` series.  Returns the name."""
    from rocket_tpu.observe.export import register_source

    register_source(name, stats.snapshot)
    return name


def format_table(stats: CritPathStats) -> str:
    """Human-readable per-class breakdown — the ``--critpath`` summary
    the load generator prints: mean ms per segment, its share of mean
    e2e, and the dominant-segment tally."""
    lines: List[str] = []
    for cls in stats.classes:
        n = stats._count[cls]
        e2e_mean = stats._e2e.get(cls, 0.0) / n
        lines.append(
            f"class {cls}: {int(n)} request(s), "
            f"mean e2e {e2e_mean:.2f} ms"
        )
        tot = stats._totals.get(cls, {})
        denom = max(sum(tot.values()), 1e-9)
        for seg in SEGMENTS:
            ms = tot.get(seg, 0.0)
            if ms <= 0.0:
                continue
            lines.append(
                f"  {seg:<15} {ms / n:10.3f} ms  "
                f"{100.0 * ms / denom:5.1f}%"
            )
        dom = stats._dominant.get(cls, {})
        if dom:
            ranked = sorted(dom.items(), key=lambda kv: -kv[1])
            lines.append(
                "  dominant: " + ", ".join(
                    f"{seg} x{int(c)}" for seg, c in ranked)
            )
    return "\n".join(lines) + ("\n" if lines else "")
