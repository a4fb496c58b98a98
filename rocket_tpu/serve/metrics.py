"""Serving counters — the observability side of every robustness action.

Every shed, eviction, demotion, and watchdog trip increments a counter
here; :class:`~rocket_tpu.serve.ServingLoop` flushes a snapshot to a
tracker backend (``serve/*`` scalars) every ``flush_every`` rounds, so
serving-side faults land in the same pane as the training-side
``sentinel/*`` scalars (`docs/reliability.md`).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from rocket_tpu.observe.trace import Histogram
from rocket_tpu.serve.types import SLO_CLASSES

# Per-class TTFT targets (ms) the SLO-attainment gauges measure against
# when no explicit targets are given: interactive is tight, standard
# relaxed, batch effectively throughput-only.
DEFAULT_SLO_TARGETS: Dict[str, float] = {
    "interactive": 500.0,
    "standard": 2000.0,
    "batch": 30000.0,
}


class ServeCounters:
    """Plain integer counters plus the round-latency EMA.  ``snapshot``
    returns a flat float dict ready for ``TrackerBackend.log_scalars``.

    ``class_counts`` splits the multi-tenant events per SLO class; the
    snapshot flattens them as ``class/<cls>/<event>`` so they ride the
    same ``serve/*`` flush (and the same Prometheus export) as the flat
    counters.
    """

    _CLASS_EVENTS = ("submitted", "completed", "shed", "preempted",
                     "resumed")

    def __init__(self) -> None:
        self.class_counts: Dict[str, Dict[str, int]] = {
            cls: {ev: 0 for ev in self._CLASS_EVENTS}
            for cls in SLO_CLASSES
        }
        self.submitted = 0
        self.admitted = 0
        self.prefilled_admits = 0   # admissions that imported a KVHandoff
        self.kv_hits = 0            # admissions served from the prefix cache
        self.kv_hit_tokens = 0      # prompt tokens skipped via cached pages
        self.pool_hits = 0          # admissions served via fleet pool fetch
        self.pool_hit_tokens = 0    # prompt tokens skipped via pooled pages
        self.pool_nacks = 0         # pool consulted, nothing usable (stale)
        self.pool_pushed_pages = 0  # pages this loop pushed pool-ward
        self.completed = 0
        self.swaps = 0              # live weight hot-swaps applied
        self.publish_rejected = 0   # publications refused by verify/reshard
        self.swap_rollbacks = 0     # bounded rollbacks to the prior version
        self.weights_version = -1   # gauge: newest applied published version
        self.swap_ms_total = 0.0    # wall time spent inside swaps (counter)
        self.shed_overload = 0      # bounded-queue / draining rejections
        self.shed_deadline = 0      # shed before prefill (stage='queue')
        self.evicted_deadline = 0   # evicted mid-decode (stage='decode')
        self.preempted = 0          # batch rows evicted-to-kvstore for
                                    # higher-class admissions
        self.resumed = 0            # parked tickets re-admitted from
                                    # their cached prefix
        self.truncated = 0          # degradation max-new cap cutoffs
        self.failed = 0             # watchdog / step-error row failures
        self.watchdog_trips = 0
        self.beam_served = 0
        self.beam_demoted = 0
        self.rounds = 0
        self.degrade_level = 0
        self.degrade_peak = 0
        self.round_ms_ema = 0.0
        self.round_gap_ms_ema = 0.0  # last device read -> next dispatch
        self.host_fetches = 0       # blocking device->host reads
        self.attended_blocks = 0    # key blocks the rows in use hold
        self.total_blocks = 0       # rows x blocks of the whole slab
        self.selected_keys = 0      # keys the rows' frontier queries keep
        self.live_keys = 0          # keys those queries could see

    def observe_blocks(self, n_tok: Any, done: Any, n_slots: int,
                       block_k: int) -> None:
        """Count, from the host copies of ``n_tok`` and ``done`` a round
        hands back anyway, the key blocks its attention has to visit (a
        row in use holds ``ceil(n_tok / block_k)``, a finished row none)
        beside the blocks of the whole slab — how much of the cache the
        decode kernel (``ops.decode_attention``) reads.  The batcher calls
        it only where a round's attention IS that kernel, with the
        kernel's own block: a ``dot_attention`` round reads every slot and
        leaves both counts at 0."""
        held = -(-np.asarray(n_tok, np.int64) // block_k)
        self.attended_blocks += int(held[~np.asarray(done, bool)].sum())
        self.total_blocks += int(held.shape[0]) * -(-n_slots // block_k)

    def observe_selection(self, n_tok: Any, done: Any, top_k: int) -> None:
        """Count, from the same host copies, what an attention that
        chooses its keys keeps: the query at a row's frontier sees
        ``n_tok`` keys and keeps ``min(n_tok, top_k)``; a finished row
        none.  One query a row and round stands for the round's (the
        device's own count of every query and layer is the rounds'
        ``selected_keys`` / ``live_keys``, ``observe.trace.get_rounds``)."""
        seen = np.asarray(n_tok, np.int64)[~np.asarray(done, bool)]
        self.selected_keys += int(np.minimum(seen, top_k).sum())
        self.live_keys += int(seen.sum())

    def observe_round_gap_ms(self, gap_ms: float, decay: float = 0.8) -> None:
        if self.round_gap_ms_ema == 0.0:
            self.round_gap_ms_ema = gap_ms
        else:
            self.round_gap_ms_ema = decay * self.round_gap_ms_ema \
                + (1.0 - decay) * gap_ms

    def observe_round_ms(self, round_ms: float, decay: float = 0.8) -> None:
        self.rounds += 1
        if self.round_ms_ema == 0.0:
            self.round_ms_ema = round_ms
        else:
            self.round_ms_ema = decay * self.round_ms_ema \
                + (1.0 - decay) * round_ms

    def observe_level(self, level: int) -> None:
        self.degrade_level = level
        self.degrade_peak = max(self.degrade_peak, level)

    def observe_class(self, slo_class: str, event: str, n: int = 1) -> None:
        """Bump one per-class event counter (unknown classes are counted
        under ``standard`` rather than raising — counters must never
        take the serve path down)."""
        per = self.class_counts.get(slo_class,
                                    self.class_counts["standard"])
        per[event] = per.get(event, 0) + n

    def snapshot(self) -> Dict[str, float]:
        out = {
            f"class/{cls}/{ev}": float(n)
            for cls, events in self.class_counts.items()
            for ev, n in events.items()
        }
        out.update({
            "submitted": float(self.submitted),
            "admitted": float(self.admitted),
            "prefilled_admits": float(self.prefilled_admits),
            "kv_hits": float(self.kv_hits),
            "kv_hit_tokens": float(self.kv_hit_tokens),
            "pool_hits": float(self.pool_hits),
            "pool_hit_tokens": float(self.pool_hit_tokens),
            "pool_nacks": float(self.pool_nacks),
            "pool_pushed_pages": float(self.pool_pushed_pages),
            "completed": float(self.completed),
            "swaps": float(self.swaps),
            "publish_rejected": float(self.publish_rejected),
            "swap_rollbacks": float(self.swap_rollbacks),
            "weights_version": float(self.weights_version),
            "swap_ms_total": float(self.swap_ms_total),
            "shed_overload": float(self.shed_overload),
            "shed_deadline": float(self.shed_deadline),
            "evicted_deadline": float(self.evicted_deadline),
            "preempted": float(self.preempted),
            "resumed": float(self.resumed),
            "truncated": float(self.truncated),
            "failed": float(self.failed),
            "watchdog_trips": float(self.watchdog_trips),
            "beam_served": float(self.beam_served),
            "beam_demoted": float(self.beam_demoted),
            "rounds": float(self.rounds),
            "degrade_level": float(self.degrade_level),
            "degrade_peak": float(self.degrade_peak),
            "round_ms_ema": float(self.round_ms_ema),
            "round_gap_ms_ema": float(self.round_gap_ms_ema),
            "host_fetches": float(self.host_fetches),
            "attended_blocks": float(self.attended_blocks),
            "total_blocks": float(self.total_blocks),
            "attended_block_share":
                self.attended_blocks / max(1, self.total_blocks),
            "selected_key_share":
                self.selected_keys / max(1, self.live_keys),
        })
        return out


class ServeLatency:
    """Request-level latency histograms, all in milliseconds on the serve
    loop's injected clock (so fake-clock tests are deterministic):

    - ``queue_wait_ms`` — submit → batcher admission (prefill start);
    - ``ttft_ms`` — submit → the first harvested round that contained the
      request's first generated token (time-to-first-token);
    - ``tpot_ms`` — mean per-token interval AFTER the first token
      (time-per-output-token), recorded once at request completion;
    - ``e2e_ms`` — submit → the typed terminal result.

    :meth:`summary` flattens to ``<name>/p50|p95|p99|count`` floats —
    the serve loop prefixes them ``trace/`` and flushes them through the
    same tracker backend as the ``serve/*`` counters."""

    def __init__(self, capacity: int = 2048) -> None:
        self.queue_wait_ms = Histogram(capacity)
        self.ttft_ms = Histogram(capacity)
        self.tpot_ms = Histogram(capacity)
        self.e2e_ms = Histogram(capacity)

    def summary(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for name in ("queue_wait_ms", "ttft_ms", "tpot_ms", "e2e_ms"):
            out.update(getattr(self, name).summary(name))
        return out

    def merge(self, other: "ServeLatency") -> None:
        """Fold another replica's histograms into this one — the fleet
        router aggregates per-replica latencies into one fleet-wide
        percentile view without touching the replicas' own state."""
        for name in ("queue_wait_ms", "ttft_ms", "tpot_ms", "e2e_ms"):
            getattr(self, name).merge(getattr(other, name))


class ClassLatency:
    """Per-SLO-class TTFT and e2e histograms — the raw material for the
    SLO-attainment gauges.

    Merge rule (documented in docs/observability.md): fleet aggregation
    merges the per-class SAMPLE windows and recomputes attainment over
    the merged window — attainment fractions are never averaged across
    replicas (a quiet replica's perfect 1.0 would mask a loaded one's
    0.6)."""

    def __init__(self, capacity: int = 2048) -> None:
        self.ttft_ms: Dict[str, Histogram] = {
            cls: Histogram(capacity) for cls in SLO_CLASSES}
        self.e2e_ms: Dict[str, Histogram] = {
            cls: Histogram(capacity) for cls in SLO_CLASSES}

    def record_ttft(self, slo_class: str, ms: float) -> None:
        self.ttft_ms.get(slo_class, self.ttft_ms["standard"]).record(ms)

    def record_e2e(self, slo_class: str, ms: float) -> None:
        self.e2e_ms.get(slo_class, self.e2e_ms["standard"]).record(ms)

    def attainment(self, targets: Optional[Dict[str, float]] = None
                   ) -> Dict[str, float]:
        """Fraction of the TTFT window at or under each class's target
        (classes with no samples yet export nothing — a fake 1.0 would
        read as a healthy SLO)."""
        targets = targets or DEFAULT_SLO_TARGETS
        out: Dict[str, float] = {}
        for cls, hist in self.ttft_ms.items():
            samples = list(hist._samples)
            target = targets.get(cls)
            if not samples or target is None:
                continue
            ok = sum(1 for s in samples if s <= target)
            out[cls] = ok / len(samples)
        return out

    def summary(self) -> Dict[str, float]:
        """Flatten to ``<cls>/ttft_ms/p50...`` / ``<cls>/e2e_ms/...``."""
        out: Dict[str, float] = {}
        for cls in SLO_CLASSES:
            out.update(self.ttft_ms[cls].summary(f"{cls}/ttft_ms"))
            out.update(self.e2e_ms[cls].summary(f"{cls}/e2e_ms"))
        return out

    def merge(self, other: "ClassLatency") -> None:
        for cls in SLO_CLASSES:
            self.ttft_ms[cls].merge(other.ttft_ms[cls])
            self.e2e_ms[cls].merge(other.e2e_ms[cls])


def register_slo_source(provider: Any, name: str = "serve_slo", *,
                        targets: Optional[Dict[str, float]] = None) -> None:
    """Hang per-class SLO gauges on the Prometheus export registry.

    ``provider`` is anything exposing ``slo_latency`` — a
    :class:`~rocket_tpu.serve.ServingLoop` attribute or a
    :class:`~rocket_tpu.serve.FleetRouter` method returning the merged
    fleet view.  Exports, per class: the TTFT/e2e percentiles
    (``<cls>/ttft_ms/p95`` ...) and the attainment gauge
    ``<cls>/ttft_attainment`` — the fraction of the recent TTFT window
    meeting the class target, computed AFTER merging sample windows
    across replicas (never an average of per-replica fractions)."""
    from rocket_tpu.observe import export

    def _snapshot() -> Dict[str, float]:
        lat = provider.slo_latency
        if callable(lat):
            lat = lat()
        out = lat.summary()
        for cls, frac in lat.attainment(targets).items():
            out[f"{cls}/ttft_attainment"] = float(frac)
        counters = getattr(provider, "counters", None)
        if counters is not None and hasattr(counters, "class_counts"):
            for cls, events in counters.class_counts.items():
                for ev, n in events.items():
                    out[f"{cls}/{ev}"] = float(n)
        return out

    export.register_source(name, _snapshot)


class FleetCounters:
    """Router-level counters — the fleet analogue of
    :class:`ServeCounters`; per-replica counters stay on each replica's
    own loop, these count only decisions the ROUTER made."""

    def __init__(self) -> None:
        # Per-class routing outcomes (multi-tenant serving): flattened
        # into the snapshot as ``class/<cls>/routed`` etc., so a batch
        # flood's fleet-level sheds are attributable to batch.
        self.class_counts: Dict[str, Dict[str, int]] = {
            cls: {"routed": 0, "shed_saturated": 0} for cls in SLO_CLASSES
        }
        self.submitted = 0          # requests handed to the router
        self.routed = 0             # accepted by some replica
        self.handoffs = 0           # prefill lane -> decode lane transfers
        self.handoff_bytes = 0      # total KVHandoff payload moved
        self.requeued = 0           # salvaged from a sick replica, re-routed
        self.heals = 0              # replica rebuilds the router ordered
        self.shed_saturated = 0     # every replica refused (fleet-level shed)
        self.deadline_shed_prefill = 0  # deadline passed in the prefill lane
        self.affinity_routed = 0    # session requests routed to their replica
        self.affinity_invalidated = 0   # session stamps dropped by a heal
        self.pages_routed = 0       # routed by the shared prefix-hash index
        self.pool_handoffs = 0      # prefill->decode via the fleet page pool
        self.replicas_added = 0     # autoscaler spawns joined to the fleet
        self.replicas_retired = 0   # replicas drained out of the fleet

    def observe_class(self, slo_class: str, event: str) -> None:
        per = self.class_counts.get(slo_class,
                                    self.class_counts["standard"])
        per[event] = per.get(event, 0) + 1

    def snapshot(self) -> Dict[str, float]:
        out = {
            f"class/{cls}/{ev}": float(n)
            for cls, events in self.class_counts.items()
            for ev, n in events.items()
        }
        out.update({
            "submitted": float(self.submitted),
            "routed": float(self.routed),
            "handoffs": float(self.handoffs),
            "handoff_bytes": float(self.handoff_bytes),
            "requeued": float(self.requeued),
            "heals": float(self.heals),
            "shed_saturated": float(self.shed_saturated),
            "deadline_shed_prefill": float(self.deadline_shed_prefill),
            "affinity_routed": float(self.affinity_routed),
            "affinity_invalidated": float(self.affinity_invalidated),
            "pages_routed": float(self.pages_routed),
            "pool_handoffs": float(self.pool_handoffs),
            "replicas_added": float(self.replicas_added),
            "replicas_retired": float(self.replicas_retired),
        })
        return out
