"""ServingLoop — the self-healing wrapper around ContinuousBatcher.

The bare :class:`~rocket_tpu.models.generate.ContinuousBatcher` is a
correctness engine: drive :meth:`step`, harvest finished rows, admit
replacements.  This module adds everything a request needs to SURVIVE
contact with production, without touching the traced step body:

- **admission control** — a bounded queue; a full queue (or a draining
  loop) rejects at submit time with a typed
  :class:`~rocket_tpu.serve.types.Overloaded`;
- **deadlines** — absolute timestamps on an injected clock, checked at
  every round boundary: hopeless queue entries are shed BEFORE they
  spend a prefill, and in-flight rows past deadline are evicted at the
  next boundary and returned as
  :class:`~rocket_tpu.serve.types.DeadlineExceeded` with their partial
  tokens;
- **graceful degradation** — a
  :class:`~rocket_tpu.serve.policy.DegradationPolicy` ladder driven by
  queue depth and round latency shrinks ``n_draft`` (legal between
  steps — it is a static jit argname the carried state does not depend
  on), caps max-new-tokens at admission, and demotes beam requests to
  the greedy lane;
- **a dispatch watchdog** — the blocking step + host fetch runs on a
  worker thread with a timed poll; a wedged dispatch fails the
  in-flight rows cleanly (partials from the last good host-side carry)
  and REBUILDS the batcher from the factory.  The rebuilt instance
  reuses the persistent ``_spec_round`` jit cache (the flax modules
  hash structurally), so recovery costs a prefill, not a retrace.

Fault-free bit-equality contract: with no deadlines, no faults, and an
empty-enough queue (degradation level 0), every request served through
this loop produces tokens BIT-IDENTICAL to the bare batcher — the loop
only ever calls the public batcher API between rounds, never inside the
traced step (``tests/test_serving_resilience.py`` enforces this, plus a
trace-count and host-overhead guard).

All device work stays on the caller/worker thread; the loop itself is
single-threaded and re-entrant only via :meth:`run_round`.
"""

from __future__ import annotations

import logging
import os
import statistics
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from rocket_tpu.models.generate import (HostReads, KVHandoff,
                                         export_kv_row)
from rocket_tpu.observe.critpath import replica_segments
from rocket_tpu.observe.ledger import expect_compile, get_goodput
from rocket_tpu.observe.recorder import active_recorder
from rocket_tpu.observe.trace import (TraceContext, get_requests,
                                      get_startup, get_tracer)
from rocket_tpu.serve.kvstore import page_hashes
from rocket_tpu.serve.metrics import (
    ClassLatency,
    ServeCounters,
    ServeLatency,
)
from rocket_tpu.serve.policy import DegradationPolicy
from rocket_tpu.serve.queue import AdmissionQueue
from rocket_tpu.serve.types import (
    Completed,
    DeadlineExceeded,
    Failed,
    HealthState,
    Overloaded,
    PreemptTicket,
    Request,
)
from rocket_tpu.serve.watchdog import DispatchWatchdog

LOG = logging.getLogger("rocket_tpu.serve")

# Clean turns (no admission) whose median is a turn's cost without one.
CLEAN_TURNS = 32


class _Row:
    """Host-side bookkeeping for one occupied batcher row."""

    __slots__ = ("req", "admitted_at", "submitted_at", "first_tok_at",
                 "prompt_len", "budget", "requested", "demoted",
                 "rounds_seen", "origin", "pool_fetch_ms", "parked_ms",
                 "admit_stall_ms", "stalled_turns")

    def __init__(self, req: Request, admitted_at: float, prompt_len: int,
                 budget: int, requested: int, demoted: bool,
                 submitted_at: Optional[float] = None,
                 resumes: Optional["_Row"] = None,
                 parked_ms: float = 0.0, pool_fetch_ms: float = 0.0) -> None:
        self.req = req
        self.admitted_at = admitted_at
        # submit() stamps the request; direct-admitted requests (tests)
        # fall back to admission time so latencies stay well-defined.
        self.submitted_at = (
            submitted_at if submitted_at is not None else admitted_at
        )
        self.first_tok_at: Optional[float] = None  # TTFT instant
        self.prompt_len = prompt_len
        self.budget = budget          # new-token cap actually enforced
        self.requested = requested    # what the caller asked for
        self.demoted = demoted        # beam request served greedy
        self.rounds_seen = 0          # carry row valid only after >= 1
        # The request's book, which critpath splits its time by: the row
        # of its first admission (``origin``: where queue wait, prefill
        # and the first token are read), its pool fetch, time parked by
        # preemption, and what other requests' admissions cost it while
        # it decoded.  A row that resumes a preempted one carries its book.
        self.origin: _Row = self
        self.pool_fetch_ms = pool_fetch_ms
        self.parked_ms = parked_ms
        self.admit_stall_ms = 0.0
        self.stalled_turns = 0
        if resumes is not None:
            self.origin = resumes.origin
            self.pool_fetch_ms = resumes.pool_fetch_ms
            self.parked_ms += resumes.parked_ms
            self.admit_stall_ms = resumes.admit_stall_ms
            self.stalled_turns = resumes.stalled_turns


class ServingLoop:
    """Robust serving driver over a factory-built ContinuousBatcher.

    ``batcher_factory`` must return a FRESH, un-started
    ``ContinuousBatcher`` each call — the watchdog recovery path
    abandons the wedged instance (a zombie worker may still write to
    it) and rebuilds from the factory.  ``max_batch`` fixes the row
    count; the loop warm-starts the batcher with a dummy group and
    serves every real request through :meth:`~ContinuousBatcher.admit`,
    which keeps each request bit-equal to its solo run regardless of
    arrival order.

    ``watchdog_timeout`` (seconds) arms the stuck-step detector; first
    executions of a new ``n_draft`` variant run inline (compiles are
    slow-by-design, not stuck).  ``beam_fn(prompt_2d, max_new) ->
    tokens [1, P+T]`` serves ``Request(beam=True)`` at degradation
    level 0; without it (or degraded) beam requests demote to the
    greedy lane.  ``sink`` is a tracker backend (``log_scalars``)
    receiving ``serve/*`` counters every ``flush_every`` rounds.
    ``clock`` is injectable for deterministic deadline tests; the
    watchdog always uses real time.  ``kv_cache_int8`` (None = defer to
    the factory's model configs) forces the int8 KV-cache layout on or
    off for every batcher the loop builds — including watchdog rebuilds.
    ``kvstore`` (a :class:`~rocket_tpu.serve.kvstore.PrefixKVStore`)
    arms the prefix-cache tier: admissions import the longest cached
    prefix and prefill only the uncached suffix, retiring rows export
    their pages back — outputs stay bit-equal to serving without the
    store.
    ``kvpool`` (a :class:`~rocket_tpu.serve.kvpool.KVPoolClient`;
    requires ``kvstore``) arms the FLEET page tier on top: an
    admit-miss consults the pool before cold prefill (local store →
    pool fetch → cold — a NACK only costs the prefill we were about to
    pay anyway), and retiring rows push their pages pool-ward so other
    replicas can import them.
    """

    def __init__(
        self,
        batcher_factory: Callable[[], Any],
        *,
        max_batch: int,
        queue_capacity: int = 64,
        watchdog_timeout: Optional[float] = None,
        policy: Optional[DegradationPolicy] = None,
        beam_fn: Optional[Callable[[np.ndarray, int], np.ndarray]] = None,
        clock: Callable[[], float] = time.monotonic,
        sink: Optional[Any] = None,
        flush_every: int = 8,
        recover_rounds: int = 4,
        tracer: Optional[Any] = None,
        recorder: Optional[Any] = None,
        logger: Optional[logging.Logger] = None,
        kv_cache_int8: Optional[bool] = None,
        replica_id: Optional[str] = None,
        kvstore: Optional[Any] = None,
        kvpool: Optional[Any] = None,
        warmup: Optional[Any] = None,
        class_weights: Optional[Dict[str, float]] = None,
        class_slot_budget: Optional[Dict[str, int]] = None,
        class_byte_budget: Optional[Dict[str, int]] = None,
    ) -> None:
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self._factory = batcher_factory
        # Serve-level int8 KV-cache knob: None defers to the factory's
        # models; True/False overrides EVERY build — the initial batcher
        # and any watchdog-recovery rebuild — via set_kv_cache_int8, so
        # a recovery cannot silently drop the quantized layout.
        self._kv_cache_int8 = kv_cache_int8
        self._max_batch = int(max_batch)
        self._beam_fn = beam_fn
        self._clock = clock
        self._sink = sink
        self._flush_every = int(flush_every)
        self._recover_rounds = int(recover_rounds)
        # Tracing (ISSUE 4): spans/instants go to the process tracer (a
        # no-op unless armed); latency histograms fill regardless (host
        # floats only — no device syncs) and flush as ``trace/*`` scalars.
        # ``recorder`` overrides the process-global flight recorder for
        # crash dumps on trips/step errors.
        self._tracer = tracer if tracer is not None else get_tracer()
        # Count compile-cache hits, misses and the trace / compile /
        # retrieval seconds whoever armed the cache directory (the
        # start-up line and the compile_cache/* export read them).
        from rocket_tpu.tune import compile_cache

        compile_cache.install_listeners()
        # Fleet identity: rides every typed result's ``meta`` and names
        # this loop's queue counters (``serve/queue/<replica>/...``).
        self.replica_id = replica_id
        # Multi-tenant fairness knobs pass straight through to the
        # weighted-fair admission queue (defaults match single-tenant
        # behavior exactly: standard class, no budgets).
        self.queue = AdmissionQueue(
            queue_capacity, name=replica_id, tracer=self._tracer,
            clock=clock, weights=class_weights,
            slot_budget=class_slot_budget,
            byte_budget=class_byte_budget,
        )
        self.policy = policy if policy is not None else DegradationPolicy()
        self.watchdog = DispatchWatchdog(watchdog_timeout)
        self.counters = ServeCounters()
        # Every blocking device→host read of this loop and its batchers:
        # one serve/fetch span and one host_fetches bump each.
        self._reads = HostReads(self._tracer, self.counters)
        self._recorder = recorder
        self.latency = ServeLatency()
        # Multi-tenant serving: per-SLO-class TTFT/e2e histograms (the
        # serve_slo/* attainment gauges read these) and the parked
        # resume tickets of preempted batch rows.
        self.slo_latency = ClassLatency()
        self._parked: List[PreemptTicket] = []
        self._last_health = HealthState.SERVING
        self._log = logger if logger is not None else LOG

        self._rows: Dict[int, Optional[_Row]] = {
            r: None for r in range(self._max_batch)
        }
        self._results: List[Any] = []
        self._draining = False
        self._recover_in = 0          # rounds left in post-trip DEGRADED
        self._round_ms: Optional[float] = None  # EMA, shed floor + policy
        self._carry: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._compiled_drafts: set = set()
        # The admission book (_book_turn): where the turn in progress
        # began (the last harvest's instant; None after an idle or failed
        # turn), the admissions it dispatched, recent clean turns (ms).
        self._turn_from: Optional[float] = None
        self._turn_admits = 0
        self._clean_turns_ms: deque = deque(maxlen=CLEAN_TURNS)

        # Warm-start tier (ISSUE 15): ``warmup`` is a WarmupPlan, its
        # wire dict, or ``"auto"`` (derive from the batcher config).
        # The plan AOT-compiles the hot-path executables against the
        # persistent compile cache BEFORE the inline warm round, so
        # ``_warm_start`` consumes pre-built executables instead of
        # compiling inline; stats land in ``self.warm_stats``.
        self._warmup = warmup
        self.warm_stats: Dict[str, Any] = {}

        # Prefix-cache tier (ISSUE 11): a PrefixKVStore shared across
        # this loop's lifetime (watchdog rebuilds included — pages are
        # host-side numpy, a wedged device step cannot poison them).
        # Admission looks up the longest cached prefix and prefills only
        # the uncached suffix; completing rows export their pages back.
        self.kvstore = kvstore
        # Fleet page tier (ISSUE 16): a KVPoolClient consulted on local
        # admit-miss and fed on retire.  Strictly an accelerant — every
        # pool failure degrades to cold prefill.
        if kvpool is not None and kvstore is None:
            raise ValueError("kvpool requires kvstore (pages land in the "
                             "local store before admission imports them)")
        self.kvpool = kvpool

        # Train-while-serve (ISSUE 17): the newest published weights this
        # loop has applied.  ``_live_params`` survives watchdog rebuilds
        # (the factory would otherwise revert a rebuilt batcher to its
        # closure's original — possibly donated-away — weights);
        # ``_prev_weights`` anchors the bounded rollback.
        self._live_params: Optional[Any] = None
        self._weights_version: int = -1
        self._weights_path: Optional[str] = None
        self._prev_weights: Optional[Tuple[int, str]] = None

        self._bat = self._build_batcher()
        self.base_n_draft = int(self._bat.n_draft)
        if self.kvstore is not None and not self._bat.prefix_cache_ok:
            raise ValueError(
                "kvstore needs the position==slot cache layout; the "
                "factory's models use decode_rolling_cache"
            )
        self._warm_start(self._bat)
        # Built and warm, the loop reports SERVING: start-up is over, its
        # line goes out (once a process) and its record closes.
        get_startup().log_once(self._log)

    # -- lifecycle -----------------------------------------------------

    def _build_batcher(self) -> Any:
        """Factory call + the loop-level knobs every build must carry."""
        bat = self._factory()
        bat.reads = self._reads
        if self._kv_cache_int8 is not None:
            bat.set_kv_cache_int8(self._kv_cache_int8)
        if self._live_params is not None:
            # A rebuild after a hot-swap must serve the SWAPPED weights:
            # the factory closure's originals may already be donated away.
            bat._params = self._live_params
        return bat

    def _warm_start(self, bat: Any) -> None:
        """Start the batcher on a dummy all-retired group and run one
        inline round so the base ``n_draft`` executable is warm before
        the watchdog ever times a dispatch.  Serving everything via
        ``admit`` afterwards keeps per-request outputs independent of
        the warm group (admit rebuilds the row's state from scratch).

        With a :class:`~rocket_tpu.tune.warmup.WarmupPlan` armed, the
        plan runs FIRST: AOT ``lower().compile()`` (or a deserialized
        executable) against the persistent compile cache, so the inline
        round below — and the ledgered dispatches after it — hit
        pre-built executables.  ``_compiled_drafts`` still tracks the
        jit DISPATCH cache (AOT does not populate it), so the inline
        ``expect_compile`` discipline is unchanged; on a warm host the
        "compile" it expects is a disk-cache retrieval."""
        with get_startup().phase("startup/serve_warm_start"):
            self._warm_start_inner(bat)
        # the first served round's host gap starts at a read of its own,
        # and its turn is no measure of one
        self._reads.returned_at = None
        self._turn_from = None

    def _warm_start_inner(self, bat: Any) -> None:
        if self._warmup is not None:
            try:
                from rocket_tpu.tune.warmup import (WarmupPlan,
                                                    plan_for_batcher,
                                                    warm_batcher)
                plan = self._warmup
                if plan == "auto":
                    plan = plan_for_batcher(bat, self._max_batch)
                elif isinstance(plan, dict):
                    plan = WarmupPlan.from_wire(plan)
                self.warm_stats = warm_batcher(bat, plan)
            except Exception:
                self._log.warning(
                    "warmup plan failed; falling back to inline compile",
                    exc_info=True)
        warm = np.zeros((self._max_batch, 1), np.int32)
        bat.start(warm)
        for r in range(self._max_batch):
            bat.retire(r)
        with expect_compile("generate/spec_round"):
            bat.step()  # inline: compile, not serve
        self._compiled_drafts = {int(bat.n_draft)}
        self._carry = (self._reads(bat.state[0], "buf"),
                       self._reads(bat.state[1], "n_tok"))

    @property
    def health(self) -> HealthState:
        if self._draining:
            return HealthState.DRAINING
        if self._recover_in > 0 or self.policy.level > 0:
            return HealthState.DEGRADED
        return HealthState.SERVING

    def drain(self) -> None:
        """Stop admitting new work; queued + in-flight requests finish."""
        self._draining = True
        self._observe_health()

    def _observe_health(self) -> None:
        """Record health-state transitions as typed tracer events — the
        flight recorder's timeline then shows WHEN the loop degraded,
        not just that it did."""
        state = self.health
        if state is not self._last_health:
            self._tracer.health(
                "serve/health", state.value, prev=self._last_health.value,
                level=self.policy.level, queue_depth=len(self.queue),
            )
            self._last_health = state

    def close(self) -> None:
        self._flush(force=True)
        # what the rounds counted on the device goes to the process-wide
        # record now (observe.trace.get_rounds): readers come after us
        publish = getattr(self._bat, "publish_counters", None)
        if publish is not None:
            publish()
        self.watchdog.close()
        if self.kvpool is not None:
            try:
                self.kvpool.close()
            except Exception:
                pass

    # -- submission ----------------------------------------------------

    def _meta(self) -> Dict[str, Any]:
        """WHERE a result was decided: replica identity + degradation
        level at the moment of the decision — stamped on every typed
        result so fleet tests can assert routing without internals."""
        return {"replica": self.replica_id, "level": self.policy.level}

    @staticmethod
    def _promote(req: Request) -> None:
        """Tail-sample a bad outcome: force the request's trace context
        sampled, so the flow chain survives even when head-sampling
        skipped it.  The requests worth debugging are always traced."""
        ctx = getattr(req, "_ctx", None)
        if ctx is not None:
            ctx.sampled = True

    def _flow(self, req: Request, phase: str, **fields: Any) -> None:
        """Emit a request-flow event when the request is sampled."""
        ctx = getattr(req, "_ctx", None)
        if ctx is not None and ctx.sampled:
            self._tracer.flow("serve/request", phase, ctx.flow_id,
                              rid=req.rid, **fields)

    @property
    def load(self) -> int:
        """Queued + in-flight + parked request count — the least-loaded
        routing signal a :class:`~rocket_tpu.serve.router.FleetRouter`
        reads.  Parked (preempted) requests count: they still owe a
        result, and a replica camping on parked batch work is not as
        idle as its rows suggest."""
        return len(self.queue) + len(self._live_rows()) + len(self._parked)

    @property
    def parked(self) -> List[PreemptTicket]:
        """The parked resume tickets of preempted batch rows (read-only
        view — the loop owns the re-admission order)."""
        return list(self._parked)

    def submit(self, req: Request, *,
               record_rejection: bool = True) -> Optional[Overloaded]:
        """Enqueue a request.  Returns ``None`` on acceptance, or the
        typed :class:`Overloaded` rejection (also appended to
        :meth:`drain_results`) when the queue is full or the loop is
        draining — admission control answers IMMEDIATELY.

        ``record_rejection=False`` makes a refusal side-effect-free (no
        counters, no result recorded): a fleet router probing replicas
        owns the request's single typed result, and a refusal here just
        means "try the next replica"."""
        # Queue-wait / TTFT / e2e all measure from this stamp (the loop
        # clock, so fake-clock tests stay deterministic).  Request is a
        # plain dataclass — the private stamp rides the object.
        req._submit_ts = self._clock()
        # Distributed tracing: a request arriving without a context (the
        # local entry point) gets a fresh head-sampled one; a wire-borne
        # request keeps the one the submitter stamped.
        ctx = getattr(req, "_ctx", None)
        if ctx is None:
            ctx = TraceContext.make(req.rid)
            req._ctx = ctx
        self._tracer.instant("serve/submit", rid=req.rid,
                             cls=req.slo_class, trace_id=ctx.trace_id)
        if ctx.sampled:
            # the flow chain starts at the first hop (empty parent) and
            # steps through every later process the request enters
            self._tracer.flow("serve/request",
                              "s" if not ctx.parent else "t",
                              ctx.flow_id, rid=req.rid)
        if self._draining:
            rej = Overloaded(req.rid, self._clock(), reason="draining",
                             meta=self._meta())
        elif not self.queue.offer(req):
            rej = Overloaded(req.rid, self._clock(), reason="queue full",
                             meta=self._meta())
        else:
            self.counters.submitted += 1
            self.counters.observe_class(req.slo_class, "submitted")
            return None
        if record_rejection:
            ctx.sampled = True  # bad outcome: promote past head-sampling
            self.counters.submitted += 1
            self.counters.observe_class(req.slo_class, "submitted")
            self.counters.shed_overload += 1
            self.counters.observe_class(req.slo_class, "shed")
            self._tracer.instant("serve/overloaded", rid=req.rid,
                                 reason=rej.reason)
            self._results.append(rej)
        return rej

    def submit_prefilled(self, req: Request, handoff: Any, *,
                         record_rejection: bool = True
                         ) -> Optional[Overloaded]:
        """Submit a request whose prefill already ran on another lane
        (a :class:`~rocket_tpu.models.generate.KVHandoff`): admission
        imports the handed-off KV rows instead of prefilling, so long
        prompts never stall this loop's decode rounds."""
        req._handoff = handoff
        return self.submit(req, record_rejection=record_rejection)

    def salvage(self) -> List[Request]:
        """Strip every queued and in-flight request out of the loop
        WITHOUT emitting results for them — the fleet self-healing hook:
        the router re-enqueues the salvaged requests (remaining deadline
        intact) on a healthy replica, which then owns each one's single
        typed result.  In-flight rows retire so their slots go idle."""
        salvaged: List[Request] = []
        while True:
            req = self.queue.pop()
            if req is None:
                break
            salvaged.append(req)
        # Parked (preempted) requests salvage as their ORIGINAL request:
        # the healthy replica re-serves from scratch, which is bit-equal
        # by determinism — the ticket's cached progress dies with this
        # replica, the exactly-once contract does not.
        for ticket in self._parked:
            salvaged.append(ticket.req)
        self._parked = []
        for row, occ in self._rows.items():
            if occ is None:
                continue
            salvaged.append(occ.req)
            try:
                self._bat.retire(row)
            except Exception:  # a wedged batcher cannot even retire
                pass
            self._rows[row] = None
        return salvaged

    def drain_results(self) -> List[Any]:
        """Return and clear all typed results produced so far."""
        out, self._results = self._results, []
        return out

    # -- live weight hot-swap (train-while-serve) ----------------------

    @property
    def weights_version(self) -> int:
        """Newest applied published version (-1 = factory weights)."""
        return self._weights_version

    def swap_weights(self, path: str, version: Optional[int] = None, *,
                     deep_verify: bool = True) -> bool:
        """Hot-swap the target params onto a committed publication at
        ``path`` — called BETWEEN decode rounds only (the worker's
        one-in-flight RPC discipline makes that structural; an
        in-process caller must not call this from inside
        :meth:`run_round`).

        The gate sequence is verify → locate → ``check_reshard`` →
        restore-to-host → donation swap: the publication is integrity-
        verified (``deep_verify`` re-checksums every leaf, which is what
        catches a garbled-on-disk publication the commit marker cannot),
        its manifest locates the params subtree (a trainer publishes its
        whole TrainState; only the params restore), the reshard gate
        validates every leaf against THIS loop's mesh placement, and the
        device swap is per-leaf delete-then-put — the old leaf's buffer
        is freed before the new one uploads, so HBM never holds two full
        copies of the model.  The batcher's params are a jit *argument*
        (same shapes/dtypes/shardings), so the swap costs zero retrace.

        In-flight rows keep their KV pages and simply continue — their
        remaining tokens decode under the new weights from the next
        round boundary on; requests admitted after the swap are
        end-to-end bit-equal to a server freshly loaded from the same
        publication.  Any failure rejects the publication: counter +
        flight dump, serving continues on the old weights untouched.

        Wall time charges to the ``swap`` goodput bucket and the
        ``swap_ms_total`` counter."""
        t0 = time.monotonic()
        with get_goodput().timed("swap"):
            ok = self._swap_inner(path, version, deep_verify,
                                  rollback=False)
        self.counters.swap_ms_total += (time.monotonic() - t0) * 1e3
        return ok

    def rollback_weights(self) -> bool:
        """Bounded rollback: re-swap onto the PREVIOUS applied published
        version (the divergence remedy).  One step deep by design — the
        publisher retains ``keep >= 2`` publications, so the previous
        path still exists when divergence is noticed.  ``False`` when
        no previous published version exists."""
        prev = self._prev_weights
        if prev is None:
            self._log.warning(
                "serve: rollback requested but no previous published "
                "version is known")
            return False
        version, path = prev
        t0 = time.monotonic()
        with get_goodput().timed("swap"):
            ok = self._swap_inner(path, version, deep_verify=True,
                                  rollback=True)
        self.counters.swap_ms_total += (time.monotonic() - t0) * 1e3
        return ok

    def _swap_inner(self, path: str, version: Optional[int],
                    deep_verify: bool, rollback: bool) -> bool:
        import jax

        from rocket_tpu.persist import integrity
        from rocket_tpu.persist.orbax_io import CheckpointIO
        from rocket_tpu.serve.worker import _locate_params

        path = os.path.abspath(path)
        ok, reason = integrity.verify(path, deep=deep_verify)
        if not ok:
            return self._reject_publish(path, reason)
        manifest = integrity.read_manifest(path)
        if version is None:
            v = (manifest or {}).get("iter_idx")
            version = int(v) if isinstance(v, int) else -1
        item_key, prefix = _locate_params(manifest)
        old = self._bat._params
        nested: Any = old
        for part in reversed(prefix):
            nested = {part: nested}
        try:
            integrity.check_reshard(manifest, {item_key: nested})
        except integrity.TopologyMismatch as exc:
            return self._reject_publish(path, f"topology: {exc}")
        # Restore to HOST numpy first: the publication lands in host RAM
        # only, so the device-side swap below can free each old leaf
        # before uploading its replacement.
        host_nested = jax.tree_util.tree_map(
            lambda x: np.empty(tuple(getattr(x, "shape", ())),
                               getattr(x, "dtype", np.float32)),
            nested,
        )
        io = CheckpointIO(use_async=False)
        try:
            out = io.restore_item(path, item_key, target=host_nested,
                                  partial=bool(prefix))
        except Exception as exc:
            return self._reject_publish(path, f"restore failed: {exc!r}")
        finally:
            io.close()
        for part in prefix:
            out = out[part]
        with self._tracer.span("serve/swap", path=path, version=version,
                               rollback=rollback):
            new_params = self._donation_swap(old, out)
        self._bat._params = new_params
        self._live_params = new_params
        if rollback:
            self.counters.swap_rollbacks += 1
            self._prev_weights = None
        else:
            if self._weights_path is not None:
                self._prev_weights = (self._weights_version,
                                      self._weights_path)
            self.counters.swaps += 1
        self._weights_version = int(version)
        self._weights_path = path
        self.counters.weights_version = int(version)
        self._log.info(
            "serve: weights %s -> version %d (%s)",
            "rolled back" if rollback else "hot-swapped", version, path)
        return True

    @staticmethod
    def _donation_swap(old_tree: Any, new_host_tree: Any) -> Any:
        """Per-leaf donation: free the old device buffer, THEN upload
        the replacement onto the same sharding — peak device residency
        is one model plus one leaf, never two models."""
        import jax

        def leaf(old: Any, new: Any) -> Any:
            sharding = getattr(old, "sharding", None)
            dtype = getattr(old, "dtype", None)
            # The replacement must present the IDENTICAL jit signature —
            # dtype, sharding, AND commitment: device_put(x, sharding)
            # commits, but seed-initialised params are uncommitted, and
            # a committed/uncommitted flip alone retraces the round.
            committed = bool(getattr(old, "committed", False))
            new = np.asarray(new)
            if dtype is not None and new.dtype != dtype:
                new = new.astype(dtype)
            if hasattr(old, "delete"):
                try:
                    old.delete()
                except Exception:
                    pass  # already donated / deleted elsewhere
            if sharding is not None and committed:
                return jax.device_put(new, sharding)
            return jax.device_put(new)

        return jax.tree_util.tree_map(leaf, old_tree, new_host_tree)

    def _reject_publish(self, path: str, reason: str) -> bool:
        """A publication that fails any gate is REJECTED, never
        half-applied: count it, dump the flight recorder for the
        post-mortem, keep serving the current weights."""
        self.counters.publish_rejected += 1
        self._tracer.instant("serve/publish_rejected", path=path,
                             reason=str(reason)[:200])
        dump = self._dump_flight("publish-rejected")
        self._log.warning(
            "serve: publication %s rejected (%s)%s", path, reason,
            f" — flight dump {dump}" if dump else "")
        return False

    # -- the round -----------------------------------------------------

    def run_round(self) -> bool:
        """One full serving round: shed hopeless queue entries, admit
        into free rows, dispatch ONE speculative round (under the
        watchdog once warm), harvest finished / expired / capped rows,
        update the degradation ladder.  Returns ``True`` if any device
        work ran (False = completely idle)."""
        now = self._clock()
        with self._tracer.span("serve/shed"):
            self._shed_hopeless(now)
            self._preempt_batch(now)
        self._admit_pending(now)
        if not self._live_rows():
            # idle: the time to the next dispatch is no host gap, nor
            # the time to the next harvest a turn
            self._reads.returned_at = None
            self._turn_from = None
            self._flush()
            return False

        ok = self._dispatch()
        if ok:
            with self._tracer.span("serve/harvest"):
                self._harvest(self._clock())
            if self._recover_in > 0:
                self._recover_in -= 1
        else:
            self._turn_from = None
        with self._tracer.span("serve/policy"):
            self._update_policy()
        self._observe_health()
        self._flush()
        return True

    def run_until_idle(self, max_rounds: int = 10_000) -> List[Any]:
        """Drive rounds until the queue is empty and no row is live;
        returns the accumulated typed results."""
        for _ in range(max_rounds):
            if not self.queue and not self._live_rows() \
                    and not self._parked:
                break
            self.run_round()
        else:
            raise RuntimeError(
                f"run_until_idle: still busy after {max_rounds} rounds"
            )
        return self.drain_results()

    # -- internals -----------------------------------------------------

    def _live_rows(self) -> List[int]:
        return [r for r, occ in self._rows.items() if occ is not None]

    def _shed_hopeless(self, now: float) -> None:
        """Queue entries that cannot produce a first round before their
        deadline are shed pre-prefill — the floor is one observed round
        (0 until measured, so nothing is shed before evidence exists)."""
        floor_s = (self._round_ms or 0.0) / 1e3
        for req in self.queue.shed_hopeless(now, floor_s):
            self.counters.shed_deadline += 1
            self.counters.observe_class(req.slo_class, "shed")
            self._promote(req)
            self._flow(req, "f", outcome="shed_deadline")
            self._results.append(
                DeadlineExceeded(req.rid, now, stage="queue",
                                 meta=self._meta())
            )

    def _preempt_batch(self, now: float) -> None:
        """Round-boundary batch preemption: when non-batch requests are
        waiting and the free rows cannot seat them, evict batch-class
        in-flight rows — export their KV pages through the normal retire
        path (`_store_row`), park a typed resume ticket, free the row.
        No result is emitted here: the RESUMED run owes the request's
        single typed result, and resuming from the cached prefix is
        bit-equal to never having been preempted (the prefix-cache
        tier's acceptance oracle).  Host-side bookkeeping only — the
        export/retire/admit edges already exist, no new jit traces."""
        urgent = self.queue.urgent_waiting()
        if urgent == 0:
            return
        free = sum(1 for occ in self._rows.values() if occ is None)
        need = urgent - free
        if need <= 0:
            return
        victims = [(row, occ) for row, occ in self._rows.items()
                   if occ is not None and occ.req.slo_class == "batch"]
        if not victims:
            return
        # Least progress first: the cheapest resume (fewest pages to
        # re-import) and the least decode work at risk of cache churn.
        n_tok_h = self._reads(self._bat.state[1], "n_tok")
        victims.sort(key=lambda pair: (int(n_tok_h[pair[0]]), pair[0]))
        for row, occ in victims[:need]:
            toks, nt = self._bat.row_tokens(row)
            self._store_row(row)
            self._bat.retire(row)
            self._rows[row] = None
            req = occ.req
            req._preempted_row = occ    # the resumed row carries its book
            produced = max(0, nt - int(req.prompt.shape[0]))
            self._parked.append(PreemptTicket(
                req=req, tokens=np.asarray(toks[:nt], np.int32),
                produced=produced, preempted_at=now,
            ))
            self.counters.preempted += 1
            self.counters.observe_class(req.slo_class, "preempted")
            self._promote(req)
            self._tracer.instant("serve/preempt", rid=req.rid, row=row,
                                 n_tok=nt, produced=produced)

    def _admit_pending(self, now: float) -> None:
        level = self.policy.current
        for row in list(self._rows):
            if self._rows[row] is not None:
                continue
            # keep popping until this row is filled or the queue empties
            # (beam-lane serves and at-pop deadline sheds consume the
            # popped entry without occupying the row)
            while self._rows[row] is None:
                ticket: Optional[PreemptTicket] = None
                if self._parked and self.queue.urgent_waiting() == 0:
                    # parked batch resumes ahead of NEWER queued batch
                    # (it was admitted first), but never ahead of a
                    # waiting interactive/standard request
                    ticket = self._parked.pop(0)
                    req = ticket.req
                else:
                    req = self.queue.pop()
                if req is None:
                    return
                if req.deadline is not None and req.deadline <= now:
                    self.counters.shed_deadline += 1
                    self.counters.observe_class(req.slo_class, "shed")
                    self._promote(req)
                    self._flow(req, "f", outcome="shed_deadline")
                    if ticket is not None:
                        # it decoded before parking — ship the partial
                        self._results.append(DeadlineExceeded(
                            req.rid, now, tokens=ticket.tokens,
                            n_tok=int(ticket.tokens.shape[0]),
                            stage="decode", meta=self._meta(),
                            submitted_at=getattr(req, "_submit_ts", None),
                            due_at=req.due_at,
                        ))
                    else:
                        self._results.append(
                            DeadlineExceeded(req.rid, now, stage="queue",
                                             meta=self._meta())
                        )
                elif ticket is None and req.beam and level.beam \
                        and self._beam_fn is not None:
                    self._serve_beam(req, now)
                else:
                    self._admit_row(row, req, now, resume=ticket)

    def _budget(self, req: Request, prompt_len: int) -> Tuple[int, int]:
        """(enforced new-token budget, requested new-token count)."""
        room = self._bat.total_len - prompt_len
        requested = room if req.max_new_tokens is None \
            else min(req.max_new_tokens, room)
        cap = self.policy.current.max_new_cap
        budget = requested if cap is None else min(requested, cap)
        return max(1, budget), max(1, requested)

    def _resume_budget(self, req: Request,
                       ticket: PreemptTicket) -> Tuple[int, int]:
        """Remaining budget for a resumed row: what the original request
        asked for, minus what the preempted run already produced — so a
        preempted-then-resumed request stops at exactly the same token
        count as an uninterrupted one."""
        nt = int(ticket.tokens.shape[0])
        room = self._bat.total_len - nt
        requested = room if req.max_new_tokens is None \
            else min(req.max_new_tokens - int(ticket.produced), room)
        cap = self.policy.current.max_new_cap
        budget = requested if cap is None else min(requested, cap)
        return max(1, budget), max(1, requested)

    def _admit_row(self, row: int, req: Request, now: float, *,
                   resume: Optional[PreemptTicket] = None) -> None:
        # A resumed admission replays the preempted run's full token
        # prefix as the prompt: the kvstore lookup below imports the
        # pages the preemption exported, so only the page-unaligned tail
        # re-prefills.  req stays the ORIGINAL request (rid, deadline,
        # class) — the continuation is indistinguishable downstream.
        prompt = req.prompt if resume is None else resume.tokens
        if resume is None:
            budget, requested = self._budget(req, prompt.shape[0])
        else:
            budget, requested = self._resume_budget(req, resume)
            self.counters.resumed += 1
            self.counters.observe_class(req.slo_class, "resumed")
            self._tracer.instant("serve/resume", rid=req.rid, row=row,
                                 n_tok=int(prompt.shape[0]))
        demoted = bool(req.beam)
        if demoted and resume is None:
            self.counters.beam_demoted += 1
        submitted = getattr(req, "_submit_ts", None)
        wait_ms = (now - submitted) * 1e3 if submitted is not None else 0.0
        if resume is None:
            self.latency.queue_wait_ms.record(wait_ms)
        handoff = getattr(req, "_handoff", None)
        match = None
        pool_ms = 0.0
        if handoff is None and self.kvstore is not None:
            match = self.kvstore.lookup(prompt)
            if match is None and self.kvpool is not None:
                fetch_from = self._clock()
                match = self._pool_fetch(prompt, req)
                pool_ms = (self._clock() - fetch_from) * 1e3
        self._flow(req, "t", hop="admit")
        # The admit IS the row's prefill (the batcher rebuilds the row's
        # cache from the prompt).  The span times its DISPATCH: on a CPU
        # that is the prefill itself, on a TPU the program runs after
        # the span has closed, and every row waits for it before the
        # round; the request's book times its prefill to the harvest of
        # its first token instead (_finish_latency).
        # A handed-off request skips the prefill: its KV rows import as
        # one cheap scatter dispatch (the prefill/decode lane split).
        # A kvstore prefix hit imports the cached pages and prefills
        # only the uncached suffix — same scatter path, same bit-equal
        # outcome as a full prefill.
        self._turn_admits += 1
        with self._tracer.span(
            "serve/admit", rid=req.rid, row=row,
            prompt_len=int(prompt.shape[0]), queue_wait_ms=wait_ms,
            prefilled=handoff is not None,
            kv_hit_tokens=match.tokens if match is not None else 0,
        ):
            if handoff is not None:
                self._bat.admit_prefilled(row, handoff)
                req._handoff = None
                self.counters.prefilled_admits += 1
            elif match is not None:
                try:
                    self._bat.admit_prefilled(
                        row,
                        self._bat.prefill_from_pages(
                            prompt[None, :], match.pages),
                    )
                finally:
                    self.kvstore.release(match)
                self.counters.kv_hits += 1
                self.counters.kv_hit_tokens += match.tokens
            else:
                self._bat.admit(row, prompt[None, :])
        if resume is None:
            self._rows[row] = _Row(req, now, prompt.shape[0], budget,
                                   requested, demoted,
                                   submitted_at=submitted,
                                   pool_fetch_ms=pool_ms)
        else:
            # parked from the preemption to this turn and through the
            # resume's pool fetch
            parked_ms = (now - resume.preempted_at) * 1e3 + pool_ms
            self._rows[row] = _Row(req, now, prompt.shape[0], budget,
                                   requested, demoted,
                                   submitted_at=submitted,
                                   resumes=getattr(req, "_preempted_row",
                                                   None),
                                   parked_ms=parked_ms)
            req._preempted_row = None
        self.counters.admitted += 1

    def _pool_fetch(self, prompt: np.ndarray,
                    req: Optional[Request] = None) -> Optional[Any]:
        """Local admit-miss → consult the fleet page pool.  Fetched
        pages land in the LOCAL store first (put_pages), then a normal
        lookup pins them — admission then proceeds exactly as a local
        hit, so bit-equality and pin discipline need no second path.
        Any failure (NACK, dead pool, layout mismatch) returns ``None``
        and the admit falls through to cold prefill."""
        rid = req.rid if req is not None else None
        ctx = getattr(req, "_ctx", None) if req is not None else None
        try:
            with self._tracer.span("serve/pool_fetch", rid=rid) as sp:
                hashes = page_hashes(prompt, self.kvstore.page_tokens,
                                     limit=int(prompt.shape[0]) - 1)
                if not hashes:
                    return None
                pages = self.kvpool.fetch(hashes, ctx=ctx)
                if not pages:
                    self.counters.pool_nacks += 1
                    sp.add(nack=True)
                    return None
                self.kvstore.put_pages(hashes[:len(pages)], pages)
                match = self.kvstore.lookup(prompt)
                if match is not None:
                    self.counters.pool_hits += 1
                    self.counters.pool_hit_tokens += match.tokens
                    sp.add(hit_tokens=match.tokens)
                return match
        except Exception:
            self._log.warning("serve: kvpool fetch failed", exc_info=True)
            return None

    def _serve_beam(self, req: Request, now: float) -> None:
        """Level-0 beam lane: one inline beam call (its own prefill,
        not a batcher row).  Under pressure the ladder flips
        ``beam=False`` and these requests demote to the greedy lane."""
        budget, _ = self._budget(req, req.prompt.shape[0])
        self._turn_admits += 1          # the rows wait for it too
        with self._tracer.span("serve/beam", rid=req.rid,
                               prompt_len=int(req.prompt.shape[0])):
            toks = self._reads(
                self._beam_fn(req.prompt[None, :], budget), "beam")
        toks = toks[0] if toks.ndim == 2 else toks
        self.counters.admitted += 1
        self.counters.beam_served += 1
        self.counters.completed += 1
        self.counters.observe_class(req.slo_class, "completed")
        done = self._clock()
        submitted = getattr(req, "_submit_ts", now)
        self.latency.queue_wait_ms.record((now - submitted) * 1e3)
        self.latency.e2e_ms.record((done - submitted) * 1e3)
        self.slo_latency.record_e2e(req.slo_class, (done - submitted) * 1e3)
        self._flow(req, "f", outcome="beam")
        self._results.append(Completed(
            req.rid, done, tokens=toks, n_tok=int(toks.shape[0]),
            via_beam=True, meta=self._meta(), submitted_at=submitted,
            admitted_at=now, due_at=req.due_at,
        ))

    def _dispatch(self) -> bool:
        """ONE speculative round + host fetch, watched once the current
        ``n_draft`` executable is warm.  On a trip or a step exception,
        fail in-flight rows and rebuild the batcher."""
        bat = self._bat  # bind NOW: a zombie must not see a rebuilt self._bat
        n_draft = int(bat.n_draft)

        reads = self._reads

        def _step():
            # the host's own view of the gap between rounds: return of
            # its last device read to this dispatch
            last = reads.returned_at
            gap_ms = None if last is None \
                else (time.perf_counter() - last) * 1e3
            n_tok, done = bat.step()
            return reads(bat.state[0], "buf"), n_tok, done, gap_ms

        t0 = time.monotonic()
        # The per-round decode span: it CLOSES when the with-block exits
        # (trip, exception, or success alike), so by the time a failure
        # path dumps the flight recorder, the stuck round's span is
        # already the last thing in the ring (ISSUE 4 acceptance).
        round_span = self._tracer.span(
            "serve/round", round=self.counters.rounds + 1,
            n_draft=n_draft, live=len(self._live_rows()),
        )
        try:
            with round_span:
                if n_draft not in self._compiled_drafts:
                    # first build of this variant: compile inline, unwatched
                    # — and DELIBERATE, so the retrace sentinel must not
                    # treat the new n_draft signature as a shape bug
                    round_span.add(compile=True)
                    with expect_compile("generate/spec_round"):
                        ok, value = True, _step()
                    self._compiled_drafts.add(n_draft)
                else:
                    ok, value = self.watchdog.run(_step)
                if not ok:
                    round_span.add(tripped=True)
        except Exception as exc:  # step raised on worker/caller thread
            self._log.warning("serve: step failed: %r", exc)
            dump = self._dump_flight("step-error")
            self._fail_inflight(f"step error: {exc!r}", dump_path=dump)
            self._rebuild()
            return False
        if not ok:
            self._log.warning(
                "serve: watchdog trip (> %.3fs); rebuilding batcher",
                self.watchdog.timeout,
            )
            self.counters.watchdog_trips += 1
            dump = self._dump_flight("watchdog-trip")
            self._fail_inflight("watchdog: stuck device step",
                                dump_path=dump)
            self._rebuild()
            return False

        buf, n_tok, done, gap_ms = value
        self._carry = (buf, n_tok)
        round_ms = (time.monotonic() - t0) * 1e3
        self.counters.observe_round_ms(round_ms)
        if gap_ms is not None:
            self.counters.observe_round_gap_ms(gap_ms)
        self._round_ms = self.counters.round_ms_ema
        now = self._clock()
        for occ in self._rows.values():
            if occ is not None:
                occ.rounds_seen += 1
                if occ.rounds_seen == 1:
                    # first harvested round containing this row's first
                    # generated token — the TTFT instant
                    occ.first_tok_at = now
                    if not getattr(occ.req, "_ttft_done", False):
                        # a resumed row's first token already happened
                        # before preemption — never re-record its TTFT
                        occ.req._ttft_done = True
                        ttft_ms = (now - occ.submitted_at) * 1e3
                        self.latency.ttft_ms.record(ttft_ms)
                        self.slo_latency.record_ttft(
                            occ.req.slo_class, ttft_ms)
                        self._tracer.instant(
                            "serve/first_token", rid=occ.req.rid,
                            ttft_ms=ttft_ms, cls=occ.req.slo_class)
        return True

    @staticmethod
    def _stamps(occ: _Row) -> Dict[str, Any]:
        """The row's instants (loop clock) and the request's ``due_at``,
        for the typed result: enough to compute queue wait, TTFT and
        TPOT from the result alone."""
        return {"submitted_at": occ.submitted_at,
                "admitted_at": occ.admitted_at,
                "first_token_at": occ.first_tok_at,
                "due_at": occ.req.due_at}

    def _inflight_requests(self) -> List[Request]:
        """Every request this loop currently owes a result for: queued,
        in a row, or parked — the flight recorder's inventory."""
        out: List[Request] = [occ.req for occ in self._rows.values()
                              if occ is not None]
        out.extend(t.req for t in self._parked)
        out.extend(self.queue.pending())
        return out

    def _dump_flight(self, reason: str) -> Optional[str]:
        """Write a flight-recorder dump (loop-local recorder if given,
        else the process-global one); ``None`` when neither is armed.
        Tail-sampling: the dump metadata lists every in-flight rid with
        its trace_id, and their contexts promote to sampled — a flight
        dump is always navigable by request, even at low sampling rates.
        Never raises — the recovery path must run regardless."""
        rec = self._recorder if self._recorder is not None \
            else active_recorder()
        if rec is None:
            return None
        inflight = []
        for req in self._inflight_requests():
            self._promote(req)
            ctx = getattr(req, "_ctx", None)
            inflight.append({
                "rid": req.rid, "cls": req.slo_class,
                "trace_id": ctx.trace_id if ctx is not None else None,
            })
        try:
            return rec.dump(reason, extra_meta={"inflight": inflight})
        except Exception:
            self._log.warning("serve: flight dump failed", exc_info=True)
            return None

    def _partial(self, row: int, occ: _Row) -> Tuple[Optional[np.ndarray],
                                                     int]:
        """Last-good-carry partial tokens for a row, valid only after
        the row has survived at least one fetched round (a fresh admit's
        carry row still holds the previous occupant's data)."""
        if self._carry is None or occ.rounds_seen < 1:
            return None, 0
        buf, n_tok = self._carry
        n = int(n_tok[row])
        return np.asarray(buf[row][:n]), n

    def _fail_inflight(self, reason: str,
                       dump_path: Optional[str] = None) -> None:
        now = self._clock()
        for row, occ in self._rows.items():
            if occ is None:
                continue
            toks, n = self._partial(row, occ)
            self.counters.failed += 1
            self._promote(occ.req)
            self._flow(occ.req, "f", outcome="failed")
            self._tracer.instant("serve/failed", rid=occ.req.rid,
                                 row=row, reason=reason)
            self._results.append(Failed(
                occ.req.rid, now, tokens=toks, n_tok=n, reason=reason,
                dump_path=dump_path, meta=self._meta(),
                **self._stamps(occ),
            ))
            self._rows[row] = None

    def _rebuild(self) -> None:
        """Abandon the wedged batcher (the zombie worker may still
        write to it — harmless, nothing reads it) and warm-start a
        fresh one.  The persistent ``_spec_round`` jit cache keys on
        structurally-hashed modules, so this does NOT retrace; the cost
        is one dummy prefill + round."""
        with get_goodput().timed("watchdog_rebuild"):
            self._bat = self._build_batcher()
            self._bat.n_draft = self.policy.n_draft(self.base_n_draft)
            self._warm_start(self._bat)
        self._recover_in = self._recover_rounds

    def _book_turn(self, now: float) -> None:
        """The admission book, at the harvest that ends a turn.  A turn
        runs from the previous harvest's ``now`` to this one's, on the
        loop's clock; a turn that dispatched no admission (a fresh
        admit, resume, prefilled or prefix import, or beam serve: device
        work queued before the round) is clean and joins the window of
        the last ``CLEAN_TURNS`` clean turns.  A turn with admissions
        books ``max(0, turn - median clean turn)`` on every row that was
        decoding before it (``rounds_seen`` > 1 once this turn's round
        has counted), and counts the turn on it; the rows it admitted
        get nothing.  Before a clean turn has been seen nothing is
        booked.  Host arithmetic only: no fetch, sync or dispatch."""
        start, self._turn_from = self._turn_from, now
        admits, self._turn_admits = self._turn_admits, 0
        if start is None:
            return
        turn_ms = (now - start) * 1e3
        if not admits:
            self._clean_turns_ms.append(turn_ms)
            return
        if not self._clean_turns_ms:
            return
        stall_ms = max(0.0, turn_ms - statistics.median(self._clean_turns_ms))
        for occ in self._rows.values():
            if occ is not None and occ.rounds_seen > 1:
                occ.admit_stall_ms += stall_ms
                occ.stalled_turns += 1

    def _harvest(self, now: float) -> None:
        """Round-boundary accounting: finished rows complete; rows past
        deadline evict with partials; rows at their (possibly degraded)
        budget complete as truncated."""
        self._book_turn(now)
        n_tok_h = self._reads(self._bat.state[1], "n_tok")
        done_h = self._reads(self._bat.state[2], "done")
        for row, occ in self._rows.items():
            if occ is None:
                continue
            n = int(n_tok_h[row])
            produced = n - occ.prompt_len
            if bool(done_h[row]):
                toks, nt = self._bat.row_tokens(row)
                self._store_row(row)
                self.counters.completed += 1
                self.counters.observe_class(occ.req.slo_class, "completed")
                self._finish_latency(occ, now, nt, "serve/complete", row)
                self._results.append(Completed(
                    occ.req.rid, now, tokens=toks, n_tok=nt,
                    beam_demoted=occ.demoted, meta=self._meta(),
                    **self._stamps(occ),
                ))
                self._rows[row] = None
            elif occ.req.deadline is not None and occ.req.deadline <= now:
                toks, nt = self._bat.row_tokens(row)
                self._store_row(row)
                self._bat.retire(row)
                self.counters.evicted_deadline += 1
                self.counters.observe_class(occ.req.slo_class, "shed")
                self._finish_latency(occ, now, n, "serve/evict", row)
                self._results.append(DeadlineExceeded(
                    occ.req.rid, now, tokens=toks[:n], n_tok=n,
                    stage="decode", meta=self._meta(),
                    **self._stamps(occ),
                ))
                self._rows[row] = None
            elif produced >= occ.budget:
                toks, nt = self._bat.row_tokens(row)
                self._store_row(row)
                self._bat.retire(row)
                truncated = occ.budget < occ.requested
                if truncated:
                    self.counters.truncated += 1
                self.counters.completed += 1
                self.counters.observe_class(occ.req.slo_class, "completed")
                self._finish_latency(occ, now, nt, "serve/complete", row)
                self._results.append(Completed(
                    occ.req.rid, now, tokens=toks, n_tok=nt,
                    truncated=truncated, beam_demoted=occ.demoted,
                    meta=self._meta(), **self._stamps(occ),
                ))
                self._rows[row] = None

    def _store_row(self, row: int) -> None:
        """Export a retiring row's reusable prefix pages into the
        kvstore — the retire half of the prefix-cache flow.  Never
        raises: the store is an accelerator, not a dependency."""
        if self.kvstore is None:
            return
        try:
            with self._tracer.span("serve/kvstore_export", row=row):
                if self.kvpool is None:
                    self.kvstore.insert(export_kv_row(self._bat.state, row))
                    return
                # Pool-armed path: split/hash ONCE, feed both tiers —
                # local store for this replica's next hit, pool push so
                # any other replica can import the chain.
                host = self._reads(export_kv_row(self._bat.state, row),
                                   "kv_row", read=KVHandoff.to_host)
                pt = self.kvstore.page_tokens
                pages = host.split_pages(pt)
                if not pages:
                    return
                hashes = page_hashes(
                    np.asarray(host.buf)[0], pt,
                    limit=int(np.asarray(host.n_tok)[0]) - 1,
                )[:len(pages)]
                self.kvstore.put_pages(hashes, pages)
                self.counters.pool_pushed_pages += \
                    self.kvpool.push(hashes, pages)
        except Exception:
            self._log.warning("serve: kvstore export failed",
                              exc_info=True)

    def _finish_latency(self, occ: _Row, now: float, n_tok: int,
                        event: str, row: int) -> None:
        """Terminal accounting for one row: e2e always; TPOT when at
        least two generated tokens bracket an interval."""
        e2e_ms = (now - occ.submitted_at) * 1e3
        self.latency.e2e_ms.record(e2e_ms)
        self.slo_latency.record_e2e(occ.req.slo_class, e2e_ms)
        produced = n_tok - occ.prompt_len
        if occ.first_tok_at is not None and produced > 1:
            self.latency.tpot_ms.record(
                (now - occ.first_tok_at) * 1e3 / (produced - 1)
            )
        if event == "serve/evict":  # deadline blown mid-decode
            self._promote(occ.req)
        outcome = "evict" if event == "serve/evict" else "complete"
        self._flow(occ.req, "f", outcome=outcome)
        # The book, split by critpath's rules on this loop's clock: the
        # segments sum to e2e_ms.
        origin = occ.origin
        first = origin.first_tok_at if origin.first_tok_at is not None \
            else now
        prefill_ms = (first - origin.admitted_at) * 1e3 \
            - origin.pool_fetch_ms
        segments = replica_segments(
            queue_wait_ms=(origin.admitted_at - occ.submitted_at) * 1e3,
            prefill_ms=prefill_ms, first_to_terminal_ms=(now - first) * 1e3,
            pool_fetch_ms=origin.pool_fetch_ms, parked_ms=occ.parked_ms,
            admit_stall_ms=occ.admit_stall_ms)
        get_requests().add({
            "rid": occ.req.rid, "outcome": outcome, "first_s": first,
            "end_s": now, "out": n_tok - int(occ.req.prompt.shape[0]),
            "stalled_turns": occ.stalled_turns, "e2e_ms": e2e_ms,
            "segments": segments})
        self._tracer.instant(event, rid=occ.req.rid, row=row,
                             n_tok=n_tok, rounds=occ.rounds_seen,
                             cls=occ.req.slo_class, e2e_ms=e2e_ms,
                             prefill_ms=prefill_ms,
                             admit_stall_ms=occ.admit_stall_ms)

    def _update_policy(self) -> None:
        before = self.policy.level
        # The ladder sees only the NON-BATCH backlog: a deep batch queue
        # is answered by batch preemption and per-class budgets, never
        # by degrading interactive quality (shed batch before degrading
        # interactive — the multi-tenant ordering contract).
        level = self.policy.update(self.queue.depth_frac_urgent,
                                   self._round_ms)
        if level != before:
            self._log.info(
                "serve: degradation %d -> %d (%s)", before, level,
                self.policy.current.name,
            )
        self.counters.observe_level(level)
        self._bat.n_draft = self.policy.n_draft(self.base_n_draft)

    def _flush(self, force: bool = False) -> None:
        if self._sink is None:
            return
        if force or (self.counters.rounds % self._flush_every == 0):
            data = {
                f"serve/{k}": v for k, v in self.counters.snapshot().items()
            }
            # Request-level latency percentiles ride the same flush as
            # ``trace/*`` scalars (ISSUE 4: TTFT/TPOT/e2e p50/p95/p99).
            data.update({
                f"trace/{k}": v for k, v in self.latency.summary().items()
            })
            self._sink.log_scalars(data, step=self.counters.rounds)
