"""Continuous-batching serving loop over the batched decoder — v2.

The reference framework stops at training (SURVEY §2); this demo shows
the serving patterns the TPU build supports end to end, on ONE seeded
request trace so the two disciplines are directly comparable:

- ``--mode group`` — the v1 discipline: a batcher groups up to
  ``--max-batch`` requests, PADS the batch to a fixed width with dummy
  rows (static shapes: the whole serving process compiles exactly one
  executable), and each group decodes in ONE device dispatch via
  ``speculative_generate_batched``.  A request that arrives while a
  group is decoding waits for that group's SLOWEST row before its
  prefill even starts.
- ``--mode continuous`` — round-granular continuous batching via
  :class:`rocket_tpu.models.generate.ContinuousBatcher`: the SAME round
  body runs one speculative round per dispatch with the carry state
  kept on device, so between rounds the loop admits a fresh request
  into any finished row while the other rows keep decoding.  Nobody
  waits for a group to drain; the demo logs each mid-batch join.
- ``--mode both`` (default) runs both on the same trace and prints the
  per-request p50 comparison.
- ``--mode robust`` — the continuous loop wrapped in
  :class:`rocket_tpu.serve.ServingLoop`: bounded admission queue
  (``--queue-capacity``), per-request deadlines (``--deadline-ms``),
  the graceful-degradation ladder, and the stuck-step watchdog
  (``--watchdog-ms``); ``--stuck-round``/``--burst`` inject live faults
  and print the SERVING -> DEGRADED -> SERVING health transitions.
- ``--mode fleet`` — the robust loop replicated: a
  :class:`rocket_tpu.serve.FleetRouter` least-loaded-routes a scaled
  trace (defaults jump to 2048 requests at 2 ms mean arrival) across
  ``--replicas`` thread-backed serving replicas.  ``--prefill-replicas``
  disaggregates the lanes — long prompts prefill on a dedicated replica
  and their finished KV rows hand off to a decode replica — and
  ``--kill-round K`` kills replica r0 live so the self-healing path
  (drain, salvage, rebuild from factory, re-route) prints as it runs.
  See docs/reliability.md ("Serving fleet").
- ``--mode fleet-proc`` — the fleet across REAL processes: each replica
  is a :class:`rocket_tpu.serve.ProcReplica` supervising a
  ``python -m rocket_tpu.serve.worker`` subprocess (tiny seeded models,
  so outputs stay bit-comparable to an in-process oracle), routed by
  pages through a shared prefix index.  ``--kill-round K`` SIGKILLs
  w0's worker mid-burst and the supervisor salvage + respawn path
  prints as it runs; ``--autoscale`` starts at ONE worker and lets the
  goodput-driven :class:`rocket_tpu.serve.Autoscaler` grow the fleet
  off the exported metrics and drain it after the burst.  See
  docs/reliability.md ("Process fleet & autoscaling").
- ``--mode cache`` — the prefix-cache tier
  (:class:`rocket_tpu.serve.PrefixKVStore`): a seeded multi-turn trace
  where 90% of every prompt is a session header shared across turns
  runs cold and then cached, printing the store's hit rate and
  occupancy and the TTFT p50/p95 cold-vs-cached comparison; outputs are
  verified bit-equal between the passes.  ``--kv-bytes`` sets the LRU
  byte budget.  See docs/performance.md ("Prefix cache").
- ``--mode cache-fleet`` — the prefix cache made FLEET-WIDE
  (:class:`rocket_tpu.serve.KVPagePool`): two worker PROCESSES share a
  supervisor-hosted page pool; a seeded multi-turn session runs turn 1
  on its sticky worker, the worker is SIGKILLed mid-conversation, and
  turn 2 re-routes to the survivor, which imports the session's pages
  over the pool socket instead of re-prefilling.  Prints turn-2 TTFT
  local-hit vs pool-transferred vs cold, the pool's byte counters, the
  transfer's ``serve/kvstore/wire`` goodput charge, and verifies the
  migrated turn bit-equal to a cold in-process oracle.  ``--kv-bytes``
  sets the pool byte budget.  See docs/performance.md
  ("Fleet KV tier").
- ``--mode train-serve`` — train-while-serve: a stand-in trainer
  publishes verified weight versions (two-phase commit, checksummed,
  mesh-stamped — :class:`rocket_tpu.persist.publish.WeightPublisher`)
  while a real worker process serves, and a
  :class:`rocket_tpu.serve.WeightFeed` hot-swaps each publication into
  the live loop between decode rounds via donation (no second HBM
  copy, zero recompiles).  One publication is torn live after its
  commit marker lands; the deep verify gate rejects it without
  touching serving, and a ``rollback()`` steps the fleet back one
  published version.  Outputs verify bit-equal to an in-process
  oracle on the same publication.  See docs/reliability.md
  ("Live weight updates").
- ``--mode tenants`` — multi-tenant serving: a seeded mixed-tenant
  trace from the ``serve/loadgen.py`` harness (interactive chat
  sessions, standard API traffic, a bulk batch tenant; diurnal ramp +
  bursts, heavy-tail prompt lengths) replays twice against the
  weighted-fair :class:`rocket_tpu.serve.ServingLoop` — clean, then
  with a ``BatchFloodInjector`` pushing batch work every round.
  Prints the per-class submitted/completed/shed/TTFT-p95/attainment
  table for both passes, the preempt/resume counters, and the
  interactive p95 ratio the acceptance bench holds under 1.25x.  See
  docs/reliability.md ("Multi-tenant serving").
- ``--trace`` (implies ``--mode robust``) — arm the structured tracer
  (:mod:`rocket_tpu.observe.trace`): every round/admit/request gets a
  span, the demo prints the p50/p95 queue-wait/TTFT/TPOT/e2e table at
  the end, and a flight-recorder dump (Chrome-trace JSON, open in
  https://ui.perfetto.dev) is written with its path printed.  Combine
  with ``--stuck-round`` to see the watchdog-trip crash dump attached
  to the ``Failed`` results.
- ``--metrics-port P`` — arm the goodput/retrace ledgers
  (:mod:`rocket_tpu.observe.ledger`) and serve Prometheus text on
  ``http://127.0.0.1:P/metrics`` (``0`` = OS-assigned; the live serve /
  fleet counters register as export sources for the duration of the
  run).  The goodput bucket table prints at exit.  Works with every
  mode.

Both modes use the int8 self-draft speculative decoder (per-row KV
frontiers, no per-token host sync) and report per-request latency
(arrival -> tokens), aggregate throughput, and acceptance.

    python examples/serve_demo.py [--requests 24] [--max-batch 8]
"""

import argparse
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from rocket_tpu.models.generate import (  # noqa: E402
    ContinuousBatcher,
    speculative_generate_batched,
)
from rocket_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    TransformerLM,
)
from rocket_tpu.ops.quant import quantize_params  # noqa: E402

VOCAB, PROMPT, NEW, NDRAFT = 256, 16, 32, 4
# --mode cache trace shape: longer prompts make the shared-prefix
# fraction meaningful (36 of 40 tokens = 90%, an exact page multiple)
CACHE_PROMPT, CACHE_PAGE, CACHE_TURNS = 240, 24, 4


def _cfg(max_seq=PROMPT + NEW + NDRAFT, **kw):
    return TransformerConfig(
        vocab_size=VOCAB, hidden=128, n_layers=2, n_heads=4,
        # batched speculative decode needs n_draft slack past the
        # final token (the verify chunk can write that far)
        max_seq=max_seq,
        norm="layernorm", mlp="gelu", positions="learned",
        tie_embeddings=True, use_bias=True, attention="dot", **kw,
    )


def _build(max_seq=PROMPT + NEW + NDRAFT):
    import flax.linen as nn

    model = TransformerLM(_cfg(max_seq=max_seq))
    draft = TransformerLM(_cfg(max_seq=max_seq, weights_int8=True))
    init_prompt = jnp.zeros((1, PROMPT), jnp.int32)
    params = nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), {"tokens": init_prompt})["params"]
    )
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    draft_params = jax.jit(quantize_params)(params)
    return model, draft, params, draft_params


def run_group(args, model, draft, params, draft_params, arrivals, prompts):
    """v1 discipline: fixed-width groups, one dispatch per group."""
    R, B = args.requests, args.max_batch
    # one warmup dispatch compiles the single fixed-width executable
    warm = jnp.zeros((B, PROMPT), jnp.int32)
    speculative_generate_batched(
        model, params, draft, draft_params, warm, NEW, n_draft=NDRAFT,
    ).block_until_ready()

    t0 = time.perf_counter()
    done_at = np.zeros(R)
    served = batches = accepted = drafted = 0
    while served < R:
        now = time.perf_counter() - t0
        ready = [i for i in range(R)
                 if arrivals[i] <= now and done_at[i] == 0.0]
        if not ready:
            # sleep until the next arrival instead of spinning
            pending = arrivals[arrivals > now]
            if pending.size:
                time.sleep(float(pending.min() - now) + 1e-4)
            continue
        group = ready[:B]
        # pad to the fixed width with repeats of the last real prompt:
        # rows are independent (per-row KV frontiers), so dummy rows
        # cost compute but never touch correctness or other rows
        rows = group + [group[-1]] * (B - len(group))
        batch = jnp.asarray(prompts[rows], jnp.int32)
        toks, stats = speculative_generate_batched(
            model, params, draft, draft_params, batch, NEW,
            n_draft=NDRAFT, return_stats=True,
        )
        jax.block_until_ready(toks)
        t_done = time.perf_counter() - t0
        for i in group:
            done_at[i] = t_done
        served += len(group)
        batches += 1
        accepted += int(stats["accepted"][: len(group)].sum())
        drafted += int(stats["drafted"][: len(group)].sum())
    total = time.perf_counter() - t0
    return dict(lat=(done_at - arrivals) * 1e3, total=total,
                dispatches=batches, unit="batches",
                accepted=accepted, drafted=drafted)


def run_continuous(args, model, draft, params, draft_params,
                   arrivals, prompts):
    """Round-granular: one speculative round per dispatch; a finished
    row is re-admitted with the next pending request between rounds,
    while the other rows keep decoding."""
    R, B = args.requests, args.max_batch
    bat = ContinuousBatcher(model, draft, params, draft_params,
                            total_len=PROMPT + NEW, n_draft=NDRAFT)
    # warmup compiles prefill + round + admit before the clock starts
    warm = jnp.zeros((B, PROMPT), jnp.int32)
    bat.start(warm)
    bat.step()
    bat.admit(0, warm[:1], preempt=True)  # warmup row may still be live
    bat.step()

    done_at = np.zeros(R)
    admitted = np.zeros(R, bool)
    row_req = [None] * B  # which request occupies each row
    served = rounds = accepted = drafted = joins = 0
    t0 = time.perf_counter()

    def now():
        return time.perf_counter() - t0

    # the batch starts when the first request lands
    time.sleep(max(0.0, float(arrivals[0])) + 1e-4)
    group = [i for i in range(R) if arrivals[i] <= now()][:B]
    rows = group + [group[-1]] * (B - len(group))
    bat.start(jnp.asarray(prompts[rows], jnp.int32))
    for r, req in enumerate(group):
        row_req[r] = req
        admitted[req] = True
    for r in range(len(group), B):
        bat.retire(r)  # pad rows idle (round body skips done rows)

    while served < R:
        if any(req is not None for req in row_req):
            bat.step()  # ONE speculative round for every live row
            rounds += 1
        else:
            nxt = arrivals[~admitted]
            time.sleep(max(0.0, float(nxt.min()) - now()) + 1e-4)
        t_now = now()
        stats = bat.stats()
        for row in bat.finished_rows():
            req = row_req[row]
            if req is not None:
                # per-row counters reset on admit, so read them at
                # completion, before the slot is recycled
                done_at[req] = t_now
                accepted += int(stats["accepted"][row])
                drafted += int(stats["drafted"][row])
                row_req[row] = None
                served += 1
            pend = [i for i in range(R)
                    if not admitted[i] and arrivals[i] <= t_now]
            if pend:
                nxt_req = pend[0]
                live = sum(1 for q in row_req if q is not None)
                bat.admit(row, jnp.asarray(prompts[nxt_req], jnp.int32))
                row_req[row] = nxt_req
                admitted[nxt_req] = True
                if live:
                    joins += 1
                    print(f"  [continuous] request {nxt_req} joined row "
                          f"{row} at round {rounds} — {live} rows still "
                          f"mid-decode")
    total = now()
    return dict(lat=(done_at - arrivals) * 1e3, total=total,
                dispatches=rounds, unit="rounds",
                accepted=accepted, drafted=drafted, joins=joins)


def run_robust(args, model, draft, params, draft_params, arrivals, prompts):
    """The continuous loop wrapped in :class:`rocket_tpu.serve.ServingLoop`:
    bounded admission queue, per-request deadlines, the degradation
    ladder, and the stuck-step watchdog.  ``--stuck-round K`` wedges the
    K-th device round via ``StuckStepInjector`` so the watchdog's
    trip -> fail-in-flight -> rebuild path runs live; ``--burst`` replaces
    the Poisson trace with deterministic ``bursty_arrivals`` storms that
    overrun the queue and engage the ladder."""
    from rocket_tpu.serve import (
        Completed, DeadlineExceeded, Failed, Overloaded, Request,
        ServingLoop,
    )
    from rocket_tpu.testing.chaos import StuckStepInjector, bursty_arrivals

    tracer = recorder = None
    if args.trace:
        import tempfile

        from rocket_tpu.observe.recorder import FlightRecorder
        from rocket_tpu.observe.trace import Tracer

        tracer = Tracer(capacity=2048, enabled=True)
        recorder = FlightRecorder(tracer, out_dir=os.path.join(
            tempfile.mkdtemp(prefix="serve-demo-"), "flightrec"))

    R, B = args.requests, args.max_batch
    wrapped = {"n": 0}

    def factory():
        bat = ContinuousBatcher(model, draft, params, draft_params,
                                total_len=PROMPT + NEW, n_draft=NDRAFT)
        wrapped["n"] += 1
        if args.stuck_round >= 0 and wrapped["n"] == 1:
            # wedge only the first instance: the rebuilt batcher is clean
            return StuckStepInjector(
                bat, hang_on=(args.stuck_round,),
                hang_s=args.watchdog_ms / 1e3 * 20,
            )
        return bat

    if args.burst > 0:
        arrivals = np.asarray(bursty_arrivals(
            R, args.burst, gap_s=args.arrival_ms / 1e3 * args.burst,
        ))
    t0 = time.perf_counter()

    def now():
        return time.perf_counter() - t0

    # the loop's clock shares the demo's time origin, so the printed
    # deadlines and the loop's eviction decisions line up exactly
    loop = ServingLoop(
        factory, max_batch=B, queue_capacity=args.queue_capacity,
        watchdog_timeout=(args.watchdog_ms / 1e3
                          if args.stuck_round >= 0 else None),
        clock=now, tracer=tracer, recorder=recorder,
    )
    if args.metrics_port >= 0:
        # /metrics exports the live loop counters + latency percentiles
        # alongside the goodput/ledger gauges for the duration of the run
        from rocket_tpu.observe.export import register_source

        register_source("serve", loop.counters.snapshot)
        register_source("serve_latency", loop.latency.summary)
    health = loop.health
    print(f"  [robust] health: {health.value}")
    submitted = 0
    results = []
    while len(results) < R:
        while submitted < R and arrivals[submitted] <= now():
            deadline = (None if args.deadline_ms <= 0
                        else now() + args.deadline_ms / 1e3)
            loop.submit(Request(rid=submitted,
                                prompt=prompts[submitted].astype(np.int32),
                                deadline=deadline))
            submitted += 1
        if not loop.run_round() and submitted < R:
            time.sleep(max(0.0, float(arrivals[submitted]) - now()) + 1e-4)
        if loop.health is not health:
            health = loop.health
            print(f"  [robust] health: {health.value} "
                  f"(queue {len(loop.queue)}/{loop.queue.capacity}, "
                  f"ladder '{loop.policy.current.name}', "
                  f"trips {loop.watchdog.trips})")
        results.extend(loop.drain_results())
    total = now()
    loop.close()
    if args.metrics_port >= 0:
        from rocket_tpu.observe.export import unregister_source

        unregister_source("serve")
        unregister_source("serve_latency")

    kinds = {Completed: "completed", Overloaded: "overloaded",
             DeadlineExceeded: "deadline", Failed: "failed"}
    tally = {v: 0 for v in kinds.values()}
    for r in results:
        tally[kinds[type(r)]] += 1
    snap = loop.counters.snapshot()
    print(f"  [robust] results: {tally}")
    print(f"  [robust] watchdog trips {int(snap['watchdog_trips'])}, "
          f"degrade peak level {int(snap['degrade_peak'])}, "
          f"rounds {int(snap['rounds'])}")
    if args.trace:
        summary = loop.latency.summary()
        print("  [trace] request latency percentiles (ms):")
        print(f"  [trace]   {'metric':<14} {'p50':>8} {'p95':>8}")
        for name in ("queue_wait_ms", "ttft_ms", "tpot_ms", "e2e_ms"):
            p50 = summary.get(f"{name}/p50")
            if p50 is not None:
                print(f"  [trace]   {name:<14} {p50:8.1f} "
                      f"{summary[f'{name}/p95']:8.1f}")
        crash = [r.dump_path for r in results
                 if isinstance(r, Failed) and r.dump_path]
        if crash:
            print(f"  [trace] crash dump (attached to Failed results) -> "
                  f"{crash[0]}")
        dump = recorder.dump("demo-exit")
        print(f"  [trace] flight-recorder dump -> {dump}")
        print("  [trace] open trace.json in https://ui.perfetto.dev "
              "(merge per-host dumps: python -m rocket_tpu.observe.trace "
              "<dir>)")
    done = [r for r in results if isinstance(r, Completed)]
    lat = np.asarray([r.finished_at - arrivals[r.rid] for r in done])
    return dict(lat=lat * 1e3 if lat.size else np.zeros(1), total=total,
                dispatches=int(snap["rounds"]), unit="rounds",
                accepted=0, drafted=0, tally=tally)


def run_fleet(args, model, draft, params, draft_params, arrivals, prompts):
    """Multi-replica serving: a :class:`rocket_tpu.serve.FleetRouter`
    load-balances the trace across ``--replicas`` thread-backed
    :class:`rocket_tpu.serve.Replica`\\ s; ``--prefill-replicas`` adds a
    disaggregated prefill lane (finished KV rows hand off to a decode
    replica); ``--kill-round K`` wedges replica r0's K-th round via
    ``ReplicaKillInjector`` so the drain -> salvage -> rebuild self-healing
    path runs live while the rest of the fleet keeps serving."""
    from rocket_tpu.serve import (
        Completed, DeadlineExceeded, Failed, FleetRouter, Overloaded,
        PrefillReplica, Replica, Request, ServingLoop,
    )
    from rocket_tpu.testing.chaos import ReplicaKillInjector

    R, B = args.requests, args.max_batch
    t0 = time.perf_counter()

    def now():
        return time.perf_counter() - t0

    def bat_factory():
        return ContinuousBatcher(model, draft, params, draft_params,
                                 total_len=PROMPT + NEW, n_draft=NDRAFT)

    def loop_factory():
        return ServingLoop(bat_factory, max_batch=B,
                           queue_capacity=args.queue_capacity, clock=now)

    built = {"r0": 0}

    def loop_factory_r0():
        # wedge only the first instance: the healed rebuild is clean
        loop = loop_factory()
        built["r0"] += 1
        if args.kill_round >= 0 and built["r0"] == 1:
            return ReplicaKillInjector(loop, kill_on=(args.kill_round,))
        return loop

    replicas = [Replica(loop_factory_r0 if i == 0 else loop_factory,
                        f"r{i}")
                for i in range(args.replicas)]
    prefill = [PrefillReplica(bat_factory, f"p{i}", clock=now)
               for i in range(args.prefill_replicas)]
    router = FleetRouter(replicas, prefill_replicas=prefill, clock=now)
    router.start()
    if args.metrics_port >= 0:
        from rocket_tpu.observe.export import register_source

        register_source("fleet", router.snapshot)
        register_source("fleet_latency", lambda: router.latency().summary())
    lanes = (f"{len(replicas)} decode + {len(prefill)} prefill replicas"
             if prefill else f"{len(replicas)} replicas (merged lane)")
    print(f"  [fleet] serving {R} requests across {lanes}")

    health = {rep.replica_id: rep.health for rep in replicas}
    heals = 0
    submitted = 0
    results = []
    while submitted < R:
        while submitted < R and arrivals[submitted] <= now():
            deadline = (None if args.deadline_ms <= 0
                        else now() + args.deadline_ms / 1e3)
            router.submit(Request(rid=submitted,
                                  prompt=prompts[submitted].astype(np.int32),
                                  deadline=deadline))
            submitted += 1
        router.pump()  # supervision beat: probe, heal, collect
        for rep in replicas:
            h = rep.health
            if h is not health[rep.replica_id]:
                print(f"  [fleet] {rep.replica_id}: "
                      f"{health[rep.replica_id].value} -> {h.value}")
                health[rep.replica_id] = h
        if router.counters.heals > heals:
            heals = router.counters.heals
            print(f"  [fleet] healed a replica: {heals} heal(s), "
                  f"{router.counters.requeued} request(s) salvaged and "
                  f"re-routed")
        results.extend(router.drain_results())
        if submitted < R:
            time.sleep(min(2e-3,
                           max(0.0, float(arrivals[submitted]) - now())))
    results.extend(router.run_until_idle(max_rounds=1_000_000))
    total = now()

    kinds = {Completed: "completed", Overloaded: "overloaded",
             DeadlineExceeded: "deadline", Failed: "failed"}
    tally = {v: 0 for v in kinds.values()}
    served_by = {}
    for r in results:
        tally[kinds[type(r)]] += 1
        if isinstance(r, Completed):
            rep = (r.meta or {}).get("replica")
            served_by[rep] = served_by.get(rep, 0) + 1
    snap = router.snapshot()
    print(f"  [fleet] results: {tally} "
          f"({len(results)}/{R} typed — exactly once)")
    print(f"  [fleet] served by: "
          + "  ".join(f"{k}={v}" for k, v in sorted(served_by.items())))
    print(f"  [fleet] routed {int(snap['routed'])}, heals "
          f"{int(snap['heals'])}, requeued {int(snap['requeued'])}, shed "
          f"saturated {int(snap['shed_saturated'])}")
    if prefill:
        print(f"  [fleet] prefill lane: {int(snap['handoffs'])} KV "
              f"handoffs, {int(snap['handoff_bytes'])} bytes transferred")
    summary = router.latency().summary()
    for name in ("ttft_ms", "tpot_ms", "e2e_ms"):
        p50 = summary.get(f"{name}/p50")
        if p50 is not None:
            print(f"  [fleet] {name:<8} p50 {p50:8.1f}  "
                  f"p95 {summary[f'{name}/p95']:8.1f}")
    router.close()
    if args.metrics_port >= 0:
        from rocket_tpu.observe.export import unregister_source

        unregister_source("fleet")
        unregister_source("fleet_latency")

    done = [r for r in results if isinstance(r, Completed)]
    lat = np.asarray([r.finished_at - arrivals[r.rid] for r in done])
    return dict(lat=lat * 1e3 if lat.size else np.zeros(1), total=total,
                dispatches=int(snap["routed"]), unit="routes",
                accepted=0, drafted=0, tally=tally)


def run_fleet_proc(args, model, draft, params, draft_params,
                   arrivals, prompts):
    """Process-backed fleet: every replica is a real ``python -m
    rocket_tpu.serve.worker`` subprocess (the tiny testing model — the
    WorkerSpec names a module-level builder, and seeded init makes all
    workers bit-identical).  A seeded burst storms the fleet, replica
    w0's worker takes a REAL ``kill -9`` mid-burst (``--kill-round``
    picks the beat; default a third into the burst, ``-2`` disables),
    and the supervisor's shadow salvages its in-flight requests onto
    the survivors while the corpse respawns.  ``--autoscale`` starts at
    ONE worker and lets the goodput-driven :class:`rocket_tpu.serve.
    Autoscaler` grow the fleet off the /metrics surface (TTFT p95 SLO),
    then drain it once the burst passes.  See docs/reliability.md
    ("Process fleet & autoscaling")."""
    from rocket_tpu.serve import (
        Autoscaler, Completed, DeadlineExceeded, Failed, FleetRouter,
        Overloaded, ProcReplica, Request, SharedPrefixIndex, SLOPolicy,
        WorkerSpec, register_fleet_source,
    )
    from rocket_tpu.observe.export import unregister_source
    from rocket_tpu.testing import workers as tw
    from rocket_tpu.testing.chaos import ProcessKillInjector, bursty_arrivals

    R = args.requests
    rng = np.random.default_rng(23)
    prompts = rng.integers(1, tw.VOCAB, size=(R, tw.P)).astype(np.int32)
    burst = args.burst if args.burst > 0 else 8
    arrivals = np.asarray(bursty_arrivals(R, burst, gap_s=0.25,
                                          spread_s=0.02))
    # every spawn — including the post-kill respawn — restores weights
    # through the elastic-restore gate (newest valid snapshot,
    # check_reshard against whatever devices the worker got)
    snap_root = tempfile.mkdtemp(prefix="rocket_tpu_fleet_proc_")
    snap_path = tw.save_tiny_snapshot(snap_root)
    print(f"  [proc] workers elastic-restore from {snap_path}")
    autoscale = args.autoscale or args.standby > 0
    spec_kwargs = {"queue_capacity": max(args.queue_capacity, 16),
                   "kvstore_page_tokens": 4,
                   "restore_dir": snap_root}
    if args.standby > 0:
        # pre-warmed spawns: every worker (standbys included) runs its
        # WarmupPlan against the persistent compile cache before READY
        spec_kwargs["warmup"] = "auto"
    spec = WorkerSpec(
        builder="rocket_tpu.testing.workers:build_tiny_loop",
        kwargs=spec_kwargs,
    )
    index = SharedPrefixIndex(page_tokens=4)
    n0 = 1 if autoscale else min(max(args.replicas, 2), 4)

    def spawn(rid):
        t = time.perf_counter()
        rep = ProcReplica(spec, rid, prefix_index=index)
        print(f"  [proc] spawned worker {rid} (pid {rep.pid}) in "
              f"{time.perf_counter() - t:.1f}s")
        return rep

    reps = [spawn(f"w{i}") for i in range(n0)]
    router = FleetRouter(reps, prefix_index=index)
    register_fleet_source(router)
    auto = None
    if autoscale:
        auto = Autoscaler(router, spawn, SLOPolicy(
            ttft_p95_ms=5.0, max_shed_rate=0.02, breach_rounds=1,
            min_replicas=1, max_replicas=4,
            scale_up_cooldown_s=0.0, scale_down_cooldown_s=0.0,
            drain_below_load=0.5, standby=max(0, args.standby)))
        print("  [proc] autoscaler armed: TTFT p95 SLO 5 ms, "
              "1..4 worker processes")
        if args.standby > 0:
            ready = auto.wait_standby(timeout_s=120.0)
            print(f"  [proc] standby pool: {ready} pre-warmed worker(s) "
                  f"waiting off-rotation (scale-up = rename, not spawn)")
    kill_tick = args.kill_round if args.kill_round >= 0 else max(2, R // 3)
    injector = None
    if args.kill_round != -2:
        injector = ProcessKillInjector(reps[0], kill_on=(kill_tick,))
        print(f"  [proc] chaos armed: SIGKILL {reps[0].replica_id}'s "
              f"worker at burst beat {kill_tick}")
    print(f"  [proc] serving {R} requests (bursts of {burst}) across "
          f"{len(router.replicas)} worker process(es)")

    t0 = time.perf_counter()
    # each worker process runs on its OWN clock — supervisor-side wall
    # latency (submit -> result drained here) is the comparable number
    done_wall = {}
    heals = 0
    submitted = 0
    results = []

    def harvest(batch):
        t_now = time.perf_counter() - t0
        for r in batch:
            done_wall[r.rid] = t_now
        results.extend(batch)

    while submitted < R:
        while submitted < R and arrivals[submitted] <= time.perf_counter() - t0:
            router.submit(Request(
                rid=submitted, prompt=prompts[submitted]))
            submitted += 1
            # the injector counts burst beats (submissions), so the
            # SIGKILL lands with requests genuinely in flight
            if injector is not None and injector.tick():
                print(f"  [proc] kill -9 delivered to "
                      f"{reps[0].replica_id}'s worker mid-burst")
        router.pump()       # supervision: discover the corpse, salvage,
        if auto is not None:
            auto.step()     # respawn; autoscaler reads the live metrics
        if router.counters.heals > heals:
            heals = router.counters.heals
            print(f"  [proc] healed: {heals} heal(s), "
                  f"{router.counters.requeued} request(s) salvaged from "
                  f"the supervisor shadow and re-routed")
        harvest(router.drain_results())
    harvest(router.run_until_idle(max_rounds=1_000_000))
    if router.counters.heals > heals:
        heals = router.counters.heals
        print(f"  [proc] healed: {heals} heal(s), "
              f"{router.counters.requeued} request(s) salvaged from "
              f"the supervisor shadow and re-routed")
    total = time.perf_counter() - t0

    if auto is not None:
        # the burst has passed: relax the latency SLO (cumulative
        # percentiles never decay) and let the cold-fleet trigger drain
        auto.policy.ttft_p95_ms = float("inf")
        for _ in range(30):
            auto.step()
            router.pump()
            if auto.counters.scale_downs > 0 and not router._retiring:
                break
        for ev in auto.events:
            extra = ""
            if ev.get("standby"):
                extra = (f" (standby promotion, worker compiled "
                         f"{ev.get('compile_ms', 0.0):.0f} ms before "
                         f"joining rotation)")
            print(f"  [proc] autoscale event: {ev['action']} "
                  f"{ev['replica']}{extra}")
        print(f"  [proc] autoscaler: {auto.counters.scale_ups} scale-up(s),"
              f" {auto.counters.scale_downs} scale-down(s), "
              f"{auto.counters.standby_promotions} standby promotion(s), "
              f"{len(router.replicas)} worker(s) remain")

    kinds = {Completed: "completed", Overloaded: "overloaded",
             DeadlineExceeded: "deadline", Failed: "failed"}
    tally = {v: 0 for v in kinds.values()}
    served_by = {}
    for r in results:
        tally[kinds[type(r)]] += 1
        if isinstance(r, Completed):
            rep_id = (r.meta or {}).get("replica")
            served_by[rep_id] = served_by.get(rep_id, 0) + 1
    snap = router.snapshot()
    print(f"  [proc] results: {tally} "
          f"({len(results)}/{R} typed — exactly once)")
    print("  [proc] served by: "
          + "  ".join(f"{k}={v}" for k, v in sorted(served_by.items(),
                                                    key=str)))
    print(f"  [proc] routed {int(snap['routed'])}, heals "
          f"{int(snap['heals'])}, requeued {int(snap['requeued'])}, "
          f"pages-routed {int(snap['pages_routed'])}, shed "
          f"{int(snap['shed_saturated'])}")
    summary = router.latency().summary()
    for name in ("ttft_ms", "tpot_ms", "e2e_ms"):
        p50 = summary.get(f"{name}/p50")
        if p50 is not None:
            print(f"  [proc] {name:<8} p50 {p50:8.1f}  "
                  f"p95 {summary[f'{name}/p95']:8.1f} "
                  f"(merged across worker processes)")
    if auto is not None:
        auto.close()    # retire the standby pool's off-rotation workers
    router.close()
    unregister_source("serve_fleet")
    if auto is not None:
        unregister_source("autoscaler")
    shutil.rmtree(snap_root, ignore_errors=True)

    done = [r for r in results if isinstance(r, Completed)]
    lat = np.asarray([done_wall[r.rid] - arrivals[r.rid] for r in done])
    return dict(lat=lat * 1e3 if lat.size else np.zeros(1), total=total,
                dispatches=int(snap["routed"]), unit="routes",
                accepted=0, drafted=0, tally=tally,
                new_tokens=tw.TOTAL - tw.P)


def run_cache(args, model, draft, params, draft_params, arrivals, prompts):
    """Prefix-cache tier (:mod:`rocket_tpu.serve.kvstore`): a seeded
    multi-turn trace where ~90% of every prompt is a session header
    shared across the session's turns.  The SAME trace runs twice —
    cold (no store) and cached (a :class:`PrefixKVStore` armed on the
    loop) — and the TTFT p50/p95 comparison plus the store's hit-rate /
    occupancy counters print at the end.  Outputs are bit-equal between
    the two passes (the cache is a latency tier, never a correctness
    tier)."""
    from rocket_tpu.serve import (
        Completed, PrefixKVStore, Request, ServingLoop,
    )

    R, B = args.requests, args.max_batch
    sessions = max(1, R // CACHE_TURNS)
    shared = int(CACHE_PROMPT * 0.9)          # 216 — 9 exact pages of 24
    rng = np.random.default_rng(17)
    headers = rng.integers(0, VOCAB, size=(sessions, shared))
    tails = rng.integers(
        0, VOCAB, size=(CACHE_TURNS, sessions, CACHE_PROMPT - shared))

    def bat_factory():
        return ContinuousBatcher(model, draft, params, draft_params,
                                 total_len=CACHE_PROMPT + NEW,
                                 n_draft=NDRAFT)

    def turn_prompt(s, t):
        return np.concatenate([headers[s], tails[t][s]]).astype(np.int32)

    def serve_trace(store):
        t0 = time.perf_counter()
        loop = ServingLoop(bat_factory, max_batch=B,
                           queue_capacity=max(args.queue_capacity, R),
                           clock=lambda: time.perf_counter() - t0,
                           kvstore=store)
        outs = []
        submit_at = {}
        rid = 0
        for t in range(CACHE_TURNS):
            # a turn is submitted only after the previous turn's rows
            # retired (and exported their pages) — the multi-turn shape
            for s in range(sessions):
                if rid >= R:
                    break
                submit_at[rid] = time.perf_counter() - t0
                loop.submit(Request(rid=rid, prompt=turn_prompt(s, t),
                                    session=s))
                rid += 1
            outs.extend(loop.run_until_idle(max_rounds=1_000_000))
        total = time.perf_counter() - t0
        summary = loop.latency.summary()
        snap = loop.counters.snapshot()
        loop.close()
        lat = np.asarray([r.finished_at - submit_at[r.rid] for r in outs
                          if isinstance(r, Completed)])
        return outs, summary, snap, total, lat

    # warm every executable BOTH passes dispatch (full prefill, suffix
    # prefill, import scatter, round) so the comparison is dispatch time
    warm = PrefixKVStore(page_tokens=CACHE_PAGE, capacity_bytes=1 << 28)
    wloop = ServingLoop(bat_factory, max_batch=B, queue_capacity=4,
                        kvstore=warm)
    for t in range(2):
        wloop.submit(Request(rid=f"w{t}", prompt=turn_prompt(0, t),
                             session="warm"))
        wloop.run_until_idle(max_rounds=1_000_000)
    wloop.close()

    store = PrefixKVStore(page_tokens=CACHE_PAGE,
                          capacity_bytes=args.kv_bytes)
    if args.metrics_port >= 0:
        from rocket_tpu.serve import register_kvstore_source

        register_kvstore_source([store])
    cold_out, cold_sum, _, _, _ = serve_trace(None)
    out, summary, snap, total, lat = serve_trace(store)

    by_rid = {r.rid: r for r in cold_out}
    mismatch = sum(
        1 for r in out
        if isinstance(r, Completed)
        and not np.array_equal(r.tokens, by_rid[r.rid].tokens))
    kv = store.snapshot()
    frac = shared / CACHE_PROMPT
    print(f"  [cache] trace: {sessions} sessions x {CACHE_TURNS} turns, "
          f"{shared}/{CACHE_PROMPT} prompt tokens shared "
          f"({frac:.0%} prefix)")
    print(f"  [cache] hit rate {kv['hit_rate']:.0%} "
          f"({int(kv['hits'])}/{int(kv['lookups'])} lookups, "
          f"{int(kv['hit_tokens'])} prompt tokens served from pages)")
    print(f"  [cache] store: {int(kv['pages'])} pages, "
          f"{int(kv['occupancy_bytes'])}/{int(kv['capacity_bytes'])} "
          f"bytes, {int(kv['evictions'])} evictions")
    print(f"  [cache] {'':<8} {'ttft p50':>10} {'ttft p95':>10}")
    for tag, s in (("cold", cold_sum), ("cached", summary)):
        print(f"  [cache] {tag:<8} {s['ttft_ms/p50']:>9.1f}ms "
              f"{s['ttft_ms/p95']:>9.1f}ms")
    drop = 1.0 - summary["ttft_ms/p50"] / max(cold_sum["ttft_ms/p50"], 1e-9)
    print(f"  [cache] cached TTFT p50 {drop:+.0%} vs cold "
          f"(shared-prefill fraction {frac:.0%})")
    print(f"  [cache] outputs bit-equal to cold pass: "
          f"{'yes' if mismatch == 0 else f'NO ({mismatch} mismatches)'}")
    if args.metrics_port >= 0:
        from rocket_tpu.observe.export import unregister_source

        unregister_source("serve_kvstore")

    return dict(lat=lat * 1e3 if lat.size else np.zeros(1), total=total,
                dispatches=int(snap["rounds"]), unit="rounds",
                accepted=0, drafted=0)


def run_cache_fleet(args, model, draft, params, draft_params, arrivals,
                    prompts):
    """Fleet KV page tier (:mod:`rocket_tpu.serve.kvpool`): the prefix
    cache made FLEET-WIDE across real worker processes.  Two workers
    share one supervisor-hosted page pool; a seeded multi-turn session
    runs turn 1 on its sticky worker, the worker is SIGKILLed
    mid-conversation, and turn 2 lands on the survivor — which has
    never seen the session and imports the pages over the pool socket
    instead of re-prefilling.  The demo prints the turn-2 TTFT three
    ways (local hit / pool-transferred / cold), the pool's byte
    counters, and verifies the migrated turn bit-equal to an in-process
    cold oracle.  See docs/performance.md ("Fleet KV tier")."""
    from rocket_tpu.serve import (
        Completed, FleetRouter, KVPagePool, ProcReplica, Request,
        SharedPrefixIndex, WorkerSpec, register_kvpool_source,
    )
    from rocket_tpu.testing import workers as tw

    PAGE = 3            # tiny-worker page size: 5 full pages per 16-token turn
    pool = KVPagePool(page_tokens=PAGE, capacity_bytes=args.kv_bytes)
    index = SharedPrefixIndex(page_tokens=PAGE)
    spec = WorkerSpec(
        builder="rocket_tpu.testing.workers:build_tiny_loop",
        kwargs={"kvstore_page_tokens": PAGE},
        kvpool=pool.address,
    )
    if args.metrics_port >= 0:
        register_kvpool_source(pool)
    print(f"  [kvfleet] page pool listening on {pool.address} "
          f"(page_tokens={PAGE}, budget {args.kv_bytes} bytes)")

    def spawn(rid):
        t = time.perf_counter()
        rep = ProcReplica(spec, rid, prefix_index=index)
        print(f"  [kvfleet] spawned worker {rid} (pid {rep.pid}) in "
              f"{time.perf_counter() - t:.1f}s")
        return rep

    reps = [spawn(f"cf{i}") for i in range(2)]
    router = FleetRouter(reps, prefix_index=index)

    rng = np.random.default_rng(11)

    def fresh(n=tw.P):
        return rng.integers(1, tw.VOCAB, size=n).astype(np.int32)

    def drive(rep, req, max_rounds=400):
        assert rep.submit(req)
        out = []
        for _ in range(max_rounds):
            rep.pump()
            out.extend(rep.drain_results())
            if out:
                return out[0]
        raise RuntimeError("worker never returned the warmup turn")

    def last_ttft(rep):
        # the worker ships its cumulative latency histograms each STEP;
        # the newest ttft sample is the turn that just finished
        return rep.latency.ttft_ms._samples[-1]

    def serve_turn(rid, prompt, session):
        t0 = time.perf_counter()
        assert router.submit(Request(rid=rid, prompt=prompt,
                                     session=session)) is None
        results = router.run_until_idle(max_rounds=1_000_000)
        wall = (time.perf_counter() - t0) * 1e3
        (res,) = [r for r in results if r.rid == rid]
        assert isinstance(res, Completed), res
        (rep,) = [r for r in router.replicas
                  if r.replica_id == (res.meta or {}).get("replica")]
        return res, rep, last_ttft(rep), wall

    # warm every executable the measured turns dispatch (8- and
    # 16-token cold prefill, page import scatter, suffix prefill,
    # round) so the three TTFTs compare dispatch time, not compile time
    def warm(rep):
        tag = f"{rep.replica_id}-{rep.spawns}"
        w1 = drive(rep, Request(rid=f"warm1-{tag}", prompt=fresh(),
                                session="warm"))
        drive(rep, Request(
            rid=f"warm2-{tag}",
            prompt=np.asarray(w1.tokens)[:16].astype(np.int32),
            session="warm"))
        drive(rep, Request(rid=f"warm3-{tag}", prompt=fresh(16),
                           session="warm"))

    print("  [kvfleet] warming both workers (throwaway 3-turn session "
          "each)...")
    for rep in reps:
        warm(rep)

    t_run = time.perf_counter()
    walls = []

    # -- cold reference: a 16-token prompt no store or pool has seen --
    _, _, ttft_cold, wall = serve_turn("C1", fresh(16), "cold")
    walls.append(wall)

    # -- local-hit oracle: both turns stay on the sticky worker --------
    r_l1, _, _, wall = serve_turn("L1", fresh(), "local")
    walls.append(wall)
    p2_local = np.asarray(r_l1.tokens)[:16].astype(np.int32)
    _, rep_l, ttft_local, wall = serve_turn("L2", p2_local, "local")
    walls.append(wall)
    print(f"  [kvfleet] session 'local': both turns on "
          f"{rep_l.replica_id} — turn-2 served from its own store")

    # -- migration: kill the sticky worker between the turns -----------
    r_m1, _, _, wall = serve_turn("M1", fresh(), "mig")
    walls.append(wall)
    sticky_id = router._affinity["mig"]
    (sticky,) = [r for r in reps if r.replica_id == sticky_id]
    sticky.kill()
    deadline = time.monotonic() + 10.0
    while sticky.proc.poll() is None and time.monotonic() < deadline:
        time.sleep(0.01)
    print(f"  [kvfleet] session 'mig': SIGKILLed its sticky worker "
          f"{sticky_id} mid-conversation (pid reaped)")
    # let supervision discover the corpse and respawn it BEFORE the next
    # turn, so the migrated TTFT measures the transfer, not the heal
    for _ in range(400):
        router.pump()
        if router.counters.heals:
            break
    print(f"  [kvfleet] supervision healed {sticky_id} "
          f"({router.counters.heals} heal(s), spawn #{sticky.spawns}); "
          f"its local page store died with the old process")
    warm(sticky)
    p2_mig = np.asarray(r_m1.tokens)[:16].astype(np.int32)
    r_m2, rep_m, ttft_xfer, wall = serve_turn("M2", p2_mig, "mig")
    walls.append(wall)
    total = time.perf_counter() - t_run
    print(f"  [kvfleet] turn 2 re-routed to {rep_m.replica_id}, whose "
          f"local store holds no trace of the session — "
          f"{int(rep_m.counters['pool_hit_tokens'])} prompt tokens "
          f"came over the pool socket")

    # the migrated turn is a latency tier, never a correctness tier:
    # verify bit-equal to a store-less, pool-less in-process oracle
    oracle = tw.build_tiny_loop()
    try:
        oracle.submit(Request(rid="o", prompt=p2_mig))
        (ro,) = oracle.run_until_idle()
        bit_equal = np.array_equal(np.asarray(r_m2.tokens),
                                   np.asarray(ro.tokens))
    finally:
        oracle.close()

    snap = pool.snapshot()
    wire_s = (rep_m.collect() or {}).get("goodput", {}).get(
        "serve/kvstore/wire_s", 0.0)
    print(f"  [kvfleet] {'turn-2 TTFT':<14} {'local hit':>12} "
          f"{'transferred':>12} {'cold':>12}")
    print(f"  [kvfleet] {'':<14} {ttft_local:>10.1f}ms "
          f"{ttft_xfer:>10.1f}ms {ttft_cold:>10.1f}ms")
    print("  [kvfleet] (tiny CPU-proxy models: a 16-token prefill is "
          "nearly free, so the wire cost shows; at real prefill "
          "lengths the transfer wins — see the slow bench guard in "
          "tests/test_kvpool_proc.py)")
    print(f"  [kvfleet] pool moved {int(snap['bytes_moved'])} bytes "
          f"({int(snap['bytes_in'])} in / {int(snap['bytes_out'])} out), "
          f"{int(snap['pages'])} pages resident, "
          f"{int(snap['fetch_hits'])}/{int(snap['fetches'])} fetch hits, "
          f"{int(snap['nacks'])} nacks, {int(snap['evictions'])} "
          f"evictions")
    print(f"  [kvfleet] {rep_m.replica_id} charged {wire_s * 1e3:.1f} ms "
          f"to the serve/kvstore/wire goodput bucket (transfer wall "
          f"time, not hidden)")
    print(f"  [kvfleet] migrated turn bit-equal to cold oracle: "
          f"{'yes' if bit_equal else 'NO'}")

    router.close()
    pool.close()
    if args.metrics_port >= 0:
        from rocket_tpu.observe.export import unregister_source

        unregister_source("serve_kvpool")

    lat = np.asarray(walls)
    return dict(lat=lat, total=total,
                dispatches=int(router.counters.routed), unit="routes",
                accepted=0, drafted=0, new_tokens=tw.TOTAL - tw.P)


def run_train_serve(args, model, draft, params, draft_params, arrivals,
                    prompts):
    """Train-while-serve: a stand-in trainer publishes verified weight
    versions while ONE real worker process serves, and a
    :class:`rocket_tpu.serve.WeightFeed` hot-swaps each publication into
    the live loop between decode rounds — integrity-verified, reshard-
    gated, donation-based (HBM never holds two copies of the params,
    and the swap retraces nothing).  Publication #1 is torn live by
    :class:`rocket_tpu.testing.chaos.TornPublishInjector` (a bit flip
    AFTER its commit marker lands) and the deep verify gate rejects it
    without touching serving; ``feed.rollback()`` then steps the fleet
    back one published version.  Outputs are verified bit-equal to an
    in-process oracle on the same publication.  See
    docs/reliability.md ("Live weight updates")."""
    from rocket_tpu.persist.publish import WeightPublisher
    from rocket_tpu.serve import (
        Completed, ProcReplica, Request, WeightFeed, WorkerSpec,
        register_swap_source,
    )
    from rocket_tpu.testing import workers as tw
    from rocket_tpu.testing.chaos import TornPublishInjector

    root = tempfile.mkdtemp(prefix="rocket_tpu_publish_")
    spec = WorkerSpec(builder="rocket_tpu.testing.workers:build_tiny_loop")
    t = time.perf_counter()
    rep = ProcReplica(spec, "ts0")
    print(f"  [trainserve] spawned worker ts0 (pid {rep.pid}) in "
          f"{time.perf_counter() - t:.1f}s; boot weights version "
          f"{rep.weights_version} (seed-initialised, never published)")
    feed = WeightFeed(root, [rep])
    if args.metrics_port >= 0:
        register_swap_source(feed)
    print(f"  [trainserve] WeightFeed watching {root}")

    # the "trainer": the real two-phase-commit publisher wrapped in the
    # chaos injector — publication index 1 (version 20) gets one leaf
    # bit-flipped AFTER its commit marker lands, the corruption shape
    # shallow verification cannot see.  keep=3 retains the rollback
    # target through the whole demo.
    publisher = TornPublishInjector(
        WeightPublisher(root, keep=3), tear_on={1: "garble"})

    def publish(step, seed):
        _, _, p, _ = tw.tiny_models(seed_target=seed)
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()).reshape(-1), ("data",))
        return publisher.publish({"params": p}, step=step, mesh=mesh)

    rng = np.random.default_rng(7)
    prompt = rng.integers(1, tw.VOCAB, size=tw.P).astype(np.int32)
    walls = []
    seq = iter(range(1000))

    def serve(tag):
        t0 = time.perf_counter()
        assert rep.submit(Request(rid=f"{tag}-{next(seq)}", prompt=prompt))
        out = []
        for _ in range(2000):
            rep.pump()
            out.extend(rep.drain_results())
            if out:
                break
        walls.append((time.perf_counter() - t0) * 1e3)
        (res,) = out
        assert isinstance(res, Completed), res
        return np.asarray(res.tokens)

    t_run = time.perf_counter()
    boot_tokens = serve("boot")

    # -- step 10 publishes; the feed offers it; the worker swaps live --
    publish(10, seed=5)
    swaps = feed.poll()
    v10_tokens = serve("v10")
    print(f"  [trainserve] published step 10 -> feed swapped {swaps} "
          f"replica(s); worker now serving version "
          f"{rep.weights_version} "
          f"(outputs changed: {not np.array_equal(boot_tokens, v10_tokens)})")
    print(f"  [trainserve] swap wall so far: "
          f"{rep.counters.get('swap_ms_total', 0.0):.1f} ms "
          f"(charged to the 'swap' goodput bucket)")

    # -- step 20 is torn in flight: rejected, serving untouched --------
    publish(20, seed=9)
    assert feed.poll() == 0
    torn_tokens = serve("torn")
    print(f"  [trainserve] published step 20 TORN (bit flip past the "
          f"commit marker) -> deep verify rejected it: "
          f"publish_rejected={int(rep.counters.get('publish_rejected', 0))},"
          f" still serving version {rep.weights_version}, outputs "
          f"untouched: {np.array_equal(torn_tokens, v10_tokens)}; "
          f"a flight-recorder dump of the rejection was written "
          f"worker-side; the feed will not re-offer it")

    # -- step 30 supersedes the rejected version -----------------------
    p30 = publish(30, seed=11)
    feed.poll()
    v30_tokens = serve("v30")
    print(f"  [trainserve] published step 30 -> worker on version "
          f"{rep.weights_version} "
          f"({int(rep.counters.get('swaps', 0))} swaps, "
          f"{int(rep.counters.get('publish_rejected', 0))} rejections)")

    # -- divergence drill: bounded rollback to the previous version ----
    feed.rollback()
    rb_tokens = serve("rollback")
    print(f"  [trainserve] rollback -> version {rep.weights_version}; "
          f"outputs bit-equal to the version-10 serve: "
          f"{np.array_equal(rb_tokens, v10_tokens)}")

    # the swap is a delivery tier, never a correctness tier: an
    # in-process loop swapped onto the SAME publication must agree
    # bit-for-bit with the worker across the process boundary
    oracle = tw.build_tiny_loop()
    try:
        oracle.swap_weights(p30, 30)
        t0 = time.perf_counter()
        oracle.submit(Request(rid="oracle", prompt=prompt))
        (ro,) = oracle.run_until_idle()
        walls.append((time.perf_counter() - t0) * 1e3)
        bit_equal = np.array_equal(v30_tokens, np.asarray(ro.tokens))
    finally:
        oracle.close()
    total = time.perf_counter() - t_run
    print(f"  [trainserve] version-30 outputs bit-equal to in-process "
          f"oracle on the same publication: {'yes' if bit_equal else 'NO'}")
    snap = feed.snapshot()
    print(f"  [trainserve] feed: {int(snap['polls'])} polls, "
          f"{int(snap['pushes'])} pushes, {int(snap['swaps'])} swaps, "
          f"{int(snap['rejected'])} rejected, "
          f"{int(snap['rollbacks'])} rollbacks, "
          f"version gauge {int(snap['version'])}")

    n_swaps = int(rep.counters.get("swaps", 0))
    rep.close()
    feed.stop()
    if args.metrics_port >= 0:
        from rocket_tpu.observe.export import unregister_source

        unregister_source("serve_swap")
    shutil.rmtree(root, ignore_errors=True)
    return dict(lat=np.asarray(walls), total=total, dispatches=n_swaps,
                unit="live swaps", accepted=0, drafted=0,
                new_tokens=tw.TOTAL - tw.P)


def run_tenants(args, model, draft, params, draft_params, arrivals,
                prompts):
    """--mode tenants: multi-tenant serving end to end (see
    docs/reliability.md "Multi-tenant serving").  One seeded
    mixed-tenant trace — interactive chat sessions with shared
    prefixes, standard API traffic, a bulk batch tenant — replays
    twice against the weighted-fair ServingLoop through the
    ``serve/loadgen.py`` harness: once clean, once with a
    ``BatchFloodInjector`` pushing batch-class work every round.
    Weighted-fair admission (8/4/1), per-class slot budgets, and cheap
    batch preemption hold the interactive p95 TTFT roughly flat under
    the flood, while the flood itself is shed/preempted — never
    starved: its completions land in the troughs.  The replay harness
    asserts exactly-once typed delivery for every trace event
    inline."""
    from rocket_tpu.serve import (
        DEFAULT_CLASS_WEIGHTS,
        Request,
        ServingLoop,
        TenantSpec,
        TraceConfig,
        register_slo_source,
        replay_trace,
        synth_trace,
    )
    from rocket_tpu.testing.chaos import BatchFloodInjector

    speed = 10.0
    mix = [
        TenantSpec("acme", "interactive", share=3.0, sessions=2),
        TenantSpec("corp", "standard", share=2.0),
        TenantSpec("bulk", "batch", share=1.0),
    ]
    cfg = TraceConfig(duration_s=8.0, base_rate=2.0, burst_rate=4.0,
                      burst_every_s=3.0, burst_len_s=1.0,
                      prompt_len_min=6, prompt_len_max=PROMPT,
                      shared_prefix_len=4, max_new_min=4,
                      max_new_max=12, vocab=VOCAB)
    trace = synth_trace(mix, cfg, seed=42)
    args.requests = len(trace)      # seed-determined; _report reads it
    w = DEFAULT_CLASS_WEIGHTS
    print(f"  [tenants] trace: {len(trace)} events over "
          f"{cfg.duration_s:.0f}s, replayed at {speed:.0f}x — "
          + ", ".join(f"{t.name}={t.slo_class}" for t in mix))
    print(f"  [tenants] weights interactive/standard/batch = "
          f"{w['interactive']:.0f}/{w['standard']:.0f}/{w['batch']:.0f}, "
          f"batch slot budget {args.queue_capacity // 4} of "
          f"{args.queue_capacity} queue slots")

    def factory():
        return ContinuousBatcher(model, draft, params, draft_params,
                                 total_len=PROMPT + NEW, n_draft=NDRAFT)

    # few rows on purpose: preemption only fires when urgent arrivals
    # outnumber free rows, so a wide batch would hide the whole arc
    mb = min(args.max_batch, 3)

    def one_pass(label, flood):
        loop = ServingLoop(
            factory, max_batch=mb,
            queue_capacity=args.queue_capacity,
            class_slot_budget={"batch": args.queue_capacity // 4},
        )
        if args.metrics_port >= 0:
            # the per-class gauges the autoscaler's class policies read
            register_slo_source(loop, "serve_slo")
        # keep the compile out of the first TTFT sample
        loop.submit(Request(rid="warm",
                            prompt=np.arange(1, 9, dtype=np.int32),
                            max_new_tokens=4))
        loop.run_until_idle()
        inj = None
        if flood:
            inj = BatchFloodInjector(loop, per_tick=1, prompt_len=8,
                                     max_new_tokens=8, vocab=VOCAB,
                                     tenant="flood")

            def pump():
                inj.tick()
                return loop.run_round()

            report = replay_trace(trace, loop, speed=speed, pump=pump)
        else:
            report = replay_trace(trace, loop, speed=speed)
        print(f"  [tenants] {label}:")
        print(f"  [tenants]   {'class':<12} {'sub':>4} {'done':>5} "
              f"{'shed':>5} {'ttft p95':>9} {'attain':>7}")
        for cls in ("interactive", "standard", "batch"):
            st = report.per_class.get(cls)
            if not st:
                continue
            p95 = st.get("ttft_p95_ms")
            att = st.get("attainment")
            p95_s = f"{p95:>7.0f}ms" if p95 is not None else f"{'--':>9}"
            att_s = f"{att:>7.2f}" if att is not None else f"{'--':>7}"
            print(f"  [tenants]   {cls:<12} {int(st['submitted']):>4} "
                  f"{int(st['completed']):>5} {int(st['shed']):>5} "
                  f"{p95_s} {att_s}")
        snap = loop.counters.snapshot()
        if flood:
            print(f"  [tenants]   flood: {inj.submitted} submitted, "
                  f"{inj.rejected} rejected at the budget, "
                  f"{int(snap['class/batch/shed'])} shed, "
                  f"{int(snap['preempted'])} preempted / "
                  f"{int(snap['resumed'])} resumed (bit-equal, "
                  f"exactly-once asserted by the harness)")
        p95 = loop.slo_latency.ttft_ms["interactive"].percentile(95)
        lat = np.asarray(list(loop.latency.e2e_ms._samples))
        if args.metrics_port >= 0:
            from rocket_tpu.observe.export import unregister_source

            unregister_source("serve_slo")
        loop.close()
        return float(p95), report, snap, lat

    # pass 0, unprinted: the admit edge compiles once per distinct
    # prompt length, so replay the whole trace on a throwaway loop
    # first — the measured passes then compare scheduling, not compiles
    warm_loop = ServingLoop(factory, max_batch=mb,
                            queue_capacity=args.queue_capacity)
    replay_trace(trace, warm_loop, speed=1000.0)
    warm_loop.close()

    crit = bool(getattr(args, "critpath", False))
    tracer = None
    if crit:
        # the serve loop records into the process tracer; arm it so the
        # flood pass yields a per-class critical-path decomposition
        from rocket_tpu.observe import trace as _obs_trace

        tracer = _obs_trace.arm(1 << 15)

    base_p95, base_rep, _, _ = one_pass("pass 1 — mixed trace, "
                                        "no flood", flood=False)
    if tracer is not None:
        tracer.clear()  # attribute pass 2 only (same rids both passes)
    flood_p95, flood_rep, snap, lat = one_pass(
        "pass 2 — same trace + batch flood every round", flood=True)
    if tracer is not None:
        print("  [tenants] critical path per class (flood pass — where "
              "each class's time went):")
        for line in flood_rep.critpath_summary(
                tracer.events()).splitlines():
            print(f"  [tenants]   {line}")
    ratio = flood_p95 / max(base_p95, 1e-9)
    print(f"  [tenants] interactive ttft p95: {base_p95:.0f}ms clean vs "
          f"{flood_p95:.0f}ms under flood ({ratio:.2f}x — the "
          f"acceptance bench holds this under 1.25x)")
    print(f"  [tenants] goodput/chip: {base_rep.goodput_per_chip:.0f} "
          f"tok/s clean vs {flood_rep.goodput_per_chip:.0f} tok/s "
          f"under flood (flood batch tokens count — cheap work fills "
          f"the troughs)")
    done = max(1, int(flood_rep.completed))
    return dict(lat=lat if lat.size else np.zeros(1),
                total=flood_rep.wall_s, dispatches=int(snap["rounds"]),
                unit="rounds", accepted=0, drafted=0,
                new_tokens=max(1, int(flood_rep.generated_tokens
                                      / done)))


def _report(name, res, n_requests):
    lat = res["lat"]
    new = res.get("new_tokens", NEW)
    print(f"[{name}] served {n_requests} requests in {res['dispatches']} "
          f"{res['unit']} ({n_requests * new / res['total']:.0f} tok/s "
          f"aggregate)")
    print(f"[{name}] latency ms: p50 {np.percentile(lat, 50):.0f}  "
          f"p90 {np.percentile(lat, 90):.0f}  max {lat.max():.0f}")
    if res["drafted"]:
        print(f"[{name}] speculative acceptance "
              f"{res['accepted'] / res['drafted']:.0%} "
              f"(int8 self-draft, n_draft={NDRAFT})")
    if "joins" in res:
        print(f"[{name}] {res['joins']} requests joined a half-finished "
              f"batch")


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--requests", type=int, default=24)
    parser.add_argument("--max-batch", type=int, default=8)
    parser.add_argument("--arrival-ms", type=float, default=30.0,
                        help="mean simulated inter-arrival gap")
    parser.add_argument("--mode",
                        choices=("group", "continuous", "both", "robust",
                                 "fleet", "fleet-proc", "cache",
                                 "cache-fleet", "train-serve", "tenants"),
                        default="both")
    parser.add_argument("--autoscale", action="store_true",
                        help="[fleet-proc] start at ONE worker process "
                             "and let the goodput-driven Autoscaler "
                             "grow/drain the fleet off the metrics "
                             "surface (TTFT p95 SLO)")
    parser.add_argument("--standby", type=int, default=0,
                        help="[fleet-proc] keep N pre-warmed standby "
                             "worker processes off-rotation (implies "
                             "--autoscale); scale-up promotes one by "
                             "rename instead of paying a cold spawn + "
                             "compile on the latency path")
    parser.add_argument("--kv-bytes", type=int, default=1 << 28,
                        help="[cache] prefix-store byte budget (LRU "
                             "eviction past it)")
    parser.add_argument("--replicas", type=int, default=3,
                        help="[fleet] thread-backed decode replicas")
    parser.add_argument("--prefill-replicas", type=int, default=0,
                        help="[fleet] disaggregated prefill-lane replicas "
                             "(0 = merged lane: decode replicas prefill)")
    parser.add_argument("--kill-round", type=int, default=-1,
                        help="[fleet] kill replica r0 on this round via "
                             "ReplicaKillInjector; the router drains, "
                             "salvages, and rebuilds it live (-1 = off). "
                             "[fleet-proc] the burst beat that SIGKILLs "
                             "w0's worker (-1 = a third into the burst, "
                             "-2 = no kill)")
    parser.add_argument("--queue-capacity", type=int, default=16,
                        help="[robust] bounded admission queue size; a "
                             "full queue rejects with a typed Overloaded")
    parser.add_argument("--deadline-ms", type=float, default=0.0,
                        help="[robust] per-request deadline (0 = none); "
                             "late rows are evicted at a round boundary")
    parser.add_argument("--watchdog-ms", type=float, default=500.0,
                        help="[robust] stuck-step watchdog poll timeout "
                             "(armed when --stuck-round >= 0)")
    parser.add_argument("--stuck-round", type=int, default=-1,
                        help="[robust] wedge this device round via "
                             "StuckStepInjector (-1 = no fault)")
    parser.add_argument("--burst", type=int, default=0,
                        help="[robust] replace the Poisson trace with "
                             "deterministic bursts of this size (0 = off)")
    parser.add_argument("--trace", action="store_true",
                        help="arm the structured tracer: per-request "
                             "spans, a p50/p95 TTFT/TPOT table, and a "
                             "flight-recorder dump path at exit "
                             "(implies --mode robust)")
    parser.add_argument("--critpath", action="store_true",
                        help="[tenants] arm the tracer during the flood "
                             "pass and print the per-class critical-path "
                             "breakdown (queue_wait / prefill / decode / "
                             "preempt_parked ... — docs/observability.md)")
    parser.add_argument("--metrics-port", type=int, default=-1,
                        help="arm the goodput/retrace ledgers and serve "
                             "Prometheus text on this port's /metrics "
                             "(0 = OS-assigned; -1 = off); prints the "
                             "goodput bucket table at exit")
    args = parser.parse_args()
    if args.trace and args.mode not in ("robust", "fleet"):
        print("--trace instruments the robust loop; switching to "
              "--mode robust")
        args.mode = "robust"
    if args.mode == "fleet":
        # a fleet exists to absorb scale: default the trace up to
        # thousands of requests arriving fast (override with the flags)
        if args.requests == 24:
            args.requests = 2048
        if args.arrival_ms == 30.0:
            args.arrival_ms = 2.0
        print(f"[fleet] trace: {args.requests} requests, mean arrival gap "
              f"{args.arrival_ms} ms")

    # ONE seeded trace shared by both modes: identical arrivals and
    # prompts make the p50s directly comparable
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(
        rng.exponential(args.arrival_ms / 1e3, size=args.requests)
    )
    prompts = rng.integers(0, VOCAB, size=(args.requests, PROMPT))
    max_seq = (CACHE_PROMPT + NEW + NDRAFT if args.mode == "cache"
               else PROMPT + NEW + NDRAFT)
    if args.mode in ("fleet-proc", "cache-fleet", "train-serve"):
        # worker subprocesses build their own tiny models from a
        # WorkerSpec — nothing big to construct in this process
        model = draft = params = draft_params = None
    if args.mode == "cache-fleet":
        # the mode runs a scripted 5-request session trace (cold +
        # local 2-turn + migrated 2-turn); --requests is ignored
        args.requests = 5
    elif args.mode == "train-serve":
        # scripted publish/swap/reject/rollback trace (5 worker serves
        # + 1 in-process oracle serve); --requests is ignored
        args.requests = 6
    else:
        model, draft, params, draft_params = _build(max_seq=max_seq)

    metrics = None
    if args.metrics_port >= 0:
        from rocket_tpu.observe.export import MetricsServer
        from rocket_tpu.observe.ledger import arm_ledgers

        # arm both ledgers: compiles land in the goodput "compile"
        # bucket and every named jit edge runs under the retrace sentinel
        arm_ledgers()
        metrics = MetricsServer(port=args.metrics_port).start()
        print(f"[metrics] scrape http://127.0.0.1:{metrics.port}/metrics "
              f"(JSON: /metrics.json) while the demo runs")

    runners = {"group": run_group, "continuous": run_continuous,
               "robust": run_robust, "fleet": run_fleet,
               "fleet-proc": run_fleet_proc, "cache": run_cache,
               "cache-fleet": run_cache_fleet,
               "train-serve": run_train_serve, "tenants": run_tenants}
    modes = ["group", "continuous"] if args.mode == "both" else [args.mode]
    results = {}
    try:
        for m in modes:
            results[m] = runners[m](args, model, draft, params,
                                    draft_params, arrivals, prompts)
            _report(m, results[m], args.requests)
    finally:
        if metrics is not None:
            from rocket_tpu.observe.ledger import (
                disarm_ledgers,
                get_goodput,
            )

            disarm_ledgers()
            for line in get_goodput().table().splitlines():
                print(f"[metrics] {line}")
            metrics.stop()
    if len(results) == 2:
        g = np.percentile(results["group"]["lat"], 50)
        c = np.percentile(results["continuous"]["lat"], 50)
        print(f"per-request p50: continuous {c:.0f} ms vs group {g:.0f} ms "
              f"({g / max(c, 1e-9):.1f}x lower)")


if __name__ == "__main__":
    main()
