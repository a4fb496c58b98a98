"""Multi-tenant serving — cross-process proofs + the SLO bench guard
(spawn-heavy, heavy tail; ISSUE 18 acceptance).

The unit zone lives in ``tests/test_tenants.py``; this file proves the
tentpole where it is actually dangerous:

- kill BETWEEN preempt and resume (tier-1 acceptance): a batch-class
  request is preempted inside a worker process (its resume ticket is
  worker-side state), the worker is SIGKILLed before the resume, and
  supervision still resolves the request to EXACTLY ONE typed result —
  bit-equal to the cold oracle, because the supervisor's request shadow
  salvages the ORIGINAL request and determinism does the rest;
- per-class telemetry across the wire (tier-1): a worker's class
  counters and ClassLatency histograms ride the STEP reply and merge
  fleet-wide under the documented merge-then-recompute rule;
- the SLO guard (tier-1 acceptance): the interactive class with a
  deterministic batch flood underneath is served whole, as in the
  batch-free baseline, while the flood's batch work actually completes
  in the troughs;
- mixed-tenant trace replay over the REAL process fleet (``slow``):
  the seeded loadgen drives two worker processes through a router and
  every event resolves exactly once with per-class attainment reported.
"""

import time

import numpy as np
import pytest

from rocket_tpu.serve import (
    Completed,
    FleetRouter,
    ProcReplica,
    Request,
    TenantSpec,
    TraceConfig,
    WorkerSpec,
    replay_trace,
    synth_trace,
)
from rocket_tpu.testing import workers as tw
from rocket_tpu.testing.chaos import BatchFloodInjector

pytestmark = [pytest.mark.tenants, pytest.mark.procfleet,
              pytest.mark.serving]

BUILDER = "rocket_tpu.testing.workers:build_tiny_loop"
SPAWN_S = 240.0     # worker spawn includes a jax import + model init


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(17)
    return rng.integers(1, tw.VOCAB, size=(8, tw.P)).astype(np.int32)


def _await_corpse(rep, timeout=10.0):
    deadline = time.monotonic() + timeout
    while rep.proc.poll() is None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert rep.proc.poll() is not None, "worker survived SIGKILL"


def _cold_serve(prompt_rows):
    """rid-index -> (tokens, n_tok) from a fresh in-process loop over
    the SAME builder the workers run — the uninterrupted oracle."""
    loop = tw.build_tiny_loop()
    try:
        for i, p in enumerate(prompt_rows):
            assert loop.submit(Request(rid=i, prompt=p)) is None
        out = {}
        for res in loop.run_until_idle():
            assert isinstance(res, Completed), res
            out[res.rid] = np.asarray(res.tokens)
    finally:
        loop.close()
    return out


# -- kill between preempt and resume (tier-1 acceptance) ---------------------


def test_preempt_then_kill_resolves_exactly_once_bit_equal(prompts):
    """Acceptance: the preempted batch request's resume ticket dies with
    the SIGKILLed worker; the supervisor shadow salvages the ORIGINAL
    request, the heal re-routes it, and the caller still observes
    exactly one typed result — bit-equal to never having been
    preempted (or killed) at all."""
    spec = WorkerSpec(builder=BUILDER,
                      kwargs={"max_batch": 2, "kvstore_page_tokens": 3})
    a = ProcReplica(spec, "ten-a", spawn_timeout_s=SPAWN_S,
                    rpc_timeout_s=SPAWN_S)
    b = ProcReplica(spec, "ten-b", spawn_timeout_s=SPAWN_S,
                    rpc_timeout_s=SPAWN_S)
    router = FleetRouter([a, b])
    try:
        # pin the scenario to worker a: a batch row decoding next to a
        # standard row, then two interactive arrivals force preemption
        assert a.submit(Request(rid="bat", prompt=prompts[0],
                                slo_class="batch", tenant="bulk"))
        assert a.submit(Request(rid="std", prompt=prompts[1]))
        a.pump()                       # both admitted, one decode round
        for i, rid in ((2, "i2"), (3, "i3")):
            assert a.submit(Request(rid=rid, prompt=prompts[i],
                                    slo_class="interactive"))
        a.pump()                       # round boundary: batch evicted
        pre_kill = dict(a.counters)    # snapshot BEFORE the respawn reset
        assert pre_kill.get("preempted") == 1.0
        assert pre_kill.get("class/batch/preempted") == 1.0

        # the window under test: ticket parked worker-side, no result
        a.kill()
        _await_corpse(a)

        results = router.run_until_idle()
        assert sorted(r.rid for r in results) == ["bat", "i2", "i3",
                                                  "std"]
        assert all(isinstance(r, Completed) for r in results), results
        oracle = _cold_serve([prompts[i] for i in range(4)])
        for rid, i in (("bat", 0), ("std", 1), ("i2", 2), ("i3", 3)):
            (res,) = [r for r in results if r.rid == rid]
            assert np.array_equal(np.asarray(res.tokens), oracle[i]), rid
        assert router.counters.heals == 1
        assert a.spawns == 2           # the corpse was respawned
    finally:
        router.close()


# -- per-class telemetry across the wire (tier-1) ----------------------------


def test_class_counters_and_slo_latency_cross_process(prompts):
    spec = WorkerSpec(builder=BUILDER)
    rep = ProcReplica(spec, "ten-t", spawn_timeout_s=SPAWN_S,
                      rpc_timeout_s=SPAWN_S)
    router = FleetRouter([rep])
    try:
        assert router.submit(Request(rid="i0", prompt=prompts[0],
                                     tenant="acme",
                                     slo_class="interactive")) is None
        (res,) = router.run_until_idle()
        assert isinstance(res, Completed)
        # the worker's per-class counters rode the STEP reply
        assert rep.counters.get("class/interactive/submitted") == 1.0
        assert rep.counters.get("class/interactive/completed") == 1.0
        # ...and so did its ClassLatency; the router merges windows
        merged = router.slo_latency()
        assert merged.ttft_ms["interactive"].count == 1
        assert merged.e2e_ms["interactive"].count == 1
        att = merged.attainment({"interactive": 1e9})
        assert att["interactive"] == 1.0
        # per-class routing split on the fleet side
        snap = router.counters.snapshot()
        assert snap["class/interactive/routed"] == 1.0
    finally:
        router.close()


# -- the SLO bench guard (tier-1 acceptance) ---------------------------------


def _interactive_trace():
    return synth_trace(
        [TenantSpec("acme", "interactive", share=1.0)],
        TraceConfig(duration_s=6.0, base_rate=2.5, prompt_len_min=4,
                    prompt_len_max=10, max_new_min=2, max_new_max=4,
                    vocab=tw.VOCAB),
        seed=29)


def _warm(loop):
    """Serve a couple of throwaway requests so every measured TTFT is a
    warm one (compiles otherwise land in the first sample)."""
    rng = np.random.default_rng(5)
    for i in range(2):
        p = rng.integers(1, tw.VOCAB, size=6).astype(np.int32)
        assert loop.submit(Request(rid=f"warm{i}", prompt=p,
                                   max_new_tokens=3)) is None
    loop.run_until_idle()


def _replay_interactive(flood):
    """Replay the SAME seeded interactive trace; ``flood`` adds the
    deterministic batch flood under it.  Returns the loop."""
    loop = tw.build_tiny_loop(max_batch=3, queue_capacity=32,
                              class_slot_budget={"batch": 6})
    _warm(loop)
    trace = _interactive_trace()
    if flood:
        inj = BatchFloodInjector(loop, per_tick=1, prompt_len=6,
                                 max_new_tokens=8, vocab=tw.VOCAB)

        def pump():
            inj.tick()
            return loop.run_round()

        replay_trace(trace, loop, speed=30.0, pump=pump)
        assert inj.submitted > 0
    else:
        replay_trace(trace, loop, speed=30.0)
    assert loop.slo_latency.ttft_ms["interactive"].count > 0
    return loop


def test_interactive_served_whole_under_batch_flood():
    """Acceptance: with a batch flood filling every trough, the
    interactive class is served as if the flood were not there — every
    request completes, none is shed or preempted — because batch rows give
    way (preempted for an interactive arrival, resumed later), and the
    flood is held back by weighted fairness + preemption rather than
    starved out: batch work really completes underneath.  (This was
    "interactive p95 TTFT within 1.25x of the batch-free baseline" on a
    CPU's clock; the first token waits when an arrival finds no row, and
    whether it finds one is what the counters say.)"""
    base_loop = _replay_interactive(flood=False)
    base = dict(base_loop.counters.class_counts["interactive"])
    base_loop.close()
    flood_loop = _replay_interactive(flood=True)
    counters = flood_loop.counters
    flood_loop.close()
    assert counters.class_counts["interactive"] == base
    assert base["completed"] == base["submitted"] > 0
    assert base["shed"] == base["preempted"] == 0
    # batch gave way, and came back
    assert counters.class_counts["batch"]["preempted"] >= 1
    assert counters.class_counts["batch"]["resumed"] \
        == counters.class_counts["batch"]["preempted"]
    # the troughs were actually filled: batch completed AND the fairness
    # machinery (not idle luck) was exercised
    assert counters.class_counts["batch"]["completed"] >= 1
    assert counters.class_counts["interactive"]["completed"] > 0


# -- mixed-tenant replay over the real process fleet (slow) ------------------


@pytest.mark.slow
@pytest.mark.resilience
def test_trace_replay_over_process_fleet(prompts):
    """The loadgen's stated purpose: a seeded mixed-tenant trace drives
    TWO worker processes through the router; every event resolves to
    exactly one typed result (replay_trace asserts it) and the report
    carries per-class attainment and goodput-per-chip."""
    spec = WorkerSpec(builder=BUILDER)
    reps = [ProcReplica(spec, f"ten-f{i}", spawn_timeout_s=SPAWN_S,
                        rpc_timeout_s=SPAWN_S) for i in range(2)]
    router = FleetRouter(reps)
    try:
        trace = synth_trace(
            [TenantSpec("acme", "interactive", share=3.0, sessions=2),
             TenantSpec("corp", "standard", share=2.0),
             TenantSpec("bulk", "batch", share=1.0)],
            TraceConfig(duration_s=6.0, base_rate=2.0, prompt_len_min=4,
                        prompt_len_max=10, shared_prefix_len=4,
                        max_new_min=2, max_new_max=4, vocab=tw.VOCAB),
            seed=31)
        report = replay_trace(trace, router, speed=10.0, chips=2)
        assert report.submitted == len(trace)
        assert report.completed > 0
        assert report.goodput_per_chip > 0.0
        for cls, stats in report.per_class.items():
            assert stats["submitted"] > 0
            if stats["completed"] > 0:
                assert "ttft_p95_ms" in stats, (cls, stats)
        # the merged fleet view fed the report's attainment gauges
        assert router.slo_latency().ttft_ms["interactive"].count > 0
    finally:
        router.close()
