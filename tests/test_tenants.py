"""Multi-tenant serving tests — SLO classes end to end (ISSUE 18).

Four layers, mirroring the tentpole:

- units: the weighted-fair admission queue (stride shares, EDF within a
  class, per-class slot/byte budgets, per-class depth counters), the
  Request class vocabulary, and the v2 wire handshake matrix;
- preemption: a batch-class in-flight row evicted at a round boundary
  resumes from its parked ticket and yields EXACTLY ONE typed result,
  bit-identical to the uninterrupted oracle — with and without the
  prefix-cache tier armed;
- per-class observability: ServeCounters / FleetCounters class splits,
  ClassLatency's merge-then-recompute attainment rule, and the
  ``serve_slo/*`` export source;
- the harness: seeded trace synthesis (determinism, diurnal shape,
  shared-prefix sessions, tenant mix), replay against a real loop with
  exactly-once asserted, and the chaos additions (BatchFloodInjector,
  the bursty_arrivals tenant-skew knob).

Spawn-heavy cases (process fleet, kill-between-preempt-and-resume, the
batch-flood acceptance) live in tests/test_tenants_proc.py
on the heavy tail.
"""

import numpy as np
import pytest

import jax

from rocket_tpu.models.generate import (
    ContinuousBatcher,
    speculative_generate_batched,
)
from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
from rocket_tpu.serve import (
    SLO_CLASSES,
    AdmissionQueue,
    ClassLatency,
    Completed,
    DEFAULT_CLASS_WEIGHTS,
    Overloaded,
    PrefixKVStore,
    Request,
    SLOPolicy,
    ServeCounters,
    ServingLoop,
    TenantSpec,
    TraceConfig,
    replay_trace,
    synth_trace,
    wire,
)
from rocket_tpu.serve.autoscale import Autoscaler
from rocket_tpu.testing.chaos import BatchFloodInjector, bursty_arrivals

pytestmark = [pytest.mark.serving, pytest.mark.tenants]

B, P, TOTAL, NDRAFT = 3, 8, 24, 4


class FakeClock:
    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float = 1.0) -> None:
        self.t += dt


def _lm(seed=1):
    cfg = TransformerConfig(
        vocab_size=64, hidden=32, n_layers=2, n_heads=4, max_seq=64,
    )
    m = TransformerLM(cfg)
    p = m.init(
        jax.random.PRNGKey(seed),
        {"tokens": np.zeros((1, P), np.int32),
         "positions": np.zeros((1, P), np.int32)},
    )["params"]
    return m, p


@pytest.fixture(scope="module")
def models():
    model, params = _lm(seed=1)
    draft, _ = _lm(seed=1)
    _, dparams = _lm(seed=7)
    return model, draft, params, dparams


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(13)
    return rng.integers(1, 64, size=(8, P)).astype(np.int32)


def _factory(models, **kw):
    model, draft, params, dparams = models

    def factory():
        return ContinuousBatcher(
            model, draft, params, dparams,
            total_len=TOTAL, n_draft=NDRAFT, eos_token=None, **kw,
        )

    return factory


def _oracle(models, prompt_row, max_new=TOTAL - P):
    model, draft, params, dparams = models
    toks = speculative_generate_batched(
        model, params, draft, dparams, prompt_row[None, :],
        max_new_tokens=max_new, n_draft=NDRAFT,
    )
    return np.asarray(toks[0])


def _req(rid, prompt, **kw):
    return Request(rid=rid, prompt=prompt, **kw)


# -- units: Request class vocabulary --------------------------------------


class TestRequestClasses:
    def test_default_is_standard_no_tenant(self):
        r = _req(0, np.ones(4, np.int32))
        assert r.slo_class == "standard" and r.tenant is None

    def test_unknown_class_refused(self):
        with pytest.raises(ValueError, match="slo_class"):
            _req(0, np.ones(4, np.int32), slo_class="platinum")

    def test_tenant_and_class_ride(self):
        r = _req(0, np.ones(4, np.int32), tenant="acme",
                 slo_class="interactive")
        assert r.tenant == "acme" and r.slo_class == "interactive"

    def test_class_order_is_priority_order(self):
        assert SLO_CLASSES == ("interactive", "standard", "batch")


# -- units: weighted-fair queue --------------------------------------------


class TestWeightedFairQueue:
    def test_stride_shares_deterministic(self):
        """interactive weight 2, batch weight 1 -> the pop sequence is
        exactly I B I I B I (stride scheduling, ties to the
        higher-priority class)."""
        q = AdmissionQueue(16, weights={"interactive": 2.0,
                                        "standard": 4.0, "batch": 1.0})
        for i in range(4):
            q.offer(_req(f"i{i}", np.ones(4, np.int32),
                         slo_class="interactive"))
        for i in range(2):
            q.offer(_req(f"b{i}", np.ones(4, np.int32), slo_class="batch"))
        order = [q.pop().slo_class[0] for _ in range(6)]
        assert order == ["i", "b", "i", "i", "b", "i"]

    def test_default_weights_favor_interactive_8x(self):
        q = AdmissionQueue(64)
        for i in range(18):
            q.offer(_req(f"i{i}", np.ones(4, np.int32),
                         slo_class="interactive"))
            q.offer(_req(f"b{i}", np.ones(4, np.int32), slo_class="batch"))
        first9 = [q.pop().slo_class for _ in range(9)]
        # 8 interactive pops before batch's first trough
        assert first9.count("interactive") == 8
        assert DEFAULT_CLASS_WEIGHTS["interactive"] \
            / DEFAULT_CLASS_WEIGHTS["batch"] == 8.0

    def test_single_class_stays_fifo(self):
        q = AdmissionQueue(8)
        for i in range(4):
            q.offer(_req(i, np.ones(4, np.int32)))
        assert [q.pop().rid for _ in range(4)] == [0, 1, 2, 3]

    def test_edf_within_class_deadlineless_behind(self):
        q = AdmissionQueue(8)
        q.offer(_req("late", np.ones(4, np.int32), deadline=90.0))
        q.offer(_req("none1", np.ones(4, np.int32)))
        q.offer(_req("soon", np.ones(4, np.int32), deadline=10.0))
        q.offer(_req("none2", np.ones(4, np.int32)))
        order = [q.pop().rid for _ in range(4)]
        assert order == ["soon", "late", "none1", "none2"]

    def test_slot_budget_refuses_only_that_class(self):
        q = AdmissionQueue(8, slot_budget={"batch": 2})
        assert q.offer(_req(0, np.ones(4, np.int32), slo_class="batch"))
        assert q.offer(_req(1, np.ones(4, np.int32), slo_class="batch"))
        assert not q.offer(_req(2, np.ones(4, np.int32),
                                slo_class="batch"))
        # other classes still welcome past batch's budget
        assert q.offer(_req(3, np.ones(4, np.int32),
                            slo_class="interactive"))

    def test_byte_budget_tracks_pop_and_shed(self):
        q = AdmissionQueue(8, byte_budget={"batch": 40})
        big = _req(0, np.ones(8, np.int32), slo_class="batch")    # 32 B
        assert q.offer(big)
        assert q.bytes_queued("batch") == 32
        assert not q.offer(_req(1, np.ones(4, np.int32),          # 16 B
                                slo_class="batch"))
        q.pop()
        assert q.bytes_queued("batch") == 0
        assert q.offer(_req(2, np.ones(4, np.int32), slo_class="batch"))

    def test_urgent_depth_excludes_batch(self):
        q = AdmissionQueue(10)
        for i in range(4):
            q.offer(_req(f"b{i}", np.ones(4, np.int32), slo_class="batch"))
        q.offer(_req("s", np.ones(4, np.int32)))
        assert q.depth() == 5 and q.depth("batch") == 4
        assert q.urgent_waiting() == 1
        assert q.depth_frac == 0.5
        assert q.depth_frac_urgent == 0.1

    def test_bad_knobs_refused(self):
        with pytest.raises(ValueError, match="unknown SLO class"):
            AdmissionQueue(4, weights={"gold": 2.0})
        with pytest.raises(ValueError, match="must be > 0"):
            AdmissionQueue(4, weights={"batch": 0.0})

    def test_per_class_depth_counters_emitted(self):
        from rocket_tpu.observe.trace import Tracer

        tracer = Tracer(capacity=64, enabled=True)
        q = AdmissionQueue(4, name="r0", tracer=tracer, clock=FakeClock())
        q.offer(_req(0, np.ones(4, np.int32), slo_class="batch"))
        q.offer(_req(1, np.ones(4, np.int32), slo_class="interactive"))
        q.pop()   # interactive pops first (smaller stride state tie)

        def series(name):
            key = name.rsplit("/", 1)[-1]
            return [e[5][key] for e in tracer.events() if e[1] == name]

        assert series("serve/queue/r0/batch/depth") == [1.0, 1.0, 1.0]
        assert series("serve/queue/r0/interactive/depth") == [0.0, 1.0,
                                                              0.0]
        assert series("serve/queue/r0/depth") == [1.0, 2.0, 1.0]

    def test_shed_hopeless_is_per_class_order_preserving(self):
        q = AdmissionQueue(8)
        q.offer(_req("b-doomed", np.ones(4, np.int32), slo_class="batch",
                     deadline=1.0))
        q.offer(_req("i-doomed", np.ones(4, np.int32),
                     slo_class="interactive", deadline=1.0))
        q.offer(_req("i-fine", np.ones(4, np.int32),
                     slo_class="interactive", deadline=100.0))
        shed = q.shed_hopeless(now=50.0, floor_s=0.0)
        # SLO_CLASSES scan order: interactive shed reported before batch
        assert [r.rid for r in shed] == ["i-doomed", "b-doomed"]
        assert {r.slo_class for r in shed} == {"interactive", "batch"}
        assert q.pop().rid == "i-fine"


# -- units: wire v2 handshake matrix ---------------------------------------


BUILDER = "rocket_tpu.testing.workers.build_tiny_loop"


class TestWireV2:
    def test_protocol_version_bumped(self):
        # at least the v2 tenant-fields bump; later protocol revisions
        # (v3 trace contexts) only raise it further
        assert wire.PROTOCOL_VERSION >= 2

    def test_old_supervisor_new_worker_refused(self):
        # a v1 supervisor's HELLO against this build's worker-side check
        with pytest.raises(wire.ProtocolMismatch) as ei:
            wire.check_hello({"proto": 1,
                              "spec": wire.WorkerSpec(builder=BUILDER)})
        assert ei.value.theirs == 1 and ei.value.side == "worker"
        assert "Remedy" in str(ei.value)

    def test_old_worker_new_supervisor_refused(self):
        # a v1 worker's READY against this build's supervisor-side check
        with pytest.raises(wire.ProtocolMismatch) as ei:
            wire.check_ready({"proto": 1, "pid": 1})
        assert ei.value.theirs == 1 and ei.value.side == "supervisor"

    def test_matched_versions_pass_both_directions(self):
        spec = wire.WorkerSpec(builder=BUILDER)
        assert wire.check_hello(wire.hello_payload(spec)) is spec
        info = wire.check_ready({"proto": wire.PROTOCOL_VERSION, "pid": 7})
        assert info["pid"] == 7

    def test_submit_frame_carries_tenant_and_class(self):
        clk = FakeClock(100.0)
        req = _req("r1", np.arange(1, 5, dtype=np.int32), tenant="acme",
                   slo_class="interactive", deadline=106.0)
        frame = wire.pack_request(req, clock=clk)
        assert frame["tenant"] == "acme"
        assert frame["slo_class"] == "interactive"
        clk.tick(2.0)
        back = wire.unpack_request(frame, clock=clk)
        assert back.tenant == "acme" and back.slo_class == "interactive"
        assert back.deadline == pytest.approx(108.0)  # remaining held

    def test_v1_frame_unpacks_to_standard(self):
        # a frame missing the v2 keys (what a v1 peer would send) must
        # not crash the unpack — it lands in the standard class
        clk = FakeClock()
        frame = wire.pack_request(_req("r1", np.ones(4, np.int32)),
                                  clock=clk)
        frame.pop("tenant")
        frame.pop("slo_class")
        back = wire.unpack_request(frame, clock=clk)
        assert back.tenant is None and back.slo_class == "standard"


# -- preemption: exactly-once, bit-equal -----------------------------------


class TestBatchPreemption:
    def _flood_then_urgent(self, models, prompts, *, kvstore=None):
        """One batch request decoding in a full loop, then interactive
        arrivals force its preemption; returns (loop, results)."""
        loop = ServingLoop(_factory(models), max_batch=2,
                           queue_capacity=8, kvstore=kvstore)
        batch_req = _req("bat", prompts[0], slo_class="batch",
                         tenant="bulk")
        std_req = _req("std", prompts[1])
        assert loop.submit(batch_req) is None
        assert loop.submit(std_req) is None
        loop.run_round()            # both admitted, one decode round
        assert loop.counters.preempted == 0
        for i in (2, 3):
            assert loop.submit(_req(f"int{i}", prompts[i],
                                    slo_class="interactive")) is None
        loop.run_round()            # urgent 2 > free 0: batch evicted
        assert loop.counters.preempted == 1
        assert len(loop.parked) == 1
        assert loop.parked[0].req.rid == "bat"
        assert loop.parked[0].produced >= 1   # it really decoded first
        results = loop.run_until_idle()
        loop.close()
        return loop, results

    def test_preempted_resumes_exactly_once_bit_equal(self, models,
                                                      prompts):
        loop, results = self._flood_then_urgent(models, prompts)
        assert sorted(r.rid for r in results) == ["bat", "int2", "int3",
                                                  "std"]
        assert all(isinstance(r, Completed) for r in results)
        (bat,) = [r for r in results if r.rid == "bat"]
        assert np.array_equal(bat.tokens, _oracle(models, prompts[0]))
        assert loop.counters.preempted == 1
        assert loop.counters.resumed == 1
        assert loop.counters.class_counts["batch"]["preempted"] == 1
        assert loop.counters.class_counts["batch"]["resumed"] == 1
        # the others were never preempted, and are bit-equal too
        for r in results:
            if r.rid != "bat":
                i = {"std": 1, "int2": 2, "int3": 3}[r.rid]
                assert np.array_equal(r.tokens, _oracle(models, prompts[i]))

    def test_preemption_with_prefix_cache_bit_equal(self, models,
                                                    prompts):
        store = PrefixKVStore(page_tokens=4)
        loop, results = self._flood_then_urgent(models, prompts,
                                                kvstore=store)
        (bat,) = [r for r in results if r.rid == "bat"]
        assert np.array_equal(bat.tokens, _oracle(models, prompts[0]))
        # the preempt exported pages; the resume imported a cached prefix
        assert loop.counters.kv_hits >= 1

    def test_no_preemption_without_urgent_pressure(self, models, prompts):
        loop = ServingLoop(_factory(models), max_batch=2,
                           queue_capacity=8)
        for i, rid in enumerate(("b0", "b1")):
            assert loop.submit(_req(rid, prompts[i],
                                    slo_class="batch")) is None
        loop.run_round()
        # more batch queued is NOT urgency — batch never preempts batch
        assert loop.submit(_req("b2", prompts[2],
                                slo_class="batch")) is None
        loop.run_round()
        assert loop.counters.preempted == 0
        results = loop.run_until_idle()
        loop.close()
        assert sorted(r.rid for r in results) == ["b0", "b1", "b2"]

    def test_resumed_respects_max_new_tokens(self, models, prompts):
        loop = ServingLoop(_factory(models), max_batch=2,
                           queue_capacity=8)
        assert loop.submit(_req("bat", prompts[0], slo_class="batch",
                                max_new_tokens=9)) is None
        assert loop.submit(_req("std", prompts[1])) is None
        loop.run_round()
        for i in (2, 3):
            assert loop.submit(_req(f"i{i}", prompts[i],
                                    slo_class="interactive")) is None
        loop.run_round()
        assert loop.counters.preempted == 1
        results = loop.run_until_idle()
        loop.close()
        (bat,) = [r for r in results if r.rid == "bat"]
        assert isinstance(bat, Completed)
        # preempted + resumed stops at the SAME count as uninterrupted
        # (tokens is the fixed-length buffer row; n_tok marks the end)
        oracle = _oracle(models, prompts[0], max_new=9)
        assert bat.n_tok == oracle.shape[0] == P + 9
        assert np.array_equal(bat.tokens[:bat.n_tok], oracle)

    def test_parked_deadline_expiry_ships_partial_once(self, models,
                                                       prompts):
        from rocket_tpu.serve import DeadlineExceeded

        clk = FakeClock()
        loop = ServingLoop(_factory(models), max_batch=2,
                           queue_capacity=8, clock=clk)
        assert loop.submit(_req("bat", prompts[0], slo_class="batch",
                                deadline=1e4)) is None
        assert loop.submit(_req("std", prompts[1])) is None
        loop.run_round()
        for i in (2, 3):
            assert loop.submit(_req(f"i{i}", prompts[i],
                                    slo_class="interactive")) is None
        loop.run_round()
        assert len(loop.parked) == 1
        clk.tick(2e4)               # the parked ticket's deadline passes
        results = loop.run_until_idle()
        loop.close()
        (bat,) = [r for r in results if r.rid == "bat"]
        assert isinstance(bat, DeadlineExceeded)
        assert bat.stage == "decode"
        assert bat.tokens is not None and bat.n_tok > P  # partial rides
        assert sum(1 for r in results if r.rid == "bat") == 1

    def test_salvage_returns_parked_original(self, models, prompts):
        loop = ServingLoop(_factory(models), max_batch=2,
                           queue_capacity=8)
        req = _req("bat", prompts[0], slo_class="batch")
        assert loop.submit(req) is None
        assert loop.submit(_req("std", prompts[1])) is None
        loop.run_round()
        for i in (2, 3):
            assert loop.submit(_req(f"i{i}", prompts[i],
                                    slo_class="interactive")) is None
        loop.run_round()
        assert len(loop.parked) == 1
        salvaged = loop.salvage()
        loop.close()
        # the ORIGINAL request object comes back — a healthy replica
        # re-serves it from scratch, bit-equal by determinism
        assert req in salvaged
        assert loop.parked == []


# -- per-class policy feeds -------------------------------------------------


class TestUrgentPolicyFeed:
    def test_batch_backlog_never_degrades(self, models, prompts):
        loop = ServingLoop(_factory(models), max_batch=1,
                           queue_capacity=8)
        for i in range(7):
            assert loop.submit(_req(f"b{i}", prompts[i % 8],
                                    slo_class="batch")) is None
        loop.run_round()
        # deep batch backlog, zero urgent depth: full quality holds
        assert loop.queue.depth_frac >= 0.5
        assert loop.policy.level == 0
        loop.run_until_idle()
        loop.close()

    def test_standard_backlog_still_degrades(self, models, prompts):
        loop = ServingLoop(_factory(models), max_batch=1,
                           queue_capacity=8)
        for i in range(7):
            assert loop.submit(_req(f"s{i}", prompts[i % 8])) is None
        loop.run_round()
        assert loop.policy.level >= 1
        loop.run_until_idle()
        loop.close()


class TestAutoscalerClassPolicies:
    def _auto(self, **kw):
        return Autoscaler(router=None, spawn_fn=lambda rid: None,
                          policy=SLOPolicy(ttft_p95_ms=1e9),
                          collect_fn=dict, **kw)

    def test_interactive_breach_trips(self):
        auto = self._auto(class_policies={
            "interactive": SLOPolicy(ttft_p95_ms=500.0)})
        assert auto._breached({"serve_slo/interactive/ttft_ms/p95": 900.0})
        assert auto.counters.breach_class_ttft == 1
        assert "breach_class_ttft" in auto.counters.snapshot()

    def test_batch_breach_never_scales_up(self):
        auto = self._auto(class_policies={
            "batch": SLOPolicy(ttft_p95_ms=1.0)})
        assert not auto._breached({"serve_slo/batch/ttft_ms/p95": 1e6})
        assert auto.counters.breach_class_ttft == 0


# -- per-class observability ------------------------------------------------


class TestClassCounters:
    def test_snapshot_flattens_class_events(self):
        c = ServeCounters()
        c.observe_class("interactive", "submitted")
        c.observe_class("batch", "preempted")
        c.observe_class("batch", "resumed", 2)
        snap = c.snapshot()
        assert snap["class/interactive/submitted"] == 1.0
        assert snap["class/batch/preempted"] == 1.0
        assert snap["class/batch/resumed"] == 2.0

    def test_unknown_class_lands_in_standard(self):
        c = ServeCounters()
        c.observe_class("mystery", "shed")
        assert c.class_counts["standard"]["shed"] == 1

    def test_loop_records_per_class(self, models, prompts):
        loop = ServingLoop(_factory(models), max_batch=B,
                           queue_capacity=8)
        assert loop.submit(_req("i0", prompts[0],
                                slo_class="interactive")) is None
        loop.run_until_idle()
        loop.close()
        assert loop.counters.class_counts["interactive"]["submitted"] == 1
        assert loop.counters.class_counts["interactive"]["completed"] == 1
        assert loop.slo_latency.ttft_ms["interactive"].count == 1
        assert loop.slo_latency.e2e_ms["interactive"].count == 1


class TestClassLatencyMerge:
    def test_attainment_recomputed_over_merged_window(self):
        # replica A: 2 good interactive samples; replica B: 8 bad ones.
        # Merge rule: recompute over the union -> 0.2, NEVER the 0.5 an
        # average of per-replica fractions would report.
        a, b = ClassLatency(), ClassLatency()
        for _ in range(2):
            a.record_ttft("interactive", 100.0)
        for _ in range(8):
            b.record_ttft("interactive", 5000.0)
        assert a.attainment()["interactive"] == 1.0
        assert b.attainment()["interactive"] == 0.0
        a.merge(b)
        assert a.attainment()["interactive"] == pytest.approx(0.2)

    def test_empty_class_exports_nothing(self):
        lat = ClassLatency()
        lat.record_ttft("interactive", 10.0)
        att = lat.attainment()
        assert "batch" not in att and "standard" not in att

    def test_summary_keys_per_class(self):
        lat = ClassLatency()
        lat.record_ttft("batch", 50.0)
        lat.record_e2e("batch", 80.0)
        s = lat.summary()
        assert s["batch/ttft_ms/p50"] == 50.0
        assert s["batch/e2e_ms/p95"] == 80.0


class TestSLOExportSource:
    def test_register_and_collect(self):
        from rocket_tpu.observe import export

        class Provider:
            def __init__(self):
                self.slo_latency = ClassLatency()
                self.counters = ServeCounters()

        prov = Provider()
        prov.slo_latency.record_ttft("interactive", 100.0)
        prov.counters.observe_class("interactive", "completed")
        try:
            from rocket_tpu.serve import register_slo_source

            register_slo_source(prov, name="serve_slo_test")
            out = export.collect()
            assert out["serve_slo_test/interactive/ttft_attainment"] == 1.0
            assert out["serve_slo_test/interactive/ttft_ms/p95"] == 100.0
            assert out["serve_slo_test/interactive/completed"] == 1.0
        finally:
            export.unregister_source("serve_slo_test")


# -- the harness: trace synthesis + replay ----------------------------------


_MIX = (TenantSpec("acme", "interactive", share=3.0, sessions=2,
                   deadline_s=30.0),
        TenantSpec("corp", "standard", share=2.0),
        TenantSpec("bulk", "batch", share=1.0))


class TestSynthTrace:
    def test_seeded_determinism(self):
        cfg = TraceConfig(duration_s=30.0, base_rate=3.0, burst_rate=4.0)
        t1 = synth_trace(_MIX, cfg, seed=11)
        t2 = synth_trace(_MIX, cfg, seed=11)
        assert len(t1) == len(t2) > 0
        for a, b in zip(t1, t2):
            assert a.t == b.t and a.rid == b.rid
            assert np.array_equal(a.prompt, b.prompt)
        t3 = synth_trace(_MIX, cfg, seed=12)
        assert [e.rid for e in t3] != [e.rid for e in t1]

    def test_arrivals_sorted_and_bounded(self):
        cfg = TraceConfig(duration_s=20.0, base_rate=5.0)
        tr = synth_trace(_MIX, cfg, seed=0)
        ts = [e.t for e in tr]
        assert ts == sorted(ts)
        assert all(0.0 <= t < 20.0 for t in ts)

    def test_diurnal_tide_shapes_arrivals(self):
        # amp 0.9, period == duration: the first half (sin > 0) must
        # carry visibly more arrivals than the second half
        cfg = TraceConfig(duration_s=60.0, base_rate=5.0,
                          diurnal_amp=0.9, diurnal_period_s=60.0)
        tr = synth_trace([TenantSpec("t")], cfg, seed=4)
        first = sum(1 for e in tr if e.t < 30.0)
        second = len(tr) - first
        assert first > second * 1.5

    def test_sessions_share_prefix(self):
        cfg = TraceConfig(duration_s=30.0, base_rate=4.0,
                          shared_prefix_len=6)
        tr = synth_trace([TenantSpec("a", sessions=1)], cfg, seed=2)
        turns = [e for e in tr if e.session is not None]
        assert len(turns) >= 2
        sid = turns[0].session
        prefix = turns[0].prompt[:6]
        for e in turns:
            assert e.session == sid
            assert np.array_equal(e.prompt[:6], prefix)

    def test_tenant_mix_and_classes(self):
        cfg = TraceConfig(duration_s=60.0, base_rate=5.0)
        tr = synth_trace(_MIX, cfg, seed=9)
        by = {t.name: sum(1 for e in tr if e.tenant == t.name)
              for t in _MIX}
        assert by["acme"] > by["bulk"]          # 3x the share
        assert {e.slo_class for e in tr if e.tenant == "bulk"} \
            == {"batch"}
        # relative deadlines ride the event, not the wall clock
        assert all(e.deadline_s == 30.0 for e in tr
                   if e.tenant == "acme")

    def test_heavy_tail_prompt_lengths(self):
        cfg = TraceConfig(duration_s=120.0, base_rate=5.0,
                          prompt_len_min=4, prompt_len_max=16,
                          prompt_tail_alpha=1.5)
        tr = synth_trace([TenantSpec("t")], cfg, seed=3)
        lens = [int(e.prompt.shape[0]) for e in tr]
        assert min(lens) >= 4 and max(lens) <= 16
        assert len(set(lens)) > 3               # a real spread, not flat

    def test_empty_mix_refused(self):
        with pytest.raises(ValueError, match="TenantSpec"):
            synth_trace([], TraceConfig())


class TestReplay:
    def test_replay_reports_per_class_exactly_once(self, models):
        loop = ServingLoop(_factory(models), max_batch=B,
                           queue_capacity=32)
        cfg = TraceConfig(duration_s=8.0, base_rate=2.0,
                          prompt_len_min=4, prompt_len_max=10,
                          max_new_max=4)
        tr = synth_trace(_MIX, cfg, seed=21)
        rep = replay_trace(tr, loop, speed=400.0)
        loop.close()
        assert rep.submitted == len(tr)
        assert rep.completed + sum(
            st["shed"] for st in rep.per_class.values()) == len(tr)
        assert rep.goodput_per_chip > 0.0
        for cls, st in rep.per_class.items():
            assert st["submitted"] >= st["completed"]
            assert cls in SLO_CLASSES

    def test_replay_asserts_on_duplicate_result(self):
        class EchoTwice:
            def __init__(self):
                self._out = []

            def submit(self, req):
                self._out.extend([
                    Completed(req.rid, 0.0, tokens=req.prompt,
                              n_tok=4, meta={}),
                    Completed(req.rid, 0.0, tokens=req.prompt,
                              n_tok=4, meta={}),
                ])
                return None

            def run_round(self):
                return False

            def drain_results(self):
                out, self._out = self._out, []
                return out

        tr = synth_trace([TenantSpec("t")],
                         TraceConfig(duration_s=2.0, base_rate=2.0),
                         seed=1)
        with pytest.raises(AssertionError, match="exactly-once"):
            replay_trace(tr, EchoTwice(), speed=1e4)


# -- chaos: flood injector + skew knob --------------------------------------


class TestBatchFlood:
    class _Sink:
        def __init__(self, refuse_after=None):
            self.reqs = []
            self._refuse_after = refuse_after

        def submit(self, req):
            if self._refuse_after is not None \
                    and len(self.reqs) >= self._refuse_after:
                return Overloaded(req.rid, 0.0, reason="queue full",
                                  meta={})
            self.reqs.append(req)
            return None

    def test_flood_is_batch_class_and_deterministic(self):
        a, b = self._Sink(), self._Sink()
        for sink in (a, b):
            inj = BatchFloodInjector(sink, per_tick=2, prompt_len=6)
            for _ in range(3):
                inj.tick()
            assert inj.submitted == 6 and inj.rejected == 0
        assert [r.rid for r in a.reqs] == [r.rid for r in b.reqs]
        for ra, rb in zip(a.reqs, b.reqs):
            assert ra.slo_class == "batch" and ra.tenant == "flood"
            assert np.array_equal(ra.prompt, rb.prompt)

    def test_flood_schedule_respected(self):
        sink = self._Sink()
        inj = BatchFloodInjector(sink, per_tick=3, flood_on=(1,))
        assert inj.tick() == 0
        assert inj.tick() == 3
        assert inj.tick() == 0
        assert inj.submitted == 3

    def test_rejections_counted_not_raised(self):
        sink = self._Sink(refuse_after=2)
        inj = BatchFloodInjector(sink, per_tick=4)
        assert inj.tick() == 2
        assert inj.submitted == 2 and inj.rejected == 2


class TestTenantSkewKnob:
    def test_plain_list_without_knob(self):
        arr = bursty_arrivals(4, burst=2, gap_s=1.0)
        assert arr == [0.0, 0.0, 1.0, 1.0]

    def test_skew_labels_deterministic_9_to_1(self):
        out = bursty_arrivals(20, burst=5, gap_s=1.0,
                              tenants=[("heavy", 9.0), ("light", 1.0)])
        labels = [name for _, name in out]
        assert labels.count("heavy") == 18 and labels.count("light") == 2
        # offsets unchanged vs the knobless call
        assert [t for t, _ in out] == bursty_arrivals(20, burst=5,
                                                      gap_s=1.0)
        # deterministic: same call, same labels
        assert out == bursty_arrivals(20, burst=5, gap_s=1.0,
                                      tenants=[("heavy", 9.0),
                                               ("light", 1.0)])

    def test_bad_shares_refused(self):
        with pytest.raises(ValueError, match="positive shares"):
            bursty_arrivals(4, burst=2, gap_s=1.0, tenants=[("t", 0.0)])
