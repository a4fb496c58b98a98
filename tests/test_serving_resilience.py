"""Serving-robustness tests — rocket_tpu.serve end to end.

Three layers, mirroring the package:

- units: AdmissionQueue, DegradationPolicy, DispatchWatchdog, the typed
  Request/Result vocabulary, the new chaos injectors, retry deadlines,
  and the ContinuousBatcher admit/start validation;
- the fault-free contract: a ServingLoop with no faults, no deadlines,
  and an uncontended queue produces tokens BIT-IDENTICAL to the solo
  one-dispatch oracle for every request, adds no traced step bodies
  (``_spec_round`` jit cache is unchanged), and costs <5% per-round
  host overhead over the bare batcher;
- the chaos trio: bursty overload (every request typed, bounded
  deadline overrun), a wedged device step (watchdog trips, in-flight
  rows fail cleanly with partials, the rebuilt batcher serves the next
  request correctly), and the degradation ladder (engages under queue
  pressure, restores full quality once the queue drains).
"""

import time

import numpy as np
import pytest

import jax

from rocket_tpu.models.generate import (
    ContinuousBatcher,
    _spec_round,
    speculative_generate_batched,
)
from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
from rocket_tpu.serve import (
    AdmissionQueue,
    Completed,
    DeadlineExceeded,
    DegradationLevel,
    DegradationPolicy,
    DispatchWatchdog,
    Failed,
    HealthState,
    Overloaded,
    Request,
    ServingLoop,
)
from rocket_tpu.testing.chaos import (
    FaultySource,
    SlowSource,
    StuckStepInjector,
    bursty_arrivals,
)
from rocket_tpu.utils.retry import retry_call

pytestmark = pytest.mark.serving

B, P, TOTAL, NDRAFT = 3, 8, 24, 4


class FakeClock:
    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float = 1.0) -> None:
        self.t += dt


def _lm(seed=1, **kw):
    cfg = TransformerConfig(
        vocab_size=64, hidden=32, n_layers=2, n_heads=4, max_seq=64, **kw
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
    draft, _ = _lm(seed=1)      # same structure...
    _, dparams = _lm(seed=7)    # ...different weights: low acceptance
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


def _oracle(models, prompt_row):
    model, draft, params, dparams = models
    toks = speculative_generate_batched(
        model, params, draft, dparams, prompt_row[None, :],
        max_new_tokens=TOTAL - P, n_draft=NDRAFT,
    )
    return np.asarray(toks[0])


# -- units: queue --------------------------------------------------------


class TestAdmissionQueue:
    def test_capacity_validated(self):
        with pytest.raises(ValueError, match="capacity"):
            AdmissionQueue(0)

    def test_offer_pop_fifo_and_full(self):
        q = AdmissionQueue(2)
        r1 = Request(rid=1, prompt=np.ones(4, np.int32))
        r2 = Request(rid=2, prompt=np.ones(4, np.int32))
        r3 = Request(rid=3, prompt=np.ones(4, np.int32))
        assert q.offer(r1) and q.offer(r2)
        assert not q.offer(r3)          # full: typed shed, not growth
        assert q.depth_frac == 1.0
        assert q.pop() is r1 and q.pop() is r2 and q.pop() is None

    def test_shed_hopeless_keeps_order_and_deadlineless(self):
        q = AdmissionQueue(4)
        doomed = Request(rid=1, prompt=np.ones(4, np.int32), deadline=5.0)
        fine = Request(rid=2, prompt=np.ones(4, np.int32), deadline=100.0)
        forever = Request(rid=3, prompt=np.ones(4, np.int32))
        for r in (doomed, fine, forever):
            q.offer(r)
        shed = q.shed_hopeless(now=4.5, floor_s=1.0)
        assert [r.rid for r in shed] == [1]
        assert [q.pop().rid for _ in range(2)] == [2, 3]


# -- units: degradation policy -------------------------------------------


class TestDegradationPolicy:
    def test_validation(self):
        with pytest.raises(ValueError, match="at least one level"):
            DegradationPolicy(ladder=())
        with pytest.raises(ValueError, match="one threshold per level"):
            DegradationPolicy(engage_depth=(0.5,))
        with pytest.raises(ValueError, match="ascending"):
            DegradationPolicy(engage_depth=(0.9, 0.5))
        with pytest.raises(ValueError, match="recover_rounds"):
            DegradationPolicy(recover_rounds=0)

    def test_depth_escalation_immediate(self):
        p = DegradationPolicy(engage_depth=(0.5, 0.875))
        assert p.update(0.2) == 0
        assert p.update(0.6) == 1          # one signal: instant
        assert p.update(0.9) == 2
        assert p.current.name == "survival"

    def test_latency_escalation(self):
        p = DegradationPolicy(round_ms_budget=100.0)
        assert p.update(0.0, round_ms=50.0) == 0
        assert p.update(0.0, round_ms=150.0) == 1
        assert p.update(0.0, round_ms=900.0) == 2  # clamped to top rung

    def test_hysteresis_recovery_one_level_at_a_time(self):
        p = DegradationPolicy(recover_rounds=3)
        p.update(0.95)
        assert p.level == 2
        for _ in range(2):
            assert p.update(0.0) == 2      # calm, but not calm enough
        assert p.update(0.0) == 1          # 3rd calm round: ONE level down
        assert p.update(0.6) == 1          # target==level resets the streak
        for _ in range(3):
            p.update(0.0)
        assert p.level == 0

    def test_n_draft_floor(self):
        p = DegradationPolicy()
        p.update(0.95)
        assert p.n_draft(4) == 1           # 4 * 0.25, floored at >= 1
        assert p.n_draft(2) == 1


# -- units: watchdog ------------------------------------------------------


class TestDispatchWatchdog:
    def test_timeout_validated(self):
        with pytest.raises(ValueError, match="timeout"):
            DispatchWatchdog(0.0)

    def test_none_runs_inline(self):
        wd = DispatchWatchdog(None)
        assert wd.run(lambda: 7) == (True, 7)
        assert wd._worker is None          # no thread was ever spawned

    def test_success_and_exception_reraise(self):
        wd = DispatchWatchdog(5.0)
        try:
            assert wd.run(lambda: "ok") == (True, "ok")
            with pytest.raises(RuntimeError, match="boom"):
                wd.run(lambda: (_ for _ in ()).throw(RuntimeError("boom")))
        finally:
            wd.close()

    def test_trip_then_recover_on_fresh_worker(self):
        wd = DispatchWatchdog(0.15)
        try:
            ok, value = wd.run(lambda: time.sleep(2.0))
            assert (ok, value) == (False, None)
            assert wd.trips == 1
            # the zombie still holds the old worker; a new one serves this
            assert wd.run(lambda: 42) == (True, 42)
        finally:
            wd.close()


# -- units: typed requests ------------------------------------------------


class TestRequestValidation:
    def test_prompt_normalized_to_1d(self):
        r = Request(rid=0, prompt=np.ones((1, 4), np.int32))
        assert r.prompt.shape == (4,) and r.prompt.dtype == np.int32

    def test_bad_prompts_rejected(self):
        with pytest.raises(ValueError, match="non-empty 1-D"):
            Request(rid=0, prompt=np.ones((2, 4), np.int32))
        with pytest.raises(ValueError, match="non-empty 1-D"):
            Request(rid=0, prompt=np.zeros((0,), np.int32))

    def test_bad_max_new_rejected(self):
        with pytest.raises(ValueError, match="max_new_tokens"):
            Request(rid=0, prompt=np.ones(4, np.int32), max_new_tokens=0)


# -- units: chaos injectors ----------------------------------------------


class TestChaosInjectors:
    def test_slow_source_delays_without_failing(self):
        naps = []
        src = SlowSource(
            list(range(5)), slow_on=(1, 3), delay_s=0.25, sleep=naps.append
        )
        assert [src[i] for i in range(5)] == list(range(5))
        assert src.stalls == 2 and naps == [0.25, 0.25]

    def test_bursty_arrivals_shape(self):
        arr = bursty_arrivals(7, burst=3, gap_s=2.0, spread_s=0.3,
                              start_s=1.0)
        assert len(arr) == 7 and arr == sorted(arr)
        assert arr[0] == 1.0 and arr[3] == 3.0 and arr[6] == 5.0
        with pytest.raises(ValueError):
            bursty_arrivals(0, 1, 1.0)

    def test_stuck_injector_delegates_and_wedges(self):
        class Inner:
            def __init__(self):
                self.n_draft = 4
                self.stepped = 0

            def step(self):
                self.stepped += 1
                return self.stepped

        naps = []
        inner = Inner()
        proxy = StuckStepInjector(inner, hang_on=(1,), hang_s=3.0,
                                  sleep=naps.append)
        assert proxy.n_draft == 4          # attribute reads delegate
        proxy.n_draft = 2                  # ...and writes land on the inner
        assert inner.n_draft == 2
        assert proxy.step() == 1 and naps == []
        assert proxy.step() == 2 and naps == [3.0]   # scheduled wedge
        assert proxy.steps == 2 and proxy.hangs == 1


# -- units: retry deadlines ----------------------------------------------


class TestRetryDeadline:
    def test_deadline_exhausted_raises_without_sleeping(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            raise OSError("transient")

        clock = FakeClock(10.0)
        scheduled = []
        # deadline == now: every backoff would finish at/past it, so the
        # FIRST failure surfaces — tries and budget still had room
        with pytest.raises(OSError, match="transient"):
            retry_call(flaky, tries=10, base_delay=0.2, budget=30.0,
                       deadline=10.0, clock=clock,
                       on_retry=lambda *a: scheduled.append(a))
        assert calls["n"] == 1
        # no backoff was slept: `on_retry` fires for every retry that
        # will sleep, and none was scheduled (was "returned in < 0.15 s")
        assert scheduled == []

    def test_generous_deadline_still_retries(self):
        calls = {"n": 0}

        def flaky_then_ok():
            calls["n"] += 1
            if calls["n"] < 3:
                raise OSError("transient")
            return "done"

        assert retry_call(flaky_then_ok, tries=5, base_delay=0.001,
                          deadline=time.monotonic() + 60.0) == "done"
        assert calls["n"] == 3

    def test_no_deadline_unchanged(self):
        with pytest.raises(OSError):
            retry_call(lambda: (_ for _ in ()).throw(OSError("x")),
                       tries=2, base_delay=0.001)


# -- units: batcher admit/start validation --------------------------------


class TestBatcherValidation:
    def test_paths(self, models, prompts):
        factory = _factory(models)
        bat = factory()
        with pytest.raises(ValueError, match="non-empty \\[B, P\\]"):
            bat.start(np.ones(P, np.int32))            # 1-D
        with pytest.raises(ValueError, match="integer token ids"):
            bat.start(np.ones((2, P), np.float32))     # float ids
        with pytest.raises(ValueError, match="exceeds total_len"):
            bat.start(np.ones((2, TOTAL), np.int32))   # no room to generate
        with pytest.raises(ValueError, match="call start"):
            bat.admit(0, prompts[0])

        bat.start(prompts[:B])
        bat.step()
        with pytest.raises(ValueError, match="out of range"):
            bat.admit(B + 2, prompts[0])   # silent .at[row] drop otherwise
        with pytest.raises(ValueError, match="still decoding"):
            bat.admit(0, prompts[3])       # live row needs explicit preempt
        with pytest.raises(ValueError, match="out of range"):
            bat.retire(B + 2)
        with pytest.raises(ValueError, match="single non-empty prompt row"):
            bat.admit(0, prompts[:2], preempt=True)    # [2, P] is 2 rows

        bat.admit(0, prompts[3], preempt=True)         # explicit: allowed
        bat.retire(1)
        bat.admit(1, prompts[4])                       # done row: allowed


# -- sentinel scalar emission ---------------------------------------------


class TestSentinelScalars:
    def test_skip_and_event_counters_emitted_on_change(self):
        from rocket_tpu.core.attributes import Attributes
        from rocket_tpu.engine.sentinel import DivergenceSentinel

        s = DivergenceSentinel(policy="warn", spike_factor=None)
        s._runtime = object()
        tracker = Attributes(scalars=[], images=[])
        losses = [1.0, float("nan"), 1.0, 1.0, 1.0]
        skips = [0.0, 1.0, 0.0, 0.0, 0.0]
        for loss, sk in zip(losses, skips):
            s.launch(Attributes(
                step_logs={"loss": loss, "skipped": sk},
                looper=Attributes(grad_enabled=True),
                tracker=tracker,
            ))
        assert s.events == 1 and s.skips == 1 and s.rollbacks == 0
        # emit-on-change: ONE record despite five launches
        assert len(tracker.scalars) == 1
        rec = tracker.scalars[0]
        assert rec.data["sentinel/skips"] == 1.0
        assert rec.data["sentinel/events"] == 1.0
        assert rec.data["sentinel/rollbacks"] == 0.0

    def test_no_tracker_no_crash(self):
        from rocket_tpu.core.attributes import Attributes
        from rocket_tpu.engine.sentinel import DivergenceSentinel

        s = DivergenceSentinel(policy="warn", spike_factor=None)
        s._runtime = object()
        s.launch(Attributes(step_logs={"loss": float("nan")},
                            looper=Attributes(grad_enabled=True)))
        s.launch(Attributes(step_logs={"loss": float("nan")},
                            looper=Attributes(grad_enabled=True)))
        assert s.events >= 1


# -- fault-free contract --------------------------------------------------


class TestFaultFree:
    def test_bit_equality_and_no_new_traces(self, models, prompts):
        # bare run first: compiles (and pins) every executable the
        # wrapped loop should reuse
        bare = _factory(models)()
        bare.start(prompts[:B])
        while not bare.all_done:
            bare.step()
        bare_rows = [bare.row_tokens(r)[0] for r in range(B)]
        for r in range(B):
            assert np.array_equal(bare_rows[r], _oracle(models, prompts[r]))

        traces_before = _spec_round._cache_size()
        loop = ServingLoop(_factory(models), max_batch=B, queue_capacity=8)
        for i in range(5):
            assert loop.submit(Request(rid=i, prompt=prompts[i])) is None
        results = loop.run_until_idle()
        loop.close()

        assert len(results) == 5
        assert all(isinstance(r, Completed) for r in results)
        for r in results:
            assert np.array_equal(r.tokens, _oracle(models, prompts[r.rid]))
        # the robustness wrapper added ZERO traced step bodies
        assert _spec_round._cache_size() == traces_before
        assert loop.health is HealthState.SERVING
        snap = loop.counters.snapshot()
        assert snap["completed"] == 5 and snap["failed"] == 0
        assert snap["watchdog_trips"] == 0 and snap["degrade_peak"] == 0

    def test_watchdog_adds_no_host_read_and_no_dispatch(self, models,
                                                        prompts):
        # Was "wrapped round <= 1.05 x a bare batcher's" on the host's
        # clock.  What the robustness wrapper could add to a fault-free
        # round is a blocking device read or a dispatch; `HostReads`
        # counts the first into `ServeCounters.host_fetches` and the
        # `serve/dispatch` span is the second.  Armed (thread hop
        # included) and unarmed, a round makes the same number of each,
        # and one dispatch a round is what the bare batcher makes.
        from rocket_tpu.observe.trace import Tracer

        rounds = 8

        def counts(watchdog_timeout):
            tracer = Tracer(capacity=1024, enabled=True)
            loop = ServingLoop(_factory(models), max_batch=B,
                               queue_capacity=8, tracer=tracer,
                               watchdog_timeout=watchdog_timeout)
            for i in range(B):
                loop.submit(Request(rid=i, prompt=prompts[i]))
            loop.run_round()  # admits + settles

            def dispatched():
                return sum(1 for e in tracer.events()
                           if e[1] == "serve/dispatch")

            fetches, dispatches = loop.counters.host_fetches, dispatched()
            for _ in range(rounds):
                loop.run_round()
            out = (loop.counters.host_fetches - fetches,
                   dispatched() - dispatches)
            assert loop.counters.watchdog_trips == 0
            loop.close()
            return out

        bare = counts(None)
        assert counts(30.0) == bare
        assert bare[1] == rounds

    def test_results_are_typed_exactly_once(self, models, prompts):
        loop = ServingLoop(_factory(models), max_batch=B, queue_capacity=2)
        outcomes = [loop.submit(Request(rid=i, prompt=prompts[i % 8]))
                    for i in range(6)]
        rejected = [o for o in outcomes if o is not None]
        assert rejected and all(isinstance(o, Overloaded) for o in rejected)
        results = loop.run_until_idle()
        loop.close()
        assert sorted(r.rid for r in results) == list(range(6))


# -- chaos trio -----------------------------------------------------------


class TestChaosTrio:
    def test_bursty_overload_every_request_typed(self, models, prompts):
        """(a) burst past capacity: every submitted request resolves to
        exactly one typed result, and nothing overruns its deadline by
        more than one decode round (here: one fake-clock tick)."""
        clock = FakeClock()
        tick = 1.0
        loop = ServingLoop(_factory(models), max_batch=B,
                           queue_capacity=4, clock=clock)
        offsets = bursty_arrivals(12, burst=6, gap_s=4 * tick)
        deadlines = {i: (clock.t + offsets[i] + 3 * tick
                         if i % 3 == 0 else None)
                     for i in range(12)}
        submitted = 0
        results = []
        for _ in range(400):
            while submitted < 12 and offsets[submitted] <= clock.t:
                loop.submit(Request(
                    rid=submitted,
                    prompt=prompts[submitted % 8],
                    deadline=deadlines[submitted],
                ))
                submitted += 1
            loop.run_round()
            results.extend(loop.drain_results())
            clock.tick(tick)
            if submitted == 12 and len(results) == 12:
                break
        loop.close()

        assert sorted(r.rid for r in results) == list(range(12))
        by_type = {}
        for r in results:
            by_type.setdefault(type(r).__name__, []).append(r)
        # the burst of 6 into 3 rows + 4 queue slots must shed typed
        assert by_type.get("Overloaded"), by_type.keys()
        for r in results:
            if isinstance(r, DeadlineExceeded):
                dl = deadlines[r.rid]
                assert dl is not None
                assert r.finished_at - dl <= tick + 1e-9, (
                    f"rid {r.rid} overran its deadline by "
                    f"{r.finished_at - dl:.3f}s (> one round tick)"
                )
                if r.stage == "decode":
                    assert r.n_tok > P   # eviction kept the partials
        completed = by_type.get("Completed", [])
        for r in completed:
            assert np.array_equal(
                r.tokens, _oracle(models, prompts[r.rid % 8])
            )

    def test_stuck_step_trips_watchdog_and_recovers(self, models, prompts):
        """(b) a wedged device dispatch: the watchdog trips, in-flight
        rows fail cleanly with last-good partials, the batcher is
        rebuilt, and the NEXT batch completes bit-correct."""
        instances = {"n": 0}
        base_factory = _factory(models)

        def factory():
            bat = base_factory()
            instances["n"] += 1
            if instances["n"] == 1:
                # proxy step #0 is the loop's inline warm step; #1 the
                # first served round; #2 wedges
                return StuckStepInjector(bat, hang_on=(2,), hang_s=8.0)
            return bat

        loop = ServingLoop(factory, max_batch=B, queue_capacity=4,
                           watchdog_timeout=0.4, recover_rounds=2)
        for i in range(2):
            loop.submit(Request(rid=i, prompt=prompts[i]))
        loop.run_round()                     # proxy step #1: fine
        assert not loop.drain_results()
        loop.run_round()                     # proxy step #2: wedged
        results = loop.drain_results()

        assert loop.watchdog.trips == 1
        assert instances["n"] == 2           # rebuilt from the factory
        assert loop.health is HealthState.DEGRADED
        assert sorted(r.rid for r in results) == [0, 1]
        for r in results:
            assert isinstance(r, Failed)
            assert "watchdog" in r.reason
            # one clean round ran first, so partials exist and start
            # with the request's own prompt
            assert r.n_tok > P
            assert np.array_equal(r.tokens[:P], prompts[r.rid])

        # the rebuilt batcher serves the next request bit-correct
        loop.submit(Request(rid=7, prompt=prompts[7]))
        results = loop.run_until_idle()
        loop.close()
        (done,) = results
        assert isinstance(done, Completed) and done.rid == 7
        assert np.array_equal(done.tokens, _oracle(models, prompts[7]))
        assert loop.health is HealthState.SERVING  # recover window elapsed

    @pytest.mark.parametrize("watchdog_timeout", [None, 30.0],
                             ids=["inline", "watched"])
    def test_step_that_raises_after_donating_its_state(
            self, models, prompts, caplog, watchdog_timeout):
        """A round that donated its state and then raised leaves
        ``bat.state`` naming deleted arrays.  The in-flight rows fail with
        their partial tokens from the loop's host copies (``_carry``), the
        batcher is rebuilt, the next request is served bit-correct, and no
        ``Array has been deleted`` reaches a result or the log."""
        instances = {"n": 0}
        base_factory = _factory(models)
        donated = []

        def fault(bat):
            before = bat.state
            bat.step()                       # dispatched: ``before`` is gone
            bat.state = before               # ...and never rebound
            donated.append(before)
            raise RuntimeError("device fault after dispatch")

        def factory():
            bat = base_factory()
            instances["n"] += 1
            if instances["n"] == 1:
                # proxy step #0 is the loop's inline warm step; #1 the
                # first served round; #2 raises where it would have hung
                return StuckStepInjector(bat, hang_on=(2,),
                                         sleep=lambda _s: fault(bat))
            return bat

        loop = ServingLoop(factory, max_batch=B, queue_capacity=4,
                           watchdog_timeout=watchdog_timeout,
                           recover_rounds=2)
        with caplog.at_level("DEBUG"):
            for i in range(2):
                loop.submit(Request(rid=i, prompt=prompts[i]))
            loop.run_round()                 # proxy step #1: fine
            assert not loop.drain_results()
            loop.run_round()                 # proxy step #2: raises
            results = loop.drain_results()

            (before,) = donated
            caches = [leaf for leaf in jax.tree_util.tree_leaves(
                (before[3], before[4])) if leaf.ndim >= 3]
            assert caches and all(leaf.is_deleted() for leaf in caches)
            assert instances["n"] == 2       # rebuilt from the factory
            assert loop.health is HealthState.DEGRADED
            assert sorted(r.rid for r in results) == [0, 1]
            for r in results:
                assert isinstance(r, Failed)
                assert "device fault after dispatch" in r.reason
                # one clean round ran first: the partials are the
                # oracle's own first tokens, read from the host's copy
                assert r.n_tok > P
                assert np.array_equal(
                    r.tokens, _oracle(models, prompts[r.rid])[:r.n_tok])

            loop.submit(Request(rid=7, prompt=prompts[7]))
            (done,) = loop.run_until_idle()
            loop.close()
        assert isinstance(done, Completed) and done.rid == 7
        assert np.array_equal(done.tokens, _oracle(models, prompts[7]))
        assert loop.health is HealthState.SERVING
        assert "has been deleted" not in caplog.text
        assert not any("has been deleted" in repr(r)
                       for r in results + [done])

    def test_degradation_ladder_engages_and_restores(self, models, prompts):
        """(c) queue pressure engages the ladder (n_draft shrinks, beam
        demotes); draining restores full quality (base n_draft, beam
        honored) — and every greedy result stays bit-equal to the
        oracle, degraded or not."""
        beam_calls = []

        def beam_fn(prompt_2d, max_new):
            beam_calls.append(int(max_new))
            row = np.asarray(prompt_2d[0])
            return np.concatenate(
                [row, np.zeros(max_new, np.int32)]
            )[None, :]

        ladder = (
            DegradationLevel("full"),
            DegradationLevel("lean", draft_frac=0.5, beam=False),
        )
        policy = DegradationPolicy(ladder=ladder, engage_depth=(0.5,),
                                   recover_rounds=2)
        loop = ServingLoop(_factory(models), max_batch=B,
                           queue_capacity=4, policy=policy,
                           beam_fn=beam_fn)
        base = loop.base_n_draft

        # fill the rows first, then pile the queue past the 0.5 threshold;
        # the beam request heads the FIFO so it is guaranteed to pop
        # while the ladder is still engaged
        for i in range(3):
            assert loop.submit(Request(rid=i, prompt=prompts[i % 8])) is None
        loop.run_round()                      # admits 3, queue empty
        loop.submit(Request(rid=90, prompt=prompts[5], beam=True))
        for i in range(3, 5):
            assert loop.submit(Request(rid=i, prompt=prompts[i % 8])) is None
        loop.run_round()                      # queue 3/4 = 0.75 -> engage
        assert loop.policy.level == 1
        assert loop.health is HealthState.DEGRADED
        assert loop._bat.n_draft == max(1, base // 2)
        peak_ndraft = loop._bat.n_draft

        results = loop.run_until_idle()
        assert loop.counters.degrade_peak == 1
        demoted = next(r for r in results if r.rid == 90)
        assert isinstance(demoted, Completed) and demoted.beam_demoted
        assert not beam_calls                 # the beam lane never ran
        assert sorted(r.rid for r in results) == [0, 1, 2, 3, 4, 90]
        for r in results:
            if r.rid != 90:
                assert np.array_equal(
                    r.tokens, _oracle(models, prompts[r.rid % 8])
                ), f"rid {r.rid} diverged while degraded"
        # greedy speculative decoding is n_draft-invariant: the demoted
        # request's tokens ALSO match its oracle
        assert np.array_equal(demoted.tokens, _oracle(models, prompts[5]))

        # drained queue -> calm rounds -> full quality restored
        assert loop.policy.level == 0
        assert loop._bat.n_draft == base > peak_ndraft
        assert loop.health is HealthState.SERVING

        # ...and the beam lane is honored again at level 0
        loop.submit(Request(rid=91, prompt=prompts[6], beam=True))
        (res,) = loop.run_until_idle()
        loop.close()
        assert isinstance(res, Completed) and res.via_beam
        assert beam_calls == [TOTAL - P]


# -- fleet satellites: queue counters, result meta, clock jumps ----------


class TestQueueTraceCounters:
    def test_depth_and_oldest_age_emitted_on_change(self):
        from rocket_tpu.observe.trace import Tracer

        tracer = Tracer(capacity=64, enabled=True)
        clk = FakeClock()
        q = AdmissionQueue(4, name="r0", tracer=tracer, clock=clk)
        q.offer(Request(rid=0, prompt=np.ones(4, np.int32)))
        clk.tick(2.0)
        q.offer(Request(rid=1, prompt=np.ones(4, np.int32)))
        q.pop()

        def series(name):
            key = name.rsplit("/", 1)[-1]
            return [e[5][key] for e in tracer.events() if e[1] == name]

        assert series("serve/queue/r0/depth") == [1.0, 2.0, 1.0]
        ages = series("serve/queue/r0/oldest_age_s")
        # offer@t0, offer@t2 (head aged 2s), pop@t2 (new head age 0)
        assert ages == [0.0, 2.0, 0.0]

    def test_shed_observes_once(self):
        from rocket_tpu.observe.trace import Tracer

        tracer = Tracer(capacity=64, enabled=True)
        clk = FakeClock()
        q = AdmissionQueue(4, name="q1", tracer=tracer, clock=clk)
        for i in range(3):
            q.offer(Request(rid=i, prompt=np.ones(4, np.int32),
                            deadline=1.0))
        before = len([e for e in tracer.events()
                      if e[1] == "serve/queue/q1/depth"])
        clk.tick(5.0)
        shed = q.shed_hopeless(clk(), 0.0)
        assert len(shed) == 3
        depth = [e[5]["depth"] for e in tracer.events()
                 if e[1] == "serve/queue/q1/depth"]
        assert len(depth) == before + 1 and depth[-1] == 0.0


class TestResultMeta:
    def test_completed_meta_carries_replica_and_level(self, models,
                                                      prompts):
        loop = ServingLoop(_factory(models), max_batch=B,
                           queue_capacity=8, replica_id="r7")
        assert loop.submit(Request(rid=0, prompt=prompts[0])) is None
        (res,) = loop.run_until_idle()
        loop.close()
        assert isinstance(res, Completed)
        assert res.meta == {"replica": "r7", "level": 0}

    def test_rejection_meta(self, models, prompts):
        loop = ServingLoop(_factory(models), max_batch=B,
                           queue_capacity=8, replica_id="r8")
        loop.drain()
        rej = loop.submit(Request(rid=0, prompt=prompts[0]))
        loop.close()
        assert isinstance(rej, Overloaded)
        assert rej.meta["replica"] == "r8"


class TestClockJumpShedding:
    def test_queued_deadlines_shed_after_wedge(self, models, prompts):
        """A clock jump while the loop was wedged: queued entries whose
        deadline passed meanwhile are shed as DeadlineExceeded
        (stage='queue', never prefilled) on the FIRST round after
        recovery; the in-flight deadline-free row still completes."""
        clk = FakeClock()
        loop = ServingLoop(_factory(models), max_batch=1,
                           queue_capacity=8, clock=clk)
        assert loop.submit(Request(rid=0, prompt=prompts[0])) is None
        loop.run_round()                     # rid 0 is in flight
        admitted_before = loop.counters.admitted
        for i in (1, 2):
            assert loop.submit(
                Request(rid=i, prompt=prompts[i], deadline=clk() + 5.0)
            ) is None

        clk.tick(100.0)                      # the wedge: deadlines passed
        loop.run_round()                     # first round after recovery

        shed = [r for r in loop.drain_results()
                if isinstance(r, DeadlineExceeded)]
        assert sorted(r.rid for r in shed) == [1, 2]
        assert all(r.stage == "queue" for r in shed)
        assert all(r.tokens is None for r in shed)
        # neither shed entry ever reached the batcher
        assert loop.counters.admitted == admitted_before
        assert loop.counters.shed_deadline == 2

        results = loop.run_until_idle()
        loop.close()
        assert [r.rid for r in results] == [0]
        assert isinstance(results[0], Completed)
        assert np.array_equal(results[0].tokens,
                              _oracle(models, prompts[0]))


class TestRetryObservability:
    def test_on_retry_hook_and_trace_counter(self):
        from rocket_tpu.observe import trace

        src = FaultySource([10, 20, 30], fail_on=(0,), times=2)
        seen = []
        trace.arm(128)
        try:
            value = retry_call(
                src.__getitem__, 0, tries=5, base_delay=0.0,
                name="fetch", on_retry=lambda a, e, d: seen.append(a),
            )
            events = [e for e in trace.get_tracer().events()
                      if e[1] == "retry/fetch/attempts"]
        finally:
            trace.disarm()
        assert value == 10
        assert seen == [1, 2]
        assert [e[5]["attempts"] for e in events] == [1.0, 2.0]
