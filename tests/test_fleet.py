"""Serving-fleet tests — FleetRouter / Replica / PrefillReplica end to end.

Three layers:

- units: KV handoff export/import bit-equality (f32 and int8 layouts),
  fleet-level saturation shedding, routing metadata;
- the fault-free contract (acceptance 2): fleet output is bit-identical
  per request to the single-``ServingLoop`` oracle regardless of which
  replica served it, with every request answered exactly once;
- the chaos pair + lanes: a replica killed mid-stream (acceptance 1 —
  every request still typed, the sick replica rebuilt from its factory,
  post-recovery output bit-correct), a flaky health probe driving the
  graceful drain-and-rebuild path, and prefill/decode disaggregation
  (acceptance 3 — a burst of long prompts stalls the merged-lane
  control visibly while the disaggregated decode lane's round cadence
  stays within a guarded bound of the no-long-prompt baseline).

CPU-proxy sizes run under tier-1; the thousand-request trace is
``slow``.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rocket_tpu.models.generate import (
    ContinuousBatcher,
    speculative_generate_batched,
)
from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
from rocket_tpu.serve import (
    Completed,
    FleetRouter,
    HealthState,
    Overloaded,
    PrefillReplica,
    PrefixKVStore,
    Replica,
    Request,
    ServingLoop,
)
from rocket_tpu.testing.chaos import (
    FlakyReplicaProxy,
    ReplicaKillInjector,
    SlowPrefillInjector,
)

pytestmark = pytest.mark.fleet

B, P, TOTAL, NDRAFT = 3, 8, 24, 4
P_LONG = 16


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
    return rng.integers(1, 64, size=(16, P)).astype(np.int32)


@pytest.fixture(scope="module")
def long_prompts():
    rng = np.random.default_rng(29)
    return rng.integers(1, 64, size=(4, P_LONG)).astype(np.int32)


def _bat_factory(models, **kw):
    model, draft, params, dparams = models

    def factory():
        return ContinuousBatcher(
            model, draft, params, dparams,
            total_len=TOTAL, n_draft=NDRAFT, eos_token=None, **kw,
        )

    return factory


def _loop_factory(models, **kw):
    bat = _bat_factory(models)
    kw.setdefault("max_batch", B)
    kw.setdefault("queue_capacity", 16)

    def factory():
        return ServingLoop(bat, **kw)

    return factory


def _oracle(models, prompt_row):
    model, draft, params, dparams = models
    toks = speculative_generate_batched(
        model, params, draft, dparams, prompt_row[None, :],
        max_new_tokens=TOTAL - prompt_row.shape[0], n_draft=NDRAFT,
    )
    return np.asarray(toks[0])


def _assert_exactly_once(results, rids):
    got = sorted(r.rid for r in results)
    assert got == sorted(rids), (got, sorted(rids))


@pytest.fixture(scope="module")
def warm_jit(models, prompts, long_prompts):
    """Compile every executable the timing-sensitive tests dispatch —
    short/long prefills, admits, the import scatter, and the round —
    so measured gaps are dispatch time, never compile time."""
    bat = _bat_factory(models)()
    bat.start(jnp.asarray(prompts[:B], jnp.int32))
    for r in range(B):
        bat.retire(r)
    bat.step()
    bat.admit(0, prompts[0][None, :])            # _spec_admit, P
    bat.retire(0)
    bat.admit(0, long_prompts[0][None, :])       # _spec_admit, P_LONG
    bat.retire(0)
    h = bat.prefill_handoff(prompts[1])          # _spec_prefill, B=1, P
    bat.admit_prefilled(0, h)                    # _spec_import_row
    bat.retire(0)
    bat.prefill_handoff(long_prompts[1])         # _spec_prefill, B=1, P_LONG
    # the loop's own warm group (P=1) + its step
    loop = _loop_factory(models)()
    loop.close()
    return True


# -- units: KV handoff ---------------------------------------------------


class TestKVHandoff:
    @pytest.mark.parametrize("int8", [False, True])
    def test_handoff_bit_equal_to_local_admit(self, models, prompts, int8):
        """A row prefilled on one batcher and imported into another is
        bit-identical to a local admit of the same prompt — including
        the int8 KV layout, whose pages travel with their scales."""
        kw = {"kv_cache_int8": True} if int8 else {}
        fac = _bat_factory(models, **kw)

        local = fac()
        local.start(jnp.asarray(prompts[:B], jnp.int32))
        for r in range(B):
            local.retire(r)
        local.admit(0, prompts[3][None, :])
        while not bool(np.asarray(local.state[2])[0]):
            local.step()
        tok_local, n_local = local.row_tokens(0)

        pre = fac()   # never start()-ed — the prefill-lane contract
        handoff = pre.prefill_handoff(prompts[3]).to_host()
        assert handoff.nbytes > 0
        assert handoff.total_len == TOTAL

        dec = fac()
        dec.start(jnp.asarray(prompts[:B], jnp.int32))
        for r in range(B):
            dec.retire(r)
        dec.admit_prefilled(0, handoff)
        while not bool(np.asarray(dec.state[2])[0]):
            dec.step()
        tok_dec, n_dec = dec.row_tokens(0)

        assert n_local == n_dec
        assert np.array_equal(tok_local, tok_dec)

    def test_int8_handoff_is_smaller(self, models, prompts):
        f32 = _bat_factory(models)().prefill_handoff(prompts[0]).to_host()
        i8 = _bat_factory(models, kv_cache_int8=True)() \
            .prefill_handoff(prompts[0]).to_host()
        assert i8.nbytes < f32.nbytes / 2

    def test_import_validates_layout(self, models, prompts):
        fac = _bat_factory(models)
        pre = fac()
        handoff = pre.prefill_handoff(prompts[0])
        dec = fac()
        with pytest.raises(ValueError, match="start"):
            dec.admit_prefilled(0, handoff)
        dec.start(jnp.asarray(prompts[:B], jnp.int32))
        with pytest.raises(ValueError, match="still decoding"):
            dec.admit_prefilled(0, handoff)
        dec.retire(0)
        with pytest.raises(ValueError, match="out of range"):
            dec.admit_prefilled(B, handoff)


# -- the fault-free contract (acceptance 2) ------------------------------


class TestFleetOracle:
    def test_fleet_matches_solo_oracle(self, models, prompts):
        """Fault-free fleet output is bit-identical per request to the
        single-loop oracle regardless of which replica served it, and
        the routing spreads across every replica."""
        reps = [Replica(_loop_factory(models), f"r{i}") for i in range(3)]
        router = FleetRouter(reps)
        n = 9
        for i in range(n):
            assert router.submit(Request(rid=i, prompt=prompts[i])) is None
        results = router.run_until_idle()
        _assert_exactly_once(results, range(n))
        served = set()
        for res in results:
            assert isinstance(res, Completed), res
            assert res.meta["replica"] in {"r0", "r1", "r2"}
            served.add(res.meta["replica"])
            assert np.array_equal(res.tokens,
                                  _oracle(models, prompts[res.rid]))
        # least-loaded routing must not pile everything on one replica
        assert len(served) >= 2, served
        assert router.counters.routed == n
        router.close()

    def test_fleet_saturation_shed(self, models, prompts):
        """When every replica refuses, the router sheds at fleet level
        with a typed Overloaded — and still exactly one result each."""
        reps = [
            Replica(_loop_factory(models, max_batch=1, queue_capacity=1),
                    f"s{i}")
            for i in range(2)
        ]
        router = FleetRouter(reps)
        n = 12
        rejected = 0
        for i in range(n):
            rej = router.submit(Request(rid=i, prompt=prompts[i % 8]))
            if rej is not None:
                assert isinstance(rej, Overloaded)
                assert rej.reason == "fleet saturated"
                assert rej.meta["replica"] is None
                rejected += 1
        assert rejected > 0
        assert router.counters.shed_saturated == rejected
        results = router.run_until_idle()
        _assert_exactly_once(results, range(n))
        completed = [r for r in results if isinstance(r, Completed)]
        assert len(completed) == n - rejected
        router.close()


# -- chaos: replica death and self-healing (acceptance 1) ----------------


class TestReplicaSelfHealing:
    def test_replica_kill_salvage_rebuild_bit_correct(self, models,
                                                      prompts):
        """Kill one of 3 replicas mid-stream: every in-flight and queued
        request still gets a typed result (here: all complete, served
        elsewhere or on the rebuilt replica), the sick replica rebuilds
        from its factory, and post-recovery output is bit-correct."""
        built = {"n": 0}
        base = _loop_factory(models)

        def killed_factory():
            built["n"] += 1
            loop = base()
            if built["n"] == 1:
                # die on the SECOND round: requests are in flight
                return ReplicaKillInjector(loop, kill_on=(1,))
            return loop

        reps = [Replica(killed_factory, "r0"),
                Replica(base, "r1"),
                Replica(base, "r2")]
        router = FleetRouter(reps)
        n = 9
        for i in range(n):
            assert router.submit(Request(rid=i, prompt=prompts[i])) is None
        results = router.run_until_idle()
        _assert_exactly_once(results, range(n))
        for res in results:
            assert isinstance(res, Completed), res
            assert np.array_equal(res.tokens,
                                  _oracle(models, prompts[res.rid]))
        assert router.counters.heals == 1
        assert router.counters.requeued > 0
        assert built["n"] == 2          # rebuilt from the factory

        # post-recovery: drain the survivors; the REBUILT replica must
        # serve — bit-correct — and routing must report it did
        reps[1].loop.drain()
        reps[2].loop.drain()
        assert reps[1].health is HealthState.DRAINING
        assert router.submit(Request(rid=100, prompt=prompts[10])) is None
        out = router.run_until_idle()
        assert len(out) == 1 and isinstance(out[0], Completed)
        assert out[0].meta["replica"] == "r0"
        assert np.array_equal(out[0].tokens,
                              _oracle(models, prompts[10]))
        router.close()

    def test_flaky_probe_drains_and_rebuilds(self, models, prompts):
        """A failed health probe (no exception anywhere) decommissions
        the replica gracefully: salvage, rebuild, keep serving."""
        built = {"n": 0}
        base = _loop_factory(models)

        def flaky_factory():
            built["n"] += 1
            loop = base()
            if built["n"] == 1:
                return FlakyReplicaProxy(loop, fail_on=(1,))
            return loop

        reps = [Replica(flaky_factory, "f0"), Replica(base, "f1")]
        router = FleetRouter(reps)
        n = 6
        for i in range(n):
            assert router.submit(Request(rid=i, prompt=prompts[i])) is None
        results = router.run_until_idle()
        _assert_exactly_once(results, range(n))
        for res in results:
            assert isinstance(res, Completed), res
            assert np.array_equal(res.tokens,
                                  _oracle(models, prompts[res.rid]))
        assert router.counters.heals == 1
        assert built["n"] == 2
        router.close()


# -- lanes: prefill/decode disaggregation (acceptance 3) -----------------


class TestDisaggregation:
    def test_handoff_lane_bit_equal(self, models, prompts):
        """With the prefill lane on, every request still matches the
        solo oracle bit for bit, and the handoffs actually happened."""
        dec = Replica(_loop_factory(models), "d0")
        pre = PrefillReplica(_bat_factory(models), "p0")
        router = FleetRouter([dec], prefill_replicas=[pre])
        n = 4
        for i in range(n):
            assert router.submit(Request(rid=i, prompt=prompts[i])) is None
        results = router.run_until_idle()
        _assert_exactly_once(results, range(n))
        for res in results:
            assert isinstance(res, Completed), res
            assert np.array_equal(res.tokens,
                                  _oracle(models, prompts[res.rid]))
        assert router.counters.handoffs == n
        assert router.counters.handoff_bytes > 0
        assert dec.loop.counters.prefilled_admits == n
        router.close()

    def _drive_decode(self, router, dec, n_expect, budget_s=60.0):
        """Pump the decode replica inline, on this thread, until
        ``n_expect`` results landed (the clock only bounds the wait)."""
        results = []
        t_end = time.monotonic() + budget_s
        while len(results) < n_expect:
            if time.monotonic() >= t_end:
                pytest.fail(
                    f"decode drive timed out with {len(results)}/{n_expect}")
            router.supervise()
            if not dec.pump():
                time.sleep(0.0005)
            results.extend(dec.drain_results())
            results.extend(router.drain_results())
        return results

    def test_long_prompt_burst_never_stalls_the_decode_lane(
            self, models, prompts, long_prompts, warm_jit):
        """The disaggregation headline: a burst of long prompts must not
        stall the decode lane.  It was asserted on the cadence of decode
        rounds (worst gap under 0.8 x an injected 0.4 s prefill delay);
        the gap stood for WHERE the stretched prefill runs, which
        ``SlowPrefillInjector``'s injected sleep can see.  Merged-lane
        control: long prompts prefill on the decode replica, so every
        stretch is slept by the thread that pumps decode and no decode
        round completes meanwhile.  Disaggregated: the same stretched
        prefills are slept by the prefill replica's own thread, never by
        the decode lane's, and decode rounds complete during them."""
        import threading

        n_short, n_long = 10, 3
        shorts = [Request(rid=i, prompt=prompts[i % 8])
                  for i in range(n_short)]
        longs = [Request(rid=100 + i, prompt=long_prompts[i % 4])
                 for i in range(n_long)]
        # interleave so longs admit while shorts still decode
        storm = shorts[:3] + [longs[0]] + shorts[3:6] + [longs[1]] \
            + shorts[6:8] + [longs[2]] + shorts[8:]
        me = threading.get_ident()
        lane = {}       # the decode replica of the run in progress
        stalls = []     # (thread, decode rounds completed meanwhile)

        def stretched(delay_s):
            rounds = lane["dec"].loop.counters.rounds
            time.sleep(delay_s)
            stalls.append((threading.get_ident(),
                           lane["dec"].loop.counters.rounds - rounds))

        def slow_bat_factory():
            # stretch only LONG prefills (min_len between P and P_LONG)
            return SlowPrefillInjector(
                _bat_factory(models)(), delay_s=0.2, min_len=P + 2,
                sleep=stretched)

        def slow_loop_factory():
            return ServingLoop(slow_bat_factory, max_batch=B,
                               queue_capacity=32)

        # merged-lane control: longs prefill ON the decode replica
        lane["dec"] = dec = Replica(slow_loop_factory, "m0")
        router = FleetRouter([dec])
        for req in storm:
            assert router.submit(req) is None
        merged_results = self._drive_decode(router, dec, n_short + n_long)
        assert all(isinstance(r, Completed) for r in merged_results)
        router.close()
        # every stretch ran on the decode lane, which stood still for it
        assert stalls == [(me, 0)] * n_long
        del stalls[:]

        # disaggregated: longs prefill on the prefill replica's thread
        lane["dec"] = dec = Replica(
            _loop_factory(models, queue_capacity=32), "d0")
        pre = PrefillReplica(slow_bat_factory, "p0")
        router = FleetRouter([dec], prefill_replicas=[pre],
                             prefill_threshold=P + 2)
        pre.start()
        try:
            for req in storm:
                assert router.submit(
                    Request(rid=req.rid, prompt=req.prompt)) is None
            dis_results = self._drive_decode(
                router, dec, n_short + n_long)
        finally:
            router.close()
        assert all(isinstance(r, Completed) for r in dis_results)
        assert router.counters.handoffs == n_long
        # no stretch was slept by the decode lane's thread...
        assert len(stalls) == n_long
        assert all(tid != me for tid, _ in stalls)
        # ...which went on completing rounds beside them
        assert sum(rounds for _, rounds in stalls) > 0


# -- scale ---------------------------------------------------------------


@pytest.mark.slow
def test_thousand_request_trace(models):
    """The seeded serve-demo arrival trace at fleet scale: a thousand
    requests across 3 replicas, every one answered exactly once, every
    completion bit-correct against the solo oracle (spot-checked)."""
    rng = np.random.default_rng(0)
    n = 1000
    all_prompts = rng.integers(1, 64, size=(n, P)).astype(np.int32)
    reps = [
        Replica(_loop_factory(models, queue_capacity=400), f"r{i}")
        for i in range(3)
    ]
    router = FleetRouter(reps)
    for i in range(n):
        router.submit(Request(rid=i, prompt=all_prompts[i]))
    results = router.run_until_idle(max_rounds=100_000)
    _assert_exactly_once(results, range(n))
    completed = [r for r in results if isinstance(r, Completed)]
    assert len(completed) == n
    for res in completed[::137]:      # spot-check bit-correctness
        assert np.array_equal(res.tokens,
                              _oracle(models, all_prompts[res.rid]))
    served = {r.meta["replica"] for r in completed}
    assert served == {"r0", "r1", "r2"}
    router.close()


# -- threaded-fleet race windows (deterministic probes) ------------------


class TestHealRaces:
    """A thread-backed replica can die BETWEEN a pump's supervise and
    its busy check, and submits can race a heal's rebuild.  Both
    windows are pinned deterministically here (no threads needed)."""

    def test_busy_sees_dead_replica_with_outstanding(self, models,
                                                     prompts):
        """A dead replica still owing results must keep the fleet busy:
        ``run_until_idle`` exiting before the next supervision beat
        would strand the shadowed request (exactly-once violation)."""
        rep = Replica(_loop_factory(models), "r0")
        router = FleetRouter([rep])
        assert router.submit(Request(rid=0, prompt=prompts[0])) is None
        # simulate the driver thread dying AFTER this beat's supervise
        rep._dead = "simulated mid-beat death"
        assert router.busy
        results = router.run_until_idle()
        _assert_exactly_once(results, [0])
        assert isinstance(results[0], Completed)
        assert np.array_equal(results[0].tokens,
                              _oracle(models, prompts[0]))
        assert router.counters.heals == 1
        assert router.counters.requeued == 1
        router.close()

    def test_heal_refuses_submits_until_rebuilt(self, models, prompts):
        """During heal's rebuild a concurrent submit must REFUSE: the
        death flag clears only after the fresh loop is in place, else
        the request lands in the old, already-salvaged loop."""
        built = {"n": 0}
        base = _loop_factory(models)
        box = {}

        def factory():
            built["n"] += 1
            if built["n"] == 2:   # i.e. called from inside heal()
                box["refused"] = not box["rep"].submit(
                    Request(rid=1, prompt=prompts[1]))
            return base()

        rep = Replica(factory, "r0")
        box["rep"] = rep
        router = FleetRouter([rep])
        assert router.submit(Request(rid=0, prompt=prompts[0])) is None
        rep._dead = "simulated"
        results = router.run_until_idle()
        _assert_exactly_once(results, [0])
        assert box["refused"] is True
        # healed: the replica accepts and serves again
        assert router.submit(Request(rid=2, prompt=prompts[2])) is None
        out = router.run_until_idle()
        _assert_exactly_once(out, [2])
        assert isinstance(out[0], Completed)
        router.close()


# -- session affinity over per-replica prefix stores (ISSUE 11) ----------


@pytest.mark.kvcache
class TestSessionAffinity:
    """Requests carrying a ``session`` key stick to the replica whose
    prefix store holds their pages; the cached turn decodes bit-equal
    to the oracle; a heal invalidates the stamp and the session
    re-routes cleanly with every request still typed exactly once."""

    PAGE = 4

    def _fleet(self, models, kill_r0_on=None, **bat_kw):
        stores = [PrefixKVStore(page_tokens=self.PAGE,
                                capacity_bytes=1 << 30) for _ in range(2)]
        base = _bat_factory(models, **bat_kw)
        built = {"r0": 0}

        def factory(i):
            def make():
                loop = ServingLoop(base, max_batch=B, queue_capacity=16,
                                   kvstore=stores[i])
                if i == 0 and kill_r0_on is not None:
                    built["r0"] += 1
                    if built["r0"] == 1:
                        return ReplicaKillInjector(loop,
                                                   kill_on=kill_r0_on)
                return loop
            return make

        reps = [Replica(factory(i), f"r{i}") for i in range(2)]
        return FleetRouter(reps), reps, stores

    def _turn(self, prompts, t):
        # turn t of the session: the first page is shared, the tail is
        # per-turn — the multi-turn shape at CPU-proxy size
        p = prompts[0].copy()
        p[self.PAGE:] = prompts[t][self.PAGE:]
        return p

    @pytest.mark.parametrize("int8", [False, True])
    def test_sticky_turn_hits_cache_bit_equal(self, models, prompts, int8):
        kw = {"kv_cache_int8": True} if int8 else {}
        router, reps, stores = self._fleet(models, **kw)
        p1, p2 = self._turn(prompts, 1), self._turn(prompts, 2)

        assert router.submit(Request(rid="t1", prompt=p1,
                                     session="s")) is None
        out1 = router.run_until_idle()
        _assert_exactly_once(out1, ["t1"])
        holder = out1[0].meta["replica"]
        assert router._affinity["s"] == holder

        # load the sticky replica so least-loaded WOULD pick the other:
        # affinity must override the load tiebreak, not ride it
        idx = int(holder[1])
        assert router.submit(Request(rid="fill", prompt=prompts[5])) is None
        if reps[idx].load == 0:
            reps[1 - idx].loop.submit(Request(rid="x", prompt=prompts[6]))
        assert router.submit(Request(rid="t2", prompt=p2,
                                     session="s")) is None
        assert router.counters.affinity_routed == 1
        out = router.run_until_idle()
        t2 = [r for r in out if r.rid == "t2"][0]
        assert isinstance(t2, Completed)
        assert t2.meta["replica"] == holder
        # the sticky replica really served turn 2 from its pages...
        snap = reps[idx].loop.counters.snapshot()
        assert snap["kv_hits"] >= 1
        assert stores[idx].snapshot()["hits"] >= 1
        assert stores[idx].snapshot()["pinned"] == 0
        # ...and the cached decode is bit-equal to the oracle
        assert np.array_equal(t2.tokens, _oracle(models, p2))
        router.close()

    def test_heal_invalidates_affinity_rerouted_exactly_once(
            self, models, prompts):
        router, reps, stores = self._fleet(models, kill_r0_on=(1,))
        p1, p2, p3 = (self._turn(prompts, t) for t in (1, 2, 3))

        assert router.submit(Request(rid="t1", prompt=p1,
                                     session="s")) is None
        out1 = router.run_until_idle()
        _assert_exactly_once(out1, ["t1"])
        assert out1[0].meta["replica"] == "r0"   # idle tie -> r0, stamped

        # turn 2 sticks to r0, which dies mid-round; the heal salvages
        # it, drops the stamp, and the re-route still types it once
        assert router.submit(Request(rid="t2", prompt=p2,
                                     session="s")) is None
        out2 = router.run_until_idle()
        _assert_exactly_once(out2, ["t2"])
        assert isinstance(out2[0], Completed)
        assert np.array_equal(out2[0].tokens, _oracle(models, p2))
        assert router.counters.heals == 1
        assert router.counters.affinity_invalidated >= 1
        # the rebuilt replica's store survived, with no leaked pins
        assert stores[0].snapshot()["pinned"] == 0

        # turn 3 routes cleanly on the fresh stamp (wherever the
        # salvaged turn 2 landed) and completes bit-correct
        assert router.submit(Request(rid="t3", prompt=p3,
                                     session="s")) is None
        out3 = router.run_until_idle()
        _assert_exactly_once(out3, ["t3"])
        assert isinstance(out3[0], Completed)
        assert np.array_equal(out3[0].tokens, _oracle(models, p3))
        assert router._affinity["s"] == out3[0].meta["replica"]
        router.close()
