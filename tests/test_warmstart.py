"""Warm-start tier tests (ISSUE 15): persistent compile cache, AOT
executable reuse, WarmupPlan, emergency-tier restore in the worker, and
the autoscaler's pre-warmed standby pool.

Layered like the tier itself: pure-host units first (cache dir
resolution, AOT keys, plan wire format), then in-process compile-cache
behavior (CompileRecord.cache_hit across a ``jax.clear_caches()``,
AOT serialize/deserialize round-trip), then the batcher/loop warmup
path, the ``restore_params`` emergency election, and the standby-pool
control logic against fakes.  The real-subprocess promotion ride lives
at the bottom under the ``warmstart`` marker.  What start-up costs in
time is ``setup_s`` of ``benchmark/run.py``, on the chip.
"""

import os
import sys
import time
import types

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from rocket_tpu.tune import compile_cache  # noqa: E402
from rocket_tpu.tune.warmup import (  # noqa: E402
    WarmupPlan,
    plan_for_batcher,
    warm_batcher,
)

import rocket_tpu.testing.workers as tw  # noqa: E402


# -- cache dir resolution ---------------------------------------------------


def test_cache_dir_is_jax_env_and_arming_keeps_it(monkeypatch, tmp_path):
    import jax

    placed = str(tmp_path / "cc")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    assert compile_cache.cache_dir() == placed
    # armed from outside: the config names that directory and no other
    assert compile_cache.enable_compile_cache() == placed
    assert jax.config.jax_compilation_cache_dir == placed
    assert compile_cache.enabled_dir() == placed


def test_cache_dir_defaults_to_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.cache_dir() == os.path.join(
        repo, "experiments", "compile_cache")


def test_unarmable_cache_dir_raises(monkeypatch, tmp_path):
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(blocker / "cc"))
    with pytest.raises(OSError):
        compile_cache.enable_compile_cache()


def test_aot_key_is_deterministic_and_filesystem_safe():
    a = compile_cache.aot_key("generate/spec_round", n_draft=4, batch=3,
                              backend="cpu")
    b = compile_cache.aot_key("generate/spec_round", backend="cpu", batch=3,
                              n_draft=4)
    assert a == b                       # kwarg order is canonicalized
    assert "/" not in a and " " not in a
    shaped = compile_cache.aot_key("engine/step", shapes="(3, 8)int32")
    assert all(c.isalnum() or c in "_.=-" for c in shaped)
    assert a != compile_cache.aot_key("generate/spec_round", n_draft=5,
                                      batch=3, backend="cpu")


# -- WarmupPlan -------------------------------------------------------------


def test_warmup_plan_wire_roundtrip():
    plan = WarmupPlan(max_batch=3, prompt_len=1, n_drafts=(4, 6), aot=False)
    assert WarmupPlan.from_wire(plan.to_wire()) == plan
    # wire dicts are plain data (WorkerSpec kwargs must pickle cleanly)
    wired = plan.to_wire()
    assert wired["n_drafts"] == [4, 6] and wired["aot"] is False
    # missing optional fields take the defaults
    assert WarmupPlan.from_wire({"max_batch": 2}) == WarmupPlan(max_batch=2)


def test_plan_for_batcher_dedupes_and_drops_nonpositive():
    bat = types.SimpleNamespace(n_draft=4)
    plan = plan_for_batcher(bat, 3, extra_drafts=(6, 4, 6, 0, -2))
    assert plan.max_batch == 3 and plan.prompt_len == 1
    assert plan.n_drafts[0] == 4        # the configured draft leads
    assert 6 in plan.n_drafts
    assert len(plan.n_drafts) == len(set(plan.n_drafts))
    assert all(n > 0 for n in plan.n_drafts)


# -- compile cache: arming, counters, per-edge cache_hit --------------------


@pytest.mark.goodput
class TestCompileCache:
    def test_enable_is_idempotent_and_registers_export(self, tmp_path,
                                                       monkeypatch):
        from rocket_tpu.observe import export

        d = str(tmp_path / "cc")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
        assert compile_cache.enable_compile_cache() == d
        assert compile_cache.enable_compile_cache() == d
        assert compile_cache.enabled_dir() == d
        assert os.path.isdir(d)
        snap = export.collect()
        assert "compile_cache/hits" in snap
        assert "compile_cache/bytes" in snap

    def test_compile_record_cache_hit_after_cache_retrieval(
            self, tmp_path, devices, monkeypatch):
        """The per-edge visibility promise: a compile served from the
        persistent disk cache stamps ``CompileRecord.cache_hit=True``
        (``jax.clear_caches()`` drops the dispatch cache, so the second
        dispatch re-lowers — but retrieves instead of compiling)."""
        import jax
        import jax.numpy as jnp

        from rocket_tpu.observe.ledger import (
            arm_ledgers,
            disarm_ledgers,
            get_retrace_ledger,
            ledger_call,
        )

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
        compile_cache.enable_compile_cache()
        compile_cache.reset_stats()
        arm_ledgers()
        try:
            fn = jax.jit(lambda x: (x * 3.0 + 1.0).sum())
            x = jnp.arange(512.0)
            ledger_call(fn, "warmstart/probe", x)       # cold: real compile
            ledger = get_retrace_ledger()
            recs = [r for r in ledger.records()
                    if r.name == "warmstart/probe"]
            assert recs and recs[-1].cache_hit is False
            jax.clear_caches()
            with ledger.expect_compile("warmstart/probe"):
                ledger_call(fn, "warmstart/probe", x)   # warm: disk hit
            recs = [r for r in ledger.records()
                    if r.name == "warmstart/probe"]
            assert recs[-1].cache_hit is True
            assert ledger.snapshot()["cache_hits"] >= 1.0
            assert compile_cache.hit_count() >= 1
            snap = compile_cache.snapshot()
            assert snap["hits"] >= 1 and snap["entries"] >= 1
        finally:
            disarm_ledgers()
            get_retrace_ledger().reset()

    def test_aot_save_load_roundtrip_and_fallthrough(self, tmp_path,
                                                     devices, monkeypatch):
        import jax
        import jax.numpy as jnp

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
        compile_cache.enable_compile_cache()
        compile_cache.reset_stats()
        fn = jax.jit(lambda x: x * 2.0 + 1.0)
        x = jnp.arange(8.0)
        compiled = fn.lower(x).compile()
        key = compile_cache.aot_key("warmstart/aot_probe", n=8)
        assert compile_cache.save_aot(key, compiled)
        loaded = compile_cache.load_aot(key)
        assert loaded is not None
        np.testing.assert_array_equal(np.asarray(loaded(x)),
                                      np.asarray(compiled(x)))
        # a missing key is a silent fall-through, never an error
        assert compile_cache.load_aot("warmstart/no_such_key") is None
        # a corrupt payload falls through too (counted, not raised)
        path = os.path.join(str(tmp_path / "cc"), "aot", key + ".pkl")
        with open(path, "wb") as f:
            f.write(b"not a pickle")
        assert compile_cache.load_aot(key) is None
        snap = compile_cache.snapshot()
        assert snap["aot_saved"] >= 1 and snap["aot_hits"] >= 1
        assert snap["aot_fallthrough"] >= 1


# -- WarmupPlan execution against the tiny batcher --------------------------


@pytest.mark.warmstart
class TestWarmBatcher:
    def test_warm_batcher_compiles_edges_then_aot_hits(self, tmp_path,
                                                       devices, monkeypatch):
        from rocket_tpu.models.generate import ContinuousBatcher

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
        compile_cache.enable_compile_cache()
        compile_cache.reset_stats()
        model, draft, params, dparams = tw.tiny_models()
        bat = ContinuousBatcher(model, draft, params, dparams,
                                total_len=tw.TOTAL, n_draft=tw.NDRAFT,
                                eos_token=None)
        plan = plan_for_batcher(bat, tw.B)
        assert tw.NDRAFT in plan.n_drafts
        stats = warm_batcher(bat, plan)
        # prefill + at least one spec round compiled, timed, counted
        assert stats["edges"] >= 2
        assert stats["compile_ms"] > 0.0
        # the spec-round executable serialized (CPU supports it) —
        # a second pass loads it instead of compiling
        assert stats["aot_serialized"] >= 1
        stats2 = warm_batcher(bat, plan)
        assert stats2["aot_hits"] >= 1

    def test_serving_loop_consumes_auto_plan(self, devices):
        from rocket_tpu.serve import Completed, Request

        loop = tw.build_tiny_loop(warmup="auto")
        try:
            assert loop.warm_stats.get("edges", 0) >= 2
            # warm start is an accelerant, never a numerics change:
            # the warmed loop still serves bit-equal to a plain one
            prompt = np.random.default_rng(13).integers(
                1, tw.VOCAB, size=tw.P).astype(np.int32)
            loop.submit(Request(rid="r0", prompt=prompt))
            (out,) = loop.run_until_idle()
            assert isinstance(out, Completed)
        finally:
            loop.close()
        plain = tw.build_tiny_loop()
        try:
            plain.submit(Request(rid="r0", prompt=prompt))
            (ref,) = plain.run_until_idle()
        finally:
            plain.close()
        np.testing.assert_array_equal(np.asarray(out.tokens),
                                      np.asarray(ref.tokens))


# -- restore_params: emergency-tier election (satellite fix) ----------------


@pytest.mark.elastic
class TestEmergencyRestore:
    SEED = 5    # differs from the builder default, so a match PROVES restore

    def _assert_restored(self, restored):
        import jax

        _, _, want, _ = tw.tiny_models(seed_target=self.SEED)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)),
            restored, want)

    def test_emergency_only_root_worker_layout(self, tmp_path, devices):
        from rocket_tpu.serve.worker import restore_params

        tw.save_tiny_emergency(str(tmp_path), seed_target=self.SEED)
        _, _, targets, _ = tw.tiny_models()     # default-seed template
        self._assert_restored(restore_params(str(tmp_path), targets))

    def test_emergency_only_root_trainer_layout(self, tmp_path, devices):
        """The flush a TRAINER leaves behind nests params inside the
        capsule state (``{"model": {"state": {"params": ...}}}``); the
        manifest's recorded leaf paths must locate the subtree."""
        from rocket_tpu.serve.worker import restore_params

        tw.save_tiny_emergency(str(tmp_path), seed_target=self.SEED,
                               trainer_layout=True)
        _, _, targets, _ = tw.tiny_models()
        self._assert_restored(restore_params(str(tmp_path), targets))

    def test_missing_root_still_raises(self, tmp_path):
        from rocket_tpu.serve.worker import restore_params

        with pytest.raises(FileNotFoundError):
            restore_params(str(tmp_path / "empty"), {})


# -- standby pool: control logic against fakes ------------------------------


class _FakeStandby:
    """Replica-shaped fake with the warm-start surface the pool touches
    (rename/close/compile_ms/standby_source)."""

    def __init__(self, rid):
        self.replica_id = rid
        self.load = 0
        self._dead = None
        self.threaded = False
        self.compile_ms = 123.0
        self.renames = []
        self.closed = False
        self.standby_source = None

    def rename(self, rid):
        self.renames.append(rid)
        self.replica_id = rid

    def start(self, idle_s=0.001):
        pass

    def drain(self):
        pass

    def close(self):
        self.closed = True


class _FakeRouter:
    def __init__(self, n=1):
        self.replicas = [_FakeStandby(f"r{i}") for i in range(n)]
        self._retiring = []
        self.added = []

    def add_replica(self, rep, *, start=None):
        self.replicas.append(rep)
        self.added.append(rep.replica_id)

    def remove_replica(self, rid):
        (rep,) = [r for r in self.replicas if r.replica_id == rid]
        self.replicas.remove(rep)
        return rep


def _standby_scaler(router, metrics, policy, spawned):
    from rocket_tpu.serve.autoscale import Autoscaler

    def spawn(rid):
        rep = _FakeStandby(rid)
        spawned.append(rep)
        return rep

    return Autoscaler(router, spawn, policy,
                      collect_fn=lambda: dict(metrics),
                      clock=time.monotonic)


@pytest.mark.procfleet
class TestStandbyPool:
    def _policy(self, **kw):
        from rocket_tpu.serve.autoscale import SLOPolicy

        base = dict(ttft_p95_ms=500.0, breach_rounds=1,
                    scale_up_cooldown_s=0.0, max_replicas=4, standby=1)
        base.update(kw)
        return SLOPolicy(**base)

    def test_pool_fills_synchronously_on_construction(self):
        spawned = []
        auto = _standby_scaler(_FakeRouter(1), {}, self._policy(), spawned)
        try:
            assert auto.counters.standby_ready == 1
            assert [r.replica_id for r in spawned] == ["standby-1"]
            # heal preference wired onto the existing router replicas
            (existing, ) = [r for r in auto.router.replicas
                            if not r.replica_id.startswith("standby")]
            assert existing.standby_source == auto._take_standby
        finally:
            auto.close()
        assert spawned[0].closed        # close tears the pool down

    def test_scale_up_promotes_standby_in_o_route(self):
        spawned = []
        router = _FakeRouter(1)
        metrics = {"serve_fleet/ttft_ms/p95": 900.0}
        auto = _standby_scaler(router, metrics, self._policy(), spawned)
        try:
            warm = spawned[0]
            assert auto.step() == 1
            # the promoted replica IS the pre-warmed one, renamed over
            # its live identity — no new spawn inside the breach
            assert router.added == ["scale-1"]
            assert router.replicas[-1] is warm
            assert warm.renames == ["scale-1"]
            assert auto.counters.standby_promotions == 1
            # the decision log surfaces the worker's READY compile_ms
            event = auto.events[-1]
            assert event["action"] == "scale_up"
            assert event["standby"] is True
            assert event["compile_ms"] == 123.0
            # the pool refills in the background toward standby=1
            assert auto.wait_standby() == 1
            assert auto.counters.standby_ready == 1
        finally:
            auto.close()

    def test_cold_spawn_fallback_when_pool_empty(self):
        spawned = []
        router = _FakeRouter(1)
        metrics = {"serve_fleet/ttft_ms/p95": 900.0}
        auto = _standby_scaler(router, metrics,
                               self._policy(standby=0), spawned)
        try:
            assert auto._take_standby() is None
            assert auto.step() == 1
            event = auto.events[-1]
            assert event["standby"] is False
            assert router.added == ["scale-1"]
        finally:
            auto.close()

    def test_failed_promotion_falls_back_to_cold_spawn(self):
        spawned = []
        router = _FakeRouter(1)
        metrics = {"serve_fleet/ttft_ms/p95": 900.0}
        auto = _standby_scaler(router, metrics, self._policy(), spawned)
        try:
            warm = spawned[0]
            warm.rename = lambda rid: (_ for _ in ()).throw(
                RuntimeError("standby died"))
            assert auto.step() == 1
            assert warm.closed          # the broken standby is reaped
            assert router.replicas[-1] is not warm
            assert router.added == ["scale-1"]
            assert auto.counters.standby_promotions == 0
            assert auto.events[-1]["standby"] is False
        finally:
            auto.close()

    def test_fleet_source_exports_spawn_and_heal_percentiles(self):
        from rocket_tpu.observe import export
        from rocket_tpu.observe.trace import Histogram
        from rocket_tpu.serve.autoscale import register_fleet_source
        from rocket_tpu.serve.metrics import ServeLatency

        class _Router:
            def __init__(self, reps):
                self.replicas = reps
                self._retiring = []

            def snapshot(self):
                return {"submitted": 0.0}

            def latency(self):
                return ServeLatency()

        rep = _FakeStandby("r0")
        rep.spawn_ms = Histogram()
        rep.heal_ms = Histogram()
        rep.first_token_ms = Histogram()
        for v in (1000.0, 2000.0, 3000.0):
            rep.spawn_ms.record(v)
        rep.heal_ms.record(500.0)
        name = "serve_fleet_ws_test"
        register_fleet_source(_Router([rep]), name)
        try:
            snap = export.collect()
            assert snap[f"{name}/spawn_ms/count"] == 3.0
            assert snap[f"{name}/spawn_ms/p50"] == 2000.0
            assert snap[f"{name}/heal_ms/p99"] == 500.0
            # an empty histogram exports no keys (thread-backed fleets)
            assert f"{name}/first_token_ms/count" not in snap
        finally:
            export.unregister_source(name)


# -- the real thing: a promoted standby serves without compiling ------------


@pytest.mark.warmstart
@pytest.mark.procfleet
def test_standby_promotion_real_worker_serves_without_compile(tmp_path):
    """ISSUE 15 acceptance: with ``standby=1`` the scale-up promotes an
    already-READY worker — the first routed request completes without
    ever touching the backend compiler (the plan pre-paid every edge
    including the per-prompt-length admit; serving dispatches are
    dispatch-cache hits or disk retrievals), under its new fleet
    identity, with zero unexpected retraces cross-process."""
    from rocket_tpu.serve.autoscale import Autoscaler, SLOPolicy
    from rocket_tpu.serve.procfleet import ProcReplica
    from rocket_tpu.serve.types import Completed, Request
    from rocket_tpu.serve.wire import WorkerSpec

    plan = WarmupPlan(max_batch=tw.B, n_drafts=(tw.NDRAFT,),
                      prompt_lens=(tw.P,))
    spec = WorkerSpec(builder="rocket_tpu.testing.workers:build_tiny_loop",
                      kwargs={"warmup": plan.to_wire()})
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc"),
           "JAX_PLATFORMS": "cpu"}

    def spawn(rid):
        return ProcReplica(spec, rid, spawn_timeout_s=600.0,
                           rpc_timeout_s=600.0, env=env)

    router = _FakeRouter(0)
    metrics = {"serve_fleet/ttft_ms/p95": 900.0}
    auto = Autoscaler(router, spawn,
                      SLOPolicy(ttft_p95_ms=500.0, breach_rounds=1,
                                scale_up_cooldown_s=0.0, max_replicas=2,
                                standby=1),
                      collect_fn=lambda: dict(metrics))
    rep = None
    try:
        assert auto.counters.standby_ready == 1
        assert auto.step() == 1
        assert auto.counters.standby_promotions == 1
        rep = router.replicas[-1]
        assert rep.replica_id == "scale-1"
        # the worker ran its WarmupPlan (prefill + round + admit) pre-READY
        assert rep.ready_info.get("warm_stats", {}).get("edges", 0) >= 3
        pre = rep.collect()
        assert pre["goodput"].get("compile_s", 0.0) > 0.0  # real work

        def compiled_programs():
            """Names of the programs the backend compiler has been asked
            for so far: it writes one persistent-cache entry a compile."""
            return sorted(name.rsplit("-", 2)[0]
                          for name in os.listdir(str(tmp_path / "cc"))
                          if name.endswith("-cache"))

        before = compiled_programs()
        for edge in ("jit__spec_prefill", "jit__spec_round",
                     "jit__spec_admit"):
            assert edge in before, (edge, before)   # the plan pre-paid it
        prompt = np.random.default_rng(13).integers(
            1, tw.VOCAB, size=tw.P).astype(np.int32)
        assert rep.submit(Request(rid="r0", prompt=prompt))
        out = []
        for _ in range(400):
            rep.pump()
            out = rep.drain_results()
            if out:
                break
        (res,) = out
        assert isinstance(res, Completed)
        # stamped with the promoted identity, not the standby's
        assert res.meta.get("replica") == "scale-1"
        post = rep.collect()
        # Serving compiled none of its programs again.  (The test used to
        # ask for a persistent-cache HIT on the admit edge and for under a
        # second of backend-compile time: since the plan's
        # ``lower().compile()`` and the live dispatch share JAX's
        # in-process executable cache the admit asks the compiler nothing,
        # hit or miss, and what serving does compile is op-by-op residue —
        # ``fold_in`` and friends — which is named here, not timed.)
        after = compiled_programs()
        for name in before:
            after.remove(name)
        assert not [n for n in after if n.startswith("jit__spec_")], after
        assert post["ledger"]["sentinel_dumps"] == 0.0
    finally:
        auto.close()
        if rep is not None:
            rep.close()
