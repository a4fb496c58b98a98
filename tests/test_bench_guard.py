"""bench.py guards: a config that raises fails the run (non-zero exit),
and a CPU run carries no MFU — plus the tracing-overhead guard (ISSUE 4
acceptance): arming the structured tracer adds ZERO jit traces and <5%
host overhead per train iteration and per serve round."""

import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture()
def bench(devices):
    import bench as bench_mod

    return bench_mod


# -- a failing config fails the run; a CPU run has no MFU -------------------


def _run_main(bench, monkeypatch, capsys, benches):
    import json

    monkeypatch.setattr(bench, "BENCHES", benches)
    persisted = []
    monkeypatch.setattr(bench, "_persist_record", persisted.append)
    rc = bench.main(["--only", "gpt2", "--steps", "1", "--warmup", "0"])
    printed = [json.loads(line)
               for line in capsys.readouterr().out.splitlines()
               if line.startswith("{")]
    assert printed == persisted  # nothing reaches the tracked file
    return rc, printed


def test_main_returns_nonzero_when_a_config_raises(bench, monkeypatch,
                                                   capsys):
    def boom(n_steps, warmup):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    rc, (record,) = _run_main(bench, monkeypatch, capsys, {"gpt2": boom})
    assert rc != 0
    assert record["value"] is None
    assert "Mosaic failed to compile" in record["error"]


def test_main_returns_zero_when_every_config_ran(bench, monkeypatch, capsys):
    rc, (record,) = _run_main(
        bench, monkeypatch, capsys,
        {"gpt2": lambda n_steps, warmup: {"config": "gpt2", "value": 1.0}})
    assert rc == 0 and record["value"] == 1.0


def test_cpu_run_gets_no_mfu_or_mbu(bench):
    # the tests run on CPU devices, which have no published peak: the
    # ladder must report no utilization rather than one over a chip's
    assert bench.peak_flops_per_chip() is None
    assert bench.peak_hbm_bytes_per_chip() is None


# -- tracing-overhead guard (ISSUE 4 acceptance) --------------------------
#
# The tentpole promise of observe.trace is "zero device syncs, lock-light,
# cheap enough to leave armed in production".  These tests hold the hot
# paths to that: with tracing armed, a train iteration and a serve round
# must (a) trace zero additional jitted step bodies and (b) stay within
# 5% host overhead of the disarmed run (plus an absolute floor for
# scheduler noise on tiny CPU steps — same tolerance discipline as
# tests/test_serving_resilience.py::test_host_overhead_under_5pct).


@pytest.mark.tracing
class TestTracingOverheadGuard:
    def test_train_iteration_overhead_and_trace_count(self, devices):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from rocket_tpu.core.attributes import Attributes
        from rocket_tpu.core.capsule import Capsule
        from rocket_tpu.launch.loop import Looper
        from rocket_tpu.observe.trace import disarm, get_tracer
        from rocket_tpu.runtime import Runtime

        class JitProbe(Capsule):
            def __init__(self):
                super().__init__()
                self.fn = jax.jit(lambda x: x * 2.0 + 1.0)
                self.x = jnp.ones((256, 256), jnp.float32)

            def launch(self, attrs=None):
                self.x = self.fn(self.x)

        repeats, trials = 50, 5

        def cycle_times(tracing):
            runtime = Runtime(tracing=tracing)
            probe = JitProbe()
            looper = Looper(capsules=[probe], repeats=repeats,
                            progress=False)
            looper.bind(runtime)
            attrs = Attributes()
            looper.setup(attrs)
            looper.launch(attrs)            # warmup cycle (compiles)
            looper.reset(attrs)
            jax.block_until_ready(probe.x)
            traces_before = probe.fn._cache_size()
            out = []
            for _ in range(trials):
                t0 = time.perf_counter()
                looper.launch(attrs)
                jax.block_until_ready(probe.x)
                out.append(time.perf_counter() - t0)
                looper.reset(attrs)
            # armed or not, the loop traced ZERO new step bodies
            assert probe.fn._cache_size() == traces_before
            return out

        try:
            bare = float(np.median(cycle_times(False))) / repeats
            armed = float(np.median(cycle_times(True))) / repeats
        finally:
            disarm()
            get_tracer().clear()
        assert armed <= bare * 1.05 + 5e-4, (
            f"armed iter {armed * 1e3:.3f}ms vs bare {bare * 1e3:.3f}ms"
        )

    def test_serve_round_overhead_and_trace_count(self, devices):
        import jax
        import numpy as np

        from rocket_tpu.models.generate import ContinuousBatcher, _spec_round
        from rocket_tpu.models.transformer import (
            TransformerConfig,
            TransformerLM,
        )
        from rocket_tpu.observe.trace import Tracer
        from rocket_tpu.serve import Request, ServingLoop

        B, P, TOTAL, NDRAFT = 3, 8, 24, 4

        def _lm(seed):
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

        model, params = _lm(1)
        draft, _ = _lm(1)
        _, dparams = _lm(7)
        rng = np.random.default_rng(13)
        prompts = rng.integers(1, 64, size=(B, P)).astype(np.int32)

        def factory():
            return ContinuousBatcher(
                model, draft, params, dparams,
                total_len=TOTAL, n_draft=NDRAFT, eos_token=None,
            )

        rounds = 8

        def round_times(tracer):
            loop = ServingLoop(factory, max_batch=B, queue_capacity=8,
                               watchdog_timeout=30.0, tracer=tracer)
            for i in range(B):
                loop.submit(Request(rid=i, prompt=prompts[i]))
            loop.run_round()  # admits + settles
            out = []
            for _ in range(rounds):
                t0 = time.perf_counter()
                loop.run_round()
                out.append(time.perf_counter() - t0)
            loop.close()
            return out

        bare = float(np.median(round_times(Tracer(enabled=False))))
        traces_before = _spec_round._cache_size()
        armed_tracer = Tracer(capacity=1024, enabled=True)
        armed = float(np.median(round_times(armed_tracer)))
        # arming recorded real spans without tracing a single new body
        assert _spec_round._cache_size() == traces_before
        assert any(e[1] == "serve/round" for e in armed_tracer.events())
        assert armed <= bare * 1.05 + 5e-4, (
            f"armed round {armed * 1e3:.3f}ms vs bare {bare * 1e3:.3f}ms"
        )


# -- distributed-tracing guard (ISSUE 19 acceptance) -----------------------
#
# The request-tracing tentpole's promise: stamping a TraceContext on
# every request and emitting its flow chain (s -> t... -> f) at sampling
# rate 1.0 is pure host bookkeeping — a crc32, a dataclass, a ring
# append per hop.  Armed, a serve round must trace ZERO new jitted
# bodies and stay within 5% host overhead of the disarmed loop (same
# tolerance discipline as the guards above).


@pytest.mark.tracing
class TestTraceCtxGuard:
    def test_ctx_stamped_round_overhead_and_trace_count(self, devices):
        import jax
        import numpy as np

        from rocket_tpu.models.generate import ContinuousBatcher, _spec_round
        from rocket_tpu.models.transformer import (
            TransformerConfig,
            TransformerLM,
        )
        from rocket_tpu.observe.trace import (
            Tracer,
            get_sampling,
            set_sampling,
        )
        from rocket_tpu.serve import Request, ServingLoop

        B, P, TOTAL, NDRAFT = 3, 8, 24, 4

        def _lm(seed):
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

        model, params = _lm(1)
        draft, _ = _lm(1)
        _, dparams = _lm(7)
        rng = np.random.default_rng(13)
        prompts = rng.integers(1, 64, size=(B, P)).astype(np.int32)

        def factory():
            return ContinuousBatcher(
                model, draft, params, dparams,
                total_len=TOTAL, n_draft=NDRAFT, eos_token=None,
            )

        rounds = 8

        def round_times(tracer):
            loop = ServingLoop(factory, max_batch=B, queue_capacity=8,
                               watchdog_timeout=30.0, tracer=tracer)
            for i in range(B):
                loop.submit(Request(rid=i, prompt=prompts[i]))
            loop.run_round()  # admits + settles
            out = []
            for _ in range(rounds):
                t0 = time.perf_counter()
                loop.run_round()
                out.append(time.perf_counter() - t0)
            loop.run_until_idle()  # terminal "f" flow events emit here
            loop.close()
            return out

        rate, seed = get_sampling()
        set_sampling(1.0, 0)     # every request stamped AND flow-traced
        try:
            bare = float(np.median(round_times(Tracer(enabled=False))))
            traces_before = _spec_round._cache_size()
            armed_tracer = Tracer(capacity=4096, enabled=True)
            armed = float(np.median(round_times(armed_tracer)))
        finally:
            set_sampling(rate, seed)
        # ctx stamping + flow emission traced zero new jitted bodies...
        assert _spec_round._cache_size() == traces_before
        # ...while really recording every request's full flow chain
        phases = [f.get("ph") for k, n, _ts, _d, _t, f
                  in armed_tracer.events()
                  if k == "F" and n == "serve/request"]
        assert phases.count("s") == B and phases.count("f") == B
        assert "t" in phases
        assert armed <= bare * 1.05 + 5e-4, (
            f"ctx-stamped round {armed * 1e3:.3f}ms vs bare "
            f"{bare * 1e3:.3f}ms"
        )


# -- async-loop guard (ISSUE 5 acceptance) --------------------------------
#
# The non-blocking Looper's promise: with readback deferred k iterations,
# the per-iteration HOST dispatch gap (the time the chip could sit idle
# between steps) drops strictly below the synchronous loop's — which pays
# a device wait every iteration to float the fresh loss — while tracing
# zero additional step bodies and adding <5% host overhead when nothing
# consumes the readback at all.  The model is sized so the device step
# clearly dominates python dispatch on CPU, making the gap comparison
# meaningful rather than noise-vs-noise.


class TestAsyncLoopGuard:
    REPEATS = 12
    BATCH = 128

    def _data(self):
        import numpy as np

        rng = np.random.default_rng(0)
        n = self.REPEATS * self.BATCH
        protos = rng.normal(size=(4, 64)).astype(np.float32) * 3.0
        labels = rng.integers(0, 4, size=n)
        x = (protos[labels] + rng.normal(size=(n, 64))).astype(np.float32)
        return {"x": x, "label": labels.astype(np.int32)}

    def _build(self, lag, reader):
        import flax.linen as nn

        import rocket_tpu as rt
        from rocket_tpu.models.objectives import cross_entropy

        class WideMLP(nn.Module):
            @nn.compact
            def __call__(self, batch, train=False):
                x = batch["x"]
                x = nn.relu(nn.Dense(512)(x))
                x = nn.relu(nn.Dense(512)(x))
                out = rt.Attributes(batch)
                out["logits"] = nn.Dense(4)(x)
                return out

        model = rt.Module(
            WideMLP(),
            capsules=[
                rt.Loss(cross_entropy(labels_key="label"), name="ce"),
                rt.Optimizer(learning_rate=1e-2),
            ],
        )
        capsules = [
            rt.Dataset(rt.ArraySource(self._data()), batch_size=self.BATCH,
                       device_prefetch=2),
            model,
        ]
        if reader is not None:
            capsules.append(reader)
        looper = rt.Looper(capsules=capsules, progress=False,
                           readback_lag=lag)
        # Single-device mesh: dispatch of an executable sharded over the 8
        # FAKE cpu devices blocks on the previous step (an artifact of the
        # forced-host-platform device emulation, not of the loop) — which
        # would drown the readback-wait difference this guard measures.
        # On one device the CPU client pipelines dispatches like a real
        # accelerator, making the gap comparison meaningful.
        import jax

        from rocket_tpu.parallel.mesh import data_parallel_mesh

        looper.bind(rt.Runtime(mesh=data_parallel_mesh(jax.devices()[:1])))
        attrs = rt.Attributes()
        looper.setup(attrs)
        return looper, model, attrs

    @staticmethod
    def _sync_reader():
        import rocket_tpu as rt

        class SyncReader(rt.Capsule):
            """The classic loop: floats THIS iteration's loss during
            dispatch — a device wait on the hot path every iteration."""

            def __init__(self):
                super().__init__(statefull=False, priority=300)
                self.seen = 0

            def launch(self, attrs=None):
                if attrs is not None and attrs.step_logs is not None:
                    float(attrs.step_logs["loss"])
                    self.seen += 1

        return SyncReader()

    @staticmethod
    def _lagged_reader():
        import rocket_tpu as rt

        class LaggedReader(rt.Capsule):
            """Consumes the k-lagged host floats — no device wait."""

            def __init__(self):
                super().__init__(statefull=False, priority=300)
                self.seen = 0

            def launch(self, attrs=None):
                if attrs is None or attrs.looper is None:
                    return
                lagged = attrs.looper.get("lagged_logs")
                if lagged is not None:
                    float(lagged["loss"])
                    self.seen += 1

        return LaggedReader()

    def _gap_ms(self, lag, reader, trials=3):
        import jax

        looper, model, attrs = self._build(lag, reader)
        looper.launch(attrs)  # warmup cycle (compiles)
        looper.reset(attrs)
        jax.block_until_ready(model.state.params)
        gaps = []
        for _ in range(trials):
            looper.launch(attrs)
            gaps.append(looper.last_dispatch_gap_ms)
            looper.reset(attrs)
            jax.block_until_ready(model.state.params)
        # the async plumbing traced ZERO new step bodies across cycles
        assert model._steps["sync"]._cache_size() == 1
        return min(gaps)

    def test_async_dispatch_gap_beats_sync(self, devices):
        sync_reader = self._sync_reader()
        gap_sync = self._gap_ms(0, sync_reader)
        lagged_reader = self._lagged_reader()
        gap_async = self._gap_ms(2, lagged_reader)
        # both variants actually consumed loss values every cycle
        assert sync_reader.seen >= self.REPEATS
        assert lagged_reader.seen > 0
        assert gap_async < gap_sync, (
            f"async gap {gap_async:.3f}ms not below sync {gap_sync:.3f}ms"
        )
        # CPU-proxy threshold: the async gap is pure host dispatch — it
        # must sit well under the device-wait-dominated sync gap, not
        # merely shave a sliver off it.
        assert gap_async < 0.5 * gap_sync + 0.3, (
            f"async gap {gap_async:.3f}ms vs sync {gap_sync:.3f}ms"
        )

    def test_lag_machinery_overhead_bounded(self, devices):
        import jax
        import numpy as np

        def cycle_times(lag, trials=5):
            looper, model, attrs = self._build(lag, None)
            looper.launch(attrs)  # warmup cycle (compiles)
            looper.reset(attrs)
            jax.block_until_ready(model.state.params)
            out = []
            for _ in range(trials):
                t0 = time.perf_counter()
                looper.launch(attrs)
                jax.block_until_ready(model.state.params)
                out.append(time.perf_counter() - t0)
                looper.reset(attrs)
            return out

        def measure():
            bare = float(np.median(cycle_times(0))) / self.REPEATS
            armed = float(np.median(cycle_times(2))) / self.REPEATS
            return bare, armed

        # On this CPU proxy an iter is ~8ms of pure host dispatch and a
        # looper's lifetime inherits its build-time allocator/thread
        # placement luck — measured build-to-build spread is ±30%, so
        # the TPU-grade <5% bound is not resolvable here.  Bound the
        # overhead at 1.5x instead, which still catches the regression
        # classes this guard exists for (an extra dispatch per iter, a
        # param-tree copy through the lag ring), and retry once with
        # fresh builds so a transient bad draw — unlike a systematic
        # regression, which fails both — doesn't flake the suite.
        bare, armed = measure()
        if armed > bare * 1.5 + 5e-4:
            bare, armed = measure()
        assert armed <= bare * 1.5 + 5e-4, (
            f"lagged iter {armed * 1e3:.3f}ms vs sync {bare * 1e3:.3f}ms"
        )


# -- emergency-tier guard (ISSUE 8 acceptance) -----------------------------
#
# The emergency checkpoint tier's promise: staging a host snapshot every
# ``emergency_every`` iterations is an ASYNC readback — zero device syncs
# and zero extra jit traces on the happy path, with the flush-to-disk cost
# paid only inside a SIGTERM grace window.  This guard holds the armed
# train loop to <5% host overhead over the unarmed one (same tolerance
# discipline as the tracing guard above).


@pytest.mark.elastic
class TestElasticGuard:
    def test_emergency_capture_overhead_and_trace_count(self, devices,
                                                        tmp_path):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from rocket_tpu.core.attributes import Attributes
        from rocket_tpu.core.capsule import Capsule
        from rocket_tpu.launch.loop import Looper
        from rocket_tpu.persist.checkpoint import Checkpointer
        from rocket_tpu.runtime import Runtime

        class JitProbe(Capsule):
            """Stateful so the emergency capture has real device arrays to
            stage every iteration."""

            def __init__(self):
                super().__init__(statefull=True)
                self.fn = jax.jit(lambda x: x * 2.0 + 1.0)
                self.x = jnp.ones((256, 256), jnp.float32)

            def launch(self, attrs=None):
                self.x = self.fn(self.x)

            def state_dict(self):
                return Attributes(x=self.x)

            def load_state_dict(self, state):
                self.x = state["x"]

        repeats, trials = 50, 5

        def cycle_times(armed, tag):
            runtime = Runtime()
            runtime.project_dir = str(tmp_path / tag)
            os.makedirs(runtime.project_dir, exist_ok=True)
            probe = JitProbe()
            capsules = [probe]
            ck = None
            if armed:
                # save_every=None: the durable cadence never fires — every
                # per-iteration cost measured here is the emergency stage.
                ck = Checkpointer(save_every=None, emergency_every=1,
                                  save_on_preemption=False)
                capsules.append(ck)
            looper = Looper(capsules=capsules, repeats=repeats,
                            progress=False)
            looper.bind(runtime)
            attrs = Attributes()
            looper.setup(attrs)
            looper.launch(attrs)            # warmup cycle (compiles)
            looper.reset(attrs)
            jax.block_until_ready(probe.x)
            traces_before = probe.fn._cache_size()
            out = []
            for _ in range(trials):
                t0 = time.perf_counter()
                looper.launch(attrs)
                jax.block_until_ready(probe.x)
                out.append(time.perf_counter() - t0)
                looper.reset(attrs)
            # armed or not, the loop traced ZERO new step bodies
            assert probe.fn._cache_size() == traces_before
            if ck is not None:
                # the tier really staged a capture every iteration
                assert ck._etier is not None
                assert ck._etier.captures >= repeats * trials
                assert ck._etier.staged_iter is not None
            looper.destroy(attrs)           # discards + deactivates the tier
            return out

        bare = float(np.median(cycle_times(False, "bare"))) / repeats
        armed = float(np.median(cycle_times(True, "armed"))) / repeats
        assert armed <= bare * 1.05 + 5e-4, (
            f"armed iter {armed * 1e3:.3f}ms vs bare {bare * 1e3:.3f}ms"
        )


# -- int8 KV-cache decode guard (autotuner ISSUE acceptance) ---------------
#
# The quantized cache's promise is BANDWIDTH, paid for with per-page
# quantize/dequantize inside the same compiled step.  These guards pin the
# two ways that deal can silently go bad on the host side: a shape or
# dtype leak that makes the decode round retrace per emitted token, and
# host-visible per-round overhead beyond the bf16-cache baseline.


@pytest.mark.serving
class TestQuantGuard:
    B, P, TOTAL, NDRAFT = 2, 6, 20, 3

    def _batcher(self, kv_cache_int8):
        import jax
        import numpy as np

        from rocket_tpu.models.generate import ContinuousBatcher
        from rocket_tpu.models.transformer import (
            TransformerConfig,
            TransformerLM,
        )

        cfg = TransformerConfig(
            vocab_size=64, hidden=32, n_layers=2, n_heads=4, max_seq=64,
        )
        model = TransformerLM(cfg)
        params = model.init(
            jax.random.PRNGKey(1),
            {"tokens": np.zeros((1, self.P), np.int32),
             "positions": np.zeros((1, self.P), np.int32)},
        )["params"]
        bat = ContinuousBatcher(
            model, model, params, params, total_len=self.TOTAL,
            n_draft=self.NDRAFT, kv_cache_int8=kv_cache_int8,
        )
        prompts = np.random.default_rng(13).integers(
            1, 64, size=(self.B, self.P)
        ).astype(np.int32)
        bat.start(prompts)
        return bat

    def test_zero_retraces_per_emitted_token(self, devices):
        from rocket_tpu.models.generate import _spec_round

        bat = self._batcher(kv_cache_int8=True)
        bat.step()  # compile round 0 (admits no new shapes afterwards)
        traces_after_warmup = _spec_round._cache_size()
        for _ in range(6):
            bat.step()
        assert _spec_round._cache_size() == traces_after_warmup, (
            "int8 KV decode retraced after warmup — a per-token shape or "
            "dtype leak in the quantized cache plumbing"
        )

    def test_host_overhead_vs_bf16_cache_under_5pct(self, devices):
        import numpy as np

        def round_times(kv_cache_int8, rounds=8):
            bat = self._batcher(kv_cache_int8)
            bat.step()  # compile
            out = []
            for _ in range(rounds):
                t0 = time.perf_counter()
                n_tok, done = bat.step()  # returns HOST arrays: synced
                out.append(time.perf_counter() - t0)
            return out

        bare = float(np.median(round_times(False)))
        quant = float(np.median(round_times(True)))
        assert quant <= bare * 1.05 + 5e-4, (
            f"int8 round {quant * 1e3:.3f}ms vs bf16 {bare * 1e3:.3f}ms"
        )


# -- goodput / retrace-ledger guard (ISSUE 9 acceptance) -------------------
#
# The ledger's promise mirrors the tracer's: routing every named jit edge
# through ``ledger_call`` must add ZERO jit traces and <5% host overhead
# per train iteration and per serve round while armed — the disarmed path
# is one global attribute check, and the armed warm path is two
# ``_cache_size()`` reads plus two clock reads.  These guards hold both
# hot paths to that (same tolerance discipline as the tracing guard).


@pytest.mark.goodput
class TestGoodputGuard:
    def test_train_iteration_overhead_and_trace_count(self, devices):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from rocket_tpu.core.attributes import Attributes
        from rocket_tpu.core.capsule import Capsule
        from rocket_tpu.launch.loop import Looper
        from rocket_tpu.observe.ledger import (
            arm_ledgers,
            disarm_ledgers,
            get_retrace_ledger,
            ledger_call,
        )
        from rocket_tpu.runtime import Runtime

        class JitProbe(Capsule):
            """Dispatches through the ledger chokepoint, exactly like
            every ``_AnnotatedStep`` does in a real run."""

            def __init__(self):
                super().__init__()
                self.fn = jax.jit(lambda x: x * 2.0 + 1.0)
                self.x = jnp.ones((256, 256), jnp.float32)

            def launch(self, attrs=None):
                self.x = ledger_call(self.fn, "probe/dispatch", self.x)

        # earlier suite tests (any Launcher run) may have left counts on
        # the global ledger — the bare run reads it, so start pristine
        disarm_ledgers()
        get_retrace_ledger().reset()
        repeats, trials = 50, 5

        def cycle_times(armed):
            if armed:
                arm_ledgers()
            probe = JitProbe()
            looper = Looper(capsules=[probe], repeats=repeats,
                            progress=False)
            looper.bind(Runtime())
            attrs = Attributes()
            looper.setup(attrs)
            looper.launch(attrs)            # warmup cycle (compiles)
            looper.reset(attrs)
            jax.block_until_ready(probe.x)
            traces_before = probe.fn._cache_size()
            out = []
            for _ in range(trials):
                t0 = time.perf_counter()
                looper.launch(attrs)
                jax.block_until_ready(probe.x)
                out.append(time.perf_counter() - t0)
                looper.reset(attrs)
            # armed or not, the ledgered edge traced ZERO new bodies —
            # and the sentinel never escalated a steady-state dispatch
            assert probe.fn._cache_size() == traces_before
            assert get_retrace_ledger().sentinel_dumps == 0
            return out

        try:
            bare = float(np.median(cycle_times(False))) / repeats
            armed = float(np.median(cycle_times(True))) / repeats
            # the armed run really ran under the ledger: the probe edge
            # went warm and its warmup compile was recorded
            ledger = get_retrace_ledger()
            assert "probe/dispatch" in ledger._warm
            assert any(r.name == "probe/dispatch" for r in ledger.records())
        finally:
            disarm_ledgers()
            get_retrace_ledger().reset()
        assert armed <= bare * 1.05 + 5e-4, (
            f"armed iter {armed * 1e3:.3f}ms vs bare {bare * 1e3:.3f}ms"
        )

    def test_serve_round_overhead_and_trace_count(self, devices):
        import jax
        import numpy as np

        from rocket_tpu.models.generate import ContinuousBatcher, _spec_round
        from rocket_tpu.models.transformer import (
            TransformerConfig,
            TransformerLM,
        )
        from rocket_tpu.observe.ledger import (
            arm_ledgers,
            disarm_ledgers,
            get_retrace_ledger,
        )
        from rocket_tpu.observe.trace import Tracer
        from rocket_tpu.serve import Request, ServingLoop

        B, P, TOTAL, NDRAFT = 3, 8, 24, 4

        def _lm(seed):
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

        model, params = _lm(1)
        draft, _ = _lm(1)
        _, dparams = _lm(7)
        rng = np.random.default_rng(13)
        prompts = rng.integers(1, 64, size=(B, P)).astype(np.int32)

        def factory():
            return ContinuousBatcher(
                model, draft, params, dparams,
                total_len=TOTAL, n_draft=NDRAFT, eos_token=None,
            )

        rounds = 8

        def round_times():
            loop = ServingLoop(factory, max_batch=B, queue_capacity=8,
                               watchdog_timeout=30.0,
                               tracer=Tracer(enabled=False))
            for i in range(B):
                loop.submit(Request(rid=i, prompt=prompts[i]))
            loop.run_round()  # admits + settles
            out = []
            for _ in range(rounds):
                t0 = time.perf_counter()
                loop.run_round()
                out.append(time.perf_counter() - t0)
            loop.close()
            return out

        disarm_ledgers()
        get_retrace_ledger().reset()
        bare = float(np.median(round_times()))
        traces_before = _spec_round._cache_size()
        try:
            arm_ledgers()
            armed = float(np.median(round_times()))
            ledger = get_retrace_ledger()
            # the armed rounds dispatched through the ledger without a
            # single new jit trace or sentinel escalation — the batcher's
            # per-prompt edges are exempt, the inline n_draft compiles
            # run under expect_compile, and steady-state decode is warm
            assert _spec_round._cache_size() == traces_before
            assert ledger.sentinel_dumps == 0
            assert "generate/spec_round" in ledger._warm
        finally:
            disarm_ledgers()
            get_retrace_ledger().reset()
        assert armed <= bare * 1.05 + 5e-4, (
            f"armed round {armed * 1e3:.3f}ms vs bare {bare * 1e3:.3f}ms"
        )


# -- prefix-cache tier guard (ISSUE 11 acceptance) -------------------------
#
# The kvstore's promise: a cache-hit admission dispatches ONLY warm
# executables (the suffix prefill and the import scatter compile once at
# their shape, then every same-shape hit reuses them), the armed store
# adds <5% host overhead to the decode hot path it never touches, and on
# a ~90%-shared-prefix multi-turn trace the cached TTFT p50 drops by a
# CPU-proxy fraction of the shared prefill.  On TPU the drop approaches
# the shared fraction itself (prefill dominates TTFT); on CPU the page
# import transfer and the first decode round dilute it, so the guard
# asserts >= 0.35x the shared fraction over median-of-5 trials.


@pytest.mark.kvcache
class TestKVStoreGuard:
    B, P, TOTAL, NDRAFT, PAGE = 3, 12, 24, 4, 4

    def _models(self, hidden=32, n_layers=2, max_seq=64, prompt=None):
        import jax
        import numpy as np

        from rocket_tpu.models.transformer import (
            TransformerConfig,
            TransformerLM,
        )

        prompt = self.P if prompt is None else prompt
        cfg = dict(vocab_size=64, hidden=hidden, n_layers=n_layers,
                   n_heads=4, max_seq=max_seq)
        out = []
        for seed in (1, 7):
            m = TransformerLM(TransformerConfig(**cfg))
            p = m.init(
                jax.random.PRNGKey(seed),
                {"tokens": np.zeros((1, prompt), np.int32),
                 "positions": np.zeros((1, prompt), np.int32)},
            )["params"]
            out.append((m, p))
        (model, params), (_, dparams) = out
        return model, model, params, dparams

    def _bat(self, models, total_len=None):
        from rocket_tpu.models.generate import ContinuousBatcher

        model, draft, params, dparams = models
        return ContinuousBatcher(
            model, draft, params, dparams,
            total_len=self.TOTAL if total_len is None else total_len,
            n_draft=self.NDRAFT, eos_token=None,
        )

    def test_zero_retraces_per_cache_hit_admit(self, devices):
        import numpy as np

        from rocket_tpu.models.generate import (
            _spec_import_row,
            _spec_round,
            _spec_suffix_prefill,
        )
        from rocket_tpu.serve import Completed, Request, ServingLoop
        from rocket_tpu.serve.kvstore import PrefixKVStore

        models = self._models()
        store = PrefixKVStore(page_tokens=self.PAGE,
                              capacity_bytes=1 << 30)
        rng = np.random.default_rng(13)
        prompt = rng.integers(1, 64, size=self.P).astype(np.int32)

        def serve(p):
            loop = ServingLoop(lambda: self._bat(models),
                               max_batch=self.B, queue_capacity=8,
                               kvstore=store)
            loop.submit(Request("r", p))
            (out,) = loop.run_until_idle()
            snap = loop.counters.snapshot()
            loop.close()
            assert isinstance(out, Completed)
            return snap

        serve(prompt)                       # miss: stores the pages
        snap = serve(prompt)                # first hit: compiles suffix
        assert snap["kv_hits"] == 1
        warm = (_spec_suffix_prefill._cache_size(),
                _spec_import_row._cache_size(),
                _spec_round._cache_size())
        for _ in range(3):                  # every further same-shape hit
            snap = serve(prompt)
            assert snap["kv_hits"] == 1
        assert (_spec_suffix_prefill._cache_size(),
                _spec_import_row._cache_size(),
                _spec_round._cache_size()) == warm, (
            "a cache-hit admission traced a new executable after warmup "
            "— a shape or dtype leak in the suffix-prefill/import path"
        )

    def test_decode_round_overhead_vs_cache_off_under_5pct(self, devices):
        import numpy as np

        from rocket_tpu.serve import Request, ServingLoop
        from rocket_tpu.serve.kvstore import PrefixKVStore

        models = self._models()
        rng = np.random.default_rng(13)
        prompt = rng.integers(1, 64, size=self.P).astype(np.int32)

        def round_times(store, rounds=8):
            loop = ServingLoop(lambda: self._bat(models),
                               max_batch=self.B, queue_capacity=8,
                               kvstore=store)
            loop.submit(Request("r", prompt))
            loop.run_round()                # admit + compile
            out = []
            for _ in range(rounds):
                t0 = time.perf_counter()
                loop.run_round()
                out.append(time.perf_counter() - t0)
            loop.run_until_idle()
            loop.close()
            return out

        bare = float(np.median(round_times(None)))
        armed = float(np.median(round_times(
            PrefixKVStore(page_tokens=self.PAGE, capacity_bytes=1 << 30))))
        assert armed <= bare * 1.05 + 5e-4, (
            f"armed round {armed * 1e3:.3f}ms vs bare {bare * 1e3:.3f}ms"
        )

    def test_cached_ttft_p50_drop_meets_cpu_proxy(self, devices):
        import numpy as np

        from rocket_tpu.serve import Request, ServingLoop
        from rocket_tpu.serve.kvstore import PrefixKVStore

        # CPU-proxy demo-trace shape: long prompts so prefill dominates
        # the dispatch (224 of 256 prompt tokens shared = 87.5%)
        PROMPT, PAGE, SHARED, NEW, TURNS = 256, 32, 224, 8, 7
        frac = SHARED / PROMPT
        models = self._models(hidden=128, max_seq=PROMPT + 16,
                              prompt=PROMPT)
        rng = np.random.default_rng(5)
        header = rng.integers(1, 64, size=SHARED)

        def turn(t):
            tail = np.random.default_rng(100 + t).integers(
                1, 64, size=PROMPT - SHARED)
            return np.concatenate([header, tail]).astype(np.int32)

        def run(store):
            t0 = time.perf_counter()
            loop = ServingLoop(
                lambda: self._bat(models, total_len=PROMPT + NEW),
                max_batch=1, queue_capacity=4,
                clock=lambda: time.perf_counter() - t0, kvstore=store)
            for t in range(TURNS):
                loop.submit(Request(rid=t, prompt=turn(t)))
                loop.run_until_idle(max_rounds=1_000_000)
            p50 = loop.latency.summary()["ttft_ms/p50"]
            loop.close()
            return p50

        warm = PrefixKVStore(page_tokens=PAGE, capacity_bytes=1 << 30)
        run(warm)                           # compile both paths
        run(warm)
        colds, cacheds = [], []
        for _ in range(3):
            colds.append(run(None))
            cacheds.append(run(PrefixKVStore(page_tokens=PAGE,
                                             capacity_bytes=1 << 30)))
        cold = float(np.median(colds))
        cached = float(np.median(cacheds))
        drop = 1.0 - cached / cold
        assert drop >= 0.35 * frac, (
            f"cached TTFT p50 {cached:.1f}ms vs cold {cold:.1f}ms — drop "
            f"{drop:.0%} under the CPU proxy of the {frac:.0%} shared "
            f"prefill fraction (expected >= {0.35 * frac:.0%})"
        )


@pytest.mark.trainserve
class TestSwapGuard:
    """Live weight hot-swap guard (ISSUE 17 acceptance): the whole point
    of swapping in place is that it beats tearing the replica down — the
    swap must add ZERO jit traces (params are a jit argument: same
    shapes/dtypes/shardings), and its wall time, charged to the ``swap``
    goodput bucket, must stay well under a cold loop rebuild."""

    def test_swap_zero_retrace_and_beats_cold_rebuild(self, devices,
                                                      tmp_path):
        import numpy as np

        from rocket_tpu.models.generate import _spec_round
        from rocket_tpu.serve.types import Request
        from rocket_tpu.testing import workers as tw

        path = tw.save_tiny_publication(str(tmp_path), step=10,
                                        seed_target=5)

        t0 = time.perf_counter()
        loop = tw.build_tiny_loop()
        cold_build_s = time.perf_counter() - t0

        def serve_one(rid):
            loop.submit(Request(rid=rid,
                                prompt=np.arange(1, 7, dtype=np.int32),
                                max_new_tokens=8))
            for _ in range(200):
                loop.run_round()
                if loop.drain_results():
                    return

        serve_one("warm")           # warm every decode shape
        traces_before = _spec_round._cache_size()
        assert loop.swap_weights(path)
        serve_one("post")
        assert _spec_round._cache_size() == traces_before, (
            "hot-swap retraced — the swapped params changed a jit "
            "signature (shape/dtype/sharding leak)"
        )
        swap_s = loop.counters.swap_ms_total / 1e3
        assert 0.0 < swap_s < 0.5 * cold_build_s, (
            f"swap {swap_s:.3f}s vs cold rebuild {cold_build_s:.3f}s — "
            "the swap path is paying a rebuild-class cost"
        )


@pytest.mark.tenants
class TestTenantGuard:
    """Batch preemption guard (ISSUE 18 acceptance): \"cheap\" means the
    park-and-resume machinery is pure host work — exporting a victim's
    KV pages, parking the ticket, and re-admitting it later must reuse
    the admit/decode shapes the loop already compiled.  A steady-state
    preempt/resume cycle adds ZERO jit traces to the decode round."""

    def test_preempt_resume_zero_retrace(self, devices):
        import numpy as np

        from rocket_tpu.models.generate import _spec_round
        from rocket_tpu.serve.types import Request
        from rocket_tpu.testing import workers as tw

        loop = tw.build_tiny_loop(max_batch=2, kvstore_page_tokens=3)
        rng = np.random.default_rng(23)
        prompts = rng.integers(1, tw.VOCAB,
                               size=(8, tw.P)).astype(np.int32)

        def cycle(tag, i0):
            # a batch row decoding next to a standard row; two
            # interactive arrivals evict the batch row at the round
            # boundary, and run-to-idle parks AND resumes it
            assert loop.submit(Request(rid=f"{tag}-bat",
                                       prompt=prompts[i0],
                                       slo_class="batch")) is None
            assert loop.submit(Request(rid=f"{tag}-std",
                                       prompt=prompts[i0 + 1])) is None
            loop.run_round()
            for j in (2, 3):
                assert loop.submit(Request(rid=f"{tag}-i{j}",
                                           prompt=prompts[i0 + j],
                                           slo_class="interactive"
                                           )) is None
            res = loop.run_until_idle()
            assert sorted(r.rid for r in res) == sorted(
                f"{tag}-{s}" for s in ("bat", "std", "i2", "i3"))

        try:
            cycle("warm", 0)        # compiles every shape involved
            assert loop.counters.preempted >= 1
            assert loop.counters.resumed >= 1
            traces = _spec_round._cache_size()
            pre, res = loop.counters.preempted, loop.counters.resumed
            cycle("run", 4)         # steady state: same shapes again
            assert loop.counters.preempted > pre
            assert loop.counters.resumed > res
            assert _spec_round._cache_size() == traces, (
                "preempt/resume retraced — parking or re-admitting a "
                "batch row changed a jit signature (shape/dtype leak "
                "in the KV export/import path)"
            )
        finally:
            loop.close()


class TestZeroGuard:
    """ZeRO-1 guard (ISSUE 12): the sharding plan's per-device optimizer
    bytes must drop >= (N-1)/N on an N-way data axis, and turning
    ``zero_stage=1`` on must not add jit retraces to the step loop."""

    def test_7b_adam_optimizer_bytes_drop(self, devices):
        """The 7B-Adam memory plan: zero_stage=1 divides the per-device
        optimizer bytes by the data-axis size (a few replicated scalars —
        optax step counts — are all that remains un-sharded)."""
        import jax
        import jax.numpy as jnp
        import optax

        import rocket_tpu as rt
        from rocket_tpu.engine.adapter import FlaxModel
        from rocket_tpu.engine.precision import Policy
        from rocket_tpu.engine.state import TrainState, memory_plan
        from rocket_tpu.models.transformer import (
            TransformerConfig, TransformerLM,
        )
        from rocket_tpu.parallel.mesh import MeshSpec
        from rocket_tpu.parallel.sharding import specs_for_state

        N = 8
        cfg = TransformerConfig.llama2_7b(scan_layers=True)
        runtime = rt.Runtime(mesh=MeshSpec(data=N).build(devices))
        policy = Policy.from_string("bf16_full")
        adapter = FlaxModel(TransformerLM(cfg))
        adapter.configure(runtime.mesh, runtime.rules)
        adapter.apply_policy(policy)
        tx = optax.adamw(1e-5)

        def init_fn():
            batch = {"tokens": jnp.zeros((N, 512), jnp.int32)}
            params, mutable = adapter.init_variables(
                jax.random.PRNGKey(0), batch)
            params = policy.cast_to_param(params)
            return TrainState.create(params, tx, mutable=mutable)

        abstract = jax.eval_shape(init_fn)
        param_specs = adapter.partition_specs(abstract.params, runtime.rules)
        repl = specs_for_state(
            runtime.mesh, abstract, param_specs=param_specs, zero_stage=0)
        zero = specs_for_state(
            runtime.mesh, abstract, param_specs=param_specs, zero_stage=1)
        repl_opt = memory_plan(
            abstract, repl.state_specs, runtime.mesh)["opt_bytes"]
        zero_opt = memory_plan(
            abstract, zero.state_specs, runtime.mesh)["opt_bytes"]
        # 7B Adam: ~25GB of replicated moments to begin with
        assert repl_opt > 20 * (1 << 30)
        # >= (N-1)/N drop == the shard is <= 1/N (+ scalar-count slack)
        assert zero_opt <= repl_opt / N + 1024, (
            f"zero_stage=1 optimizer shard {zero_opt / (1 << 30):.2f} GB "
            f"vs replicated {repl_opt / (1 << 30):.2f} GB — expected a "
            f">= {(N - 1) / N:.0%} drop"
        )
        # stage 3 divides the PARAM storage bytes by N as well
        s3 = specs_for_state(
            runtime.mesh, abstract, param_specs=param_specs, zero_stage=3)
        repl_param = memory_plan(
            abstract, repl.state_specs, runtime.mesh)["param_bytes"]
        s3_param = memory_plan(
            abstract, s3.state_specs, runtime.mesh)["param_bytes"]
        assert s3_param <= repl_param / N + (1 << 20), (
            f"zero_stage=3 param storage {s3_param / (1 << 30):.2f} GB vs "
            f"replicated {repl_param / (1 << 30):.2f} GB — expected a "
            f">= {(N - 1) / N:.0%} drop"
        )
        # offload books the optimizer shard against the host tier instead
        off = memory_plan(
            abstract, s3.state_specs, runtime.mesh, zero_offload=True)
        assert off["opt_bytes"] == 0
        assert off["host_opt_bytes"] > 0
        assert off["total_bytes"] == off["param_bytes"] + off["other_bytes"]

    def test_zero_stage1_no_retrace_per_step(self, devices):
        """The ZeRO constraints live INSIDE the jitted step: stepping N
        times adds ZERO traces over the unsharded step's count (one trace
        per distinct input-sharding signature — the first output's
        XLA-normalized specs cost one warmup retrace on both paths), and
        the steady-state count never grows with further steps."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from rocket_tpu.engine import Objective, TrainState, build_train_step
        from rocket_tpu.parallel.mesh import MeshSpec
        from rocket_tpu.parallel.sharding import specs_for_state

        mesh = MeshSpec(data=4, tensor=2).build(devices)
        params = {
            "w1": jnp.ones((32, 64), jnp.float32),
            "w2": jnp.ones((64, 32), jnp.float32),
        }
        pspecs = {"w1": P(None, "tensor"), "w2": P("tensor", None)}
        tx = optax.adamw(1e-2)
        abstract = jax.eval_shape(lambda: TrainState.create(params, tx))

        def apply_fn(p, mutable, rng, batch, train):
            out = dict(batch)
            out["pred"] = jnp.tanh(batch["x"] @ p["w1"]) @ p["w2"]
            return out, mutable

        loss = Objective("mse", lambda b: jnp.mean((b["pred"] - b["y"]) ** 2))
        batch_sh = NamedSharding(mesh, P("data"))

        def trace_counts(zero_stage):
            plan = specs_for_state(
                mesh, abstract, param_specs=pspecs, zero_stage=zero_stage)
            steps = build_train_step(
                apply_fn, [loss], tx,
                shard_plan=plan if zero_stage else None)
            state = jax.device_put(
                TrainState.create(params, tx), plan.state_shardings)
            rng = np.random.default_rng(0)
            for _ in range(2):  # warmup: first output normalizes shardings
                batch = {
                    "x": jax.device_put(jnp.asarray(
                        rng.normal(size=(8, 32)), jnp.float32), batch_sh),
                    "y": jax.device_put(jnp.asarray(
                        rng.normal(size=(8, 32)), jnp.float32), batch_sh),
                }
                state, _ = steps["sync"](state, batch)
            warm = steps["sync"]._cache_size()
            for _ in range(5):
                batch = {
                    "x": jax.device_put(jnp.asarray(
                        rng.normal(size=(8, 32)), jnp.float32), batch_sh),
                    "y": jax.device_put(jnp.asarray(
                        rng.normal(size=(8, 32)), jnp.float32), batch_sh),
                }
                state, _ = steps["sync"](state, batch)
            return warm, steps["sync"]._cache_size()

        base_warm, base_final = trace_counts(0)
        for stage in (1, 2, 3):
            zero_warm, zero_final = trace_counts(stage)
            assert zero_final == zero_warm, (
                f"zero_stage={stage} retraces per step"
            )
            # <= not ==: stages whose outputs carry explicit shard-plan
            # constraints skip the baseline's one-time output-sharding
            # normalization retrace, so they can legitimately trace FEWER
            assert zero_final <= base_final, (
                f"zero_stage={stage} traced {zero_final}x "
                f"vs baseline {base_final}x"
            )


class TestPipelineGuard:
    """Pipeline-schedule guard (ISSUE 13): interleaved(v=2)'s MEASURED
    bubble fraction — read back from the goodput ledger's per-stage
    ``pipeline/bubble/stage<p>`` buckets, not the analytic plan — must sit
    strictly below GPipe's on the same lockstep proxy run, and the bench
    record's memory columns must realize the 1F1B ≤P residency bound."""

    def test_interleaved_measured_bubble_below_gpipe(self, bench):
        measured = bench.measure_pipeline_schedules()
        gp_b = measured["gpipe"]["bubble_fraction"]
        il_b = measured["interleaved"]["bubble_fraction"]
        assert 0.0 < il_b < gp_b, measured
        # the buckets themselves were populated per stage (the fleet
        # metrics export reads these same keys)
        for sched, cols in measured.items():
            waits = cols["stage_wait_s"]
            assert len(waits) == bench.PIPELINE_PROXY["n_stages"]
            assert all(w >= 0.0 for w in waits) and sum(waits) > 0.0, (
                sched, waits,
            )
        # analytic columns ride along and agree with the ordering
        assert (measured["interleaved"]["bubble_fraction_plan"]
                < measured["gpipe"]["bubble_fraction_plan"])
        assert measured["1f1b"]["live_microbatches"] <= 2
        assert measured["gpipe"]["live_microbatches"] == (
            bench.PIPELINE_PROXY["n_micro"]
        )

    def test_pipeline_record_memory_columns(self, bench):
        """The mem_* columns come from memory_plan() on the pipelined
        proxy transformer; 1F1B's live-activation bound is P/M of
        GPipe's stash on the same config."""
        gp = bench._pipeline_memory_columns("gpipe", 1)
        fb = bench._pipeline_memory_columns("1f1b", 1)
        for cols in (gp, fb):
            assert cols["mem_param_bytes"] > 0
            assert cols["mem_opt_bytes"] > cols["mem_param_bytes"]
            assert cols["mem_total_bytes"] >= (
                cols["mem_param_bytes"] + cols["mem_opt_bytes"]
            )
        # state bytes identical across schedules; only residency moves
        assert gp["mem_total_bytes"] == fb["mem_total_bytes"]
        # P=2, M=4: 1F1B holds min(P, M)=2 of GPipe's 4 live microbatches
        assert 2 * fb["mem_live_activation_bytes"] == (
            gp["mem_live_activation_bytes"]
        )
