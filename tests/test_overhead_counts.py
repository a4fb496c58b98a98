"""What the hot paths cost, in counts: arming a feature (the tracer, the
goodput ledger, request contexts, the emergency tier, the int8 cache, the
prefix store, a weight swap, preemption, ZeRO) adds no jit trace, no
blocking device-to-host read and no dispatch to a train iteration or a
serve round.  These were wall-clock guards ("armed <= bare x 1.05") while
there was no chip; a CPU's clock measures the machine's load, so each now
asserts the count its time stood for, from what the program itself counts
(``_cache_size()``, ``ServeCounters.host_fetches`` through ``HostReads``,
the ``serve/dispatch`` and ``train/step_dispatch`` spans, the store's
hit-token counters).  The times are ``benchmark/run.py``'s, on the chip."""

import os

import pytest

B, P, TOTAL, NDRAFT = 3, 8, 24, 4


def _tiny_lm(seed, prompt=P, hidden=32, max_seq=64):
    import jax
    import numpy as np

    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM

    model = TransformerLM(TransformerConfig(
        vocab_size=64, hidden=hidden, n_layers=2, n_heads=4,
        max_seq=max_seq))
    params = model.init(
        jax.random.PRNGKey(seed),
        {"tokens": np.zeros((1, prompt), np.int32),
         "positions": np.zeros((1, prompt), np.int32)},
    )["params"]
    return model, params


def _tiny_pair(**kw):
    """(model, draft, params, dparams): one architecture, two seeds."""
    model, params = _tiny_lm(1, **kw)
    _, dparams = _tiny_lm(7, **kw)
    return model, model, params, dparams


def _batcher(pair, total_len=TOTAL, **kw):
    from rocket_tpu.models.generate import ContinuousBatcher

    return ContinuousBatcher(*pair, total_len=total_len, n_draft=NDRAFT,
                             eos_token=None, **kw)


def _prompts(n=B, length=P):
    import numpy as np

    return np.random.default_rng(13).integers(
        1, 64, size=(n, length)).astype(np.int32)


def _span_count(tracer, name):
    return sum(1 for e in tracer.events() if e[1] == name)


def _serve_rounds(pair, tracer, rounds=8, n_requests=B, **loop_kw):
    """Admit ``n_requests``, settle, then run ``rounds`` decode rounds;
    returns the blocking host reads and the dispatches those rounds
    made, as the program counted them."""
    from rocket_tpu.serve import Request, ServingLoop

    loop = ServingLoop(lambda: _batcher(pair), max_batch=B,
                       queue_capacity=8, watchdog_timeout=30.0,
                       tracer=tracer, **loop_kw)
    prompts = _prompts()
    for i in range(n_requests):
        loop.submit(Request(rid=i, prompt=prompts[i]))
    loop.run_round()  # admits + settles
    fetches = loop.counters.host_fetches
    dispatches = _span_count(tracer, "serve/dispatch")
    for _ in range(rounds):
        loop.run_round()
    fetches = loop.counters.host_fetches - fetches
    dispatches = _span_count(tracer, "serve/dispatch") - dispatches
    loop.run_until_idle()  # terminal flow events emit here
    loop.close()
    return fetches, dispatches


# -- tracing (ISSUE 4 acceptance) ------------------------------------------
#
# observe.trace's promise is "zero device syncs, lock-light, cheap enough
# to leave armed in production".  What a test can hold it to: armed, a
# train iteration and a serve round trace zero additional jitted bodies
# and really record their spans.


@pytest.mark.tracing
class TestTracingOverheadGuard:
    def test_train_iteration_trace_count(self, devices):
        import jax
        import jax.numpy as jnp

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

        def cycles(tracing):
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
            get_tracer().clear()
            for _ in range(trials):
                looper.launch(attrs)
                jax.block_until_ready(probe.x)
                looper.reset(attrs)
            # armed or not, the loop traced ZERO new step bodies
            assert probe.fn._cache_size() == traces_before
            return _span_count(get_tracer(), "JitProbe.launch")

        try:
            assert cycles(False) == 0
            # armed, every iteration's capsule span reached the ring
            assert cycles(True) == repeats * trials
        finally:
            disarm()
            get_tracer().clear()

    def test_serve_round_trace_count(self, devices):
        from rocket_tpu.models.generate import _spec_round
        from rocket_tpu.observe.trace import Tracer

        pair = _tiny_pair()
        _serve_rounds(pair, Tracer(enabled=False))
        traces_before = _spec_round._cache_size()
        armed = Tracer(capacity=1024, enabled=True)
        _, dispatches = _serve_rounds(pair, armed)
        # arming recorded real spans without tracing a single new body
        assert _spec_round._cache_size() == traces_before
        assert _span_count(armed, "serve/round") >= 8
        assert dispatches == 8


# -- distributed tracing (ISSUE 19 acceptance) -----------------------------
#
# Stamping a TraceContext on every request and emitting its flow chain
# (s -> t... -> f) at sampling rate 1.0 is pure host bookkeeping: armed,
# a serve round traces ZERO new jitted bodies and reads the device no
# more often than the unstamped loop.


@pytest.mark.tracing
class TestTraceCtxGuard:
    def test_ctx_stamped_round_reads_and_trace_count(self, devices):
        from rocket_tpu.models.generate import _spec_round
        from rocket_tpu.observe.trace import (
            Tracer,
            get_sampling,
            set_sampling,
        )

        pair = _tiny_pair()
        rate, seed = get_sampling()
        try:
            set_sampling(0.0, 0)
            bare_fetches, _ = _serve_rounds(pair, Tracer(enabled=True))
            traces_before = _spec_round._cache_size()
            set_sampling(1.0, 0)  # every request stamped AND flow-traced
            armed_tracer = Tracer(capacity=4096, enabled=True)
            fetches, _ = _serve_rounds(pair, armed_tracer)
        finally:
            set_sampling(rate, seed)
        # ctx stamping + flow emission traced zero new jitted bodies...
        assert _spec_round._cache_size() == traces_before
        # ...read the device exactly as often (the time a stamped round
        # could add beyond python bookkeeping is a blocking read)...
        assert fetches == bare_fetches
        # ...while really recording every request's full flow chain
        phases = [f.get("ph") for k, n, _ts, _d, _t, f
                  in armed_tracer.events()
                  if k == "F" and n == "serve/request"]
        assert phases.count("s") == B and phases.count("f") == B
        assert "t" in phases


# -- async loop (ISSUE 5 acceptance) ---------------------------------------
#
# The non-blocking Looper's promise: with readback deferred k iterations
# no capsule waits on the device while the iteration is dispatched — the
# stretch ``Looper.last_dispatch_gap_ms`` times — where the synchronous
# loop's reader floats the fresh loss, one blocking read an iteration.
# The dispatch gap was timed to show that; what it stood for is the
# count of device values read inside the dispatch.


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

    def _build(self, lag, reader, tracing=False):
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
        import jax

        from rocket_tpu.parallel.mesh import data_parallel_mesh

        looper.bind(rt.Runtime(mesh=data_parallel_mesh(jax.devices()[:1]),
                               tracing=tracing))
        attrs = rt.Attributes()
        looper.setup(attrs)
        return looper, model, attrs

    @staticmethod
    def _reader(lagged):
        import jax

        import rocket_tpu as rt

        class Reader(rt.Capsule):
            """Floats a loss every iteration, as a progress bar or a
            tracker does: THIS iteration's (``lagged=False``, the classic
            loop) or the one the lag window materialized (``True``).
            Counts the reads whose operand was still a device array —
            each is a wait on the device inside the dispatch."""

            def __init__(self):
                super().__init__(statefull=False, priority=300)
                self.seen = 0
                self.device_reads = 0

            def launch(self, attrs=None):
                if attrs is None or attrs.looper is None:
                    return
                logs = attrs.looper.get("lagged_logs") if lagged \
                    else attrs.step_logs
                if logs is None:
                    return
                self.device_reads += isinstance(logs["loss"], jax.Array)
                float(logs["loss"])
                self.seen += 1

        return Reader()

    def _cycles(self, lag, reader, trials=3, tracing=False):
        import jax

        looper, model, attrs = self._build(lag, reader, tracing=tracing)
        looper.launch(attrs)  # warmup cycle (compiles)
        looper.reset(attrs)
        jax.block_until_ready(model.state.params)
        for _ in range(trials):
            looper.launch(attrs)
            looper.reset(attrs)
            jax.block_until_ready(model.state.params)
        # the async plumbing traced ZERO new step bodies across cycles
        assert model._steps["sync"]._cache_size() == 1
        return looper

    def test_async_dispatch_reads_no_device_value(self, devices):
        # Was "async dispatch gap < 0.5 x sync + 0.3 ms".  The gap is the
        # host time of ``_launch_children``; the synchronous loop's is
        # long because its reader floats a device scalar there, once an
        # iteration.  So: blocking reads inside the dispatch, 1 against 0.
        sync_reader = self._reader(lagged=False)
        self._cycles(0, sync_reader)
        lagged_reader = self._reader(lagged=True)
        looper = self._cycles(2, lagged_reader)
        # both variants consumed a loss every iteration they had one (the
        # third push pops the first snapshot, read an iteration later)
        assert sync_reader.seen == 4 * self.REPEATS
        assert lagged_reader.seen == 4 * (self.REPEATS - 3)
        assert sync_reader.device_reads == sync_reader.seen
        assert lagged_reader.device_reads == 0
        # the gap the program reports is fed once an iteration
        assert looper._gap_count == self.REPEATS  # reset each cycle
        assert looper.last_dispatch_gap_ms is not None

    def test_lag_machinery_adds_no_dispatch_and_stages_scalars(self, devices):
        # Was "lagged iteration <= 1.5 x synchronous".  The regressions
        # that bound was for, as its comment said: an extra dispatch an
        # iteration, or a param-tree copy through the lag ring.  So: step
        # dispatches a cycle equal with the lag on and off (the
        # ``train/step_dispatch`` spans), and what the ring holds is the
        # step's scalar logs.
        import numpy as np

        from rocket_tpu.observe.trace import disarm, get_tracer

        import rocket_tpu as rt

        class Peek(rt.Capsule):
            """Looks into the lag ring while the cycle runs."""

            def __init__(self):
                super().__init__(statefull=False, priority=300)
                self.looper = None
                self.depths, self.sizes = [], set()

            def launch(self, attrs=None):
                window = self.looper._lag_window
                if window is None:
                    return
                self.depths.append(len(window))
                self.sizes |= {int(np.size(v)) for logs in window._window
                               for v in logs.values()}

        def dispatches(lag):
            peek = Peek()
            looper, _, attrs = self._build(lag, peek, tracing=True)
            peek.looper = looper
            get_tracer().clear()
            looper.launch(attrs)
            looper.reset(attrs)
            return peek, _span_count(get_tracer(), "train/step_dispatch")

        try:
            _, bare = dispatches(0)
            peek, armed = dispatches(2)
        finally:
            disarm()
            get_tracer().clear()
        assert bare == armed == self.REPEATS
        # the ring never holds more than `lag` snapshots of the step's
        # logs, every leaf one number: no parameter rides through it
        assert max(peek.depths) == 2
        assert peek.sizes == {1}


# -- emergency tier (ISSUE 8 acceptance) -----------------------------------
#
# Staging a host snapshot every ``emergency_every`` iterations is an ASYNC
# readback — zero extra jit traces on the happy path, the flush-to-disk
# cost paid only inside a SIGTERM grace window.


@pytest.mark.elastic
class TestElasticGuard:
    def test_emergency_capture_count_and_trace_count(self, devices,
                                                     tmp_path):
        import jax
        import jax.numpy as jnp

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

        def cycles(armed, tag):
            runtime = Runtime()
            runtime.project_dir = str(tmp_path / tag)
            os.makedirs(runtime.project_dir, exist_ok=True)
            probe = JitProbe()
            capsules = [probe]
            ck = None
            if armed:
                # save_every=None: the durable cadence never fires — all
                # the armed loop adds is the emergency stage.
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
            for _ in range(trials):
                looper.launch(attrs)
                jax.block_until_ready(probe.x)
                looper.reset(attrs)
            # armed or not, the loop traced ZERO new step bodies
            assert probe.fn._cache_size() == traces_before
            if ck is not None:
                # the tier really staged a capture every iteration
                assert ck._etier is not None
                assert ck._etier.captures >= repeats * trials
                assert ck._etier.staged_iter is not None
            looper.destroy(attrs)           # discards + deactivates the tier

        cycles(False, "bare")
        cycles(True, "armed")


# -- int8 KV-cache decode ---------------------------------------------------
#
# The quantized cache's promise is BANDWIDTH, paid for with per-page
# quantize/dequantize inside the same compiled step.  Two ways that deal
# can silently go bad on the host side: a shape or dtype leak that makes
# the decode round retrace per emitted token, and a round that reads the
# device or dispatches more often than the bf16-cache round.


@pytest.mark.serving
class TestQuantGuard:
    def _start(self, kv_cache_int8):
        from rocket_tpu.models.generate import HostReads
        from rocket_tpu.observe.trace import Tracer
        from rocket_tpu.serve.metrics import ServeCounters

        model, params = _tiny_lm(1, prompt=6)
        bat = _batcher((model, model, params, params), total_len=20,
                       kv_cache_int8=kv_cache_int8)
        bat.reads = HostReads(Tracer(enabled=True), ServeCounters())
        bat.start(_prompts(2, 6))
        return bat

    def test_zero_retraces_per_emitted_token(self, devices):
        from rocket_tpu.models.generate import _spec_round

        bat = self._start(kv_cache_int8=True)
        bat.step()  # compile round 0 (admits no new shapes afterwards)
        traces_after_warmup = _spec_round._cache_size()
        for _ in range(6):
            bat.step()
        assert _spec_round._cache_size() == traces_after_warmup, (
            "int8 KV decode retraced after warmup — a per-token shape or "
            "dtype leak in the quantized cache plumbing"
        )

    def test_round_reads_and_dispatches_equal_bf16_cache(self, devices):
        # Was "int8 round <= 1.05 x bf16 round" on the host's clock.  The
        # kernels' time is the chip's to tell; what the host can add is a
        # blocking read or a dispatch a round, and `HostReads` counts
        # both: equal with the int8 cache on and off.
        def counts(kv_cache_int8, rounds=8):
            bat = self._start(kv_cache_int8)
            bat.step()  # compile
            fetches = bat.reads.counters.host_fetches
            dispatches = _span_count(bat.reads.tracer, "serve/dispatch")
            for _ in range(rounds):
                bat.step()  # returns HOST arrays: two reads
            return (bat.reads.counters.host_fetches - fetches,
                    _span_count(bat.reads.tracer, "serve/dispatch")
                    - dispatches)

        assert counts(True) == counts(False) == (16, 8)


# -- goodput / retrace ledger (ISSUE 9 acceptance) -------------------------
#
# Routing every named jit edge through ``ledger_call`` adds ZERO jit
# traces per train iteration and per serve round while armed, and the
# sentinel never escalates a steady-state dispatch.


@pytest.mark.goodput
class TestGoodputGuard:
    def test_train_iteration_trace_count(self, devices):
        import jax
        import jax.numpy as jnp

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

        def cycles(armed):
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
            for _ in range(trials):
                looper.launch(attrs)
                jax.block_until_ready(probe.x)
                looper.reset(attrs)
            # armed or not, the ledgered edge traced ZERO new bodies —
            # and the sentinel never escalated a steady-state dispatch
            assert probe.fn._cache_size() == traces_before
            assert get_retrace_ledger().sentinel_dumps == 0

        try:
            cycles(False)
            cycles(True)
            # the armed run really ran under the ledger: the probe edge
            # went warm and its warmup compile was recorded
            ledger = get_retrace_ledger()
            assert "probe/dispatch" in ledger._warm
            assert any(r.name == "probe/dispatch" for r in ledger.records())
        finally:
            disarm_ledgers()
            get_retrace_ledger().reset()

    def test_serve_round_reads_and_trace_count(self, devices):
        from rocket_tpu.models.generate import _spec_round
        from rocket_tpu.observe.ledger import (
            arm_ledgers,
            disarm_ledgers,
            get_retrace_ledger,
        )
        from rocket_tpu.observe.trace import Tracer

        pair = _tiny_pair()
        disarm_ledgers()
        get_retrace_ledger().reset()
        bare_fetches, bare_dispatches = _serve_rounds(
            pair, Tracer(enabled=True))
        traces_before = _spec_round._cache_size()
        try:
            arm_ledgers()
            fetches, dispatches = _serve_rounds(pair, Tracer(enabled=True))
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
        # and read the device and dispatched exactly as often
        assert (fetches, dispatches) == (bare_fetches, bare_dispatches)


# -- prefix-cache tier (ISSUE 11 acceptance) -------------------------------
#
# The kvstore's promise: a cache-hit admission dispatches ONLY warm
# executables (the suffix prefill and the import scatter compile once at
# their shape, then every same-shape hit reuses them), the armed store
# leaves the decode round it never touches as it was, and on a
# shared-prefix multi-turn trace a hit prefills the prompt less the
# matched pages.


@pytest.mark.kvcache
class TestKVStoreGuard:
    PAGE = 4

    def _store(self, page=None):
        from rocket_tpu.serve.kvstore import PrefixKVStore

        return PrefixKVStore(page_tokens=page or self.PAGE,
                             capacity_bytes=1 << 30)

    def test_zero_retraces_per_cache_hit_admit(self, devices):
        from rocket_tpu.models.generate import (
            _spec_import_row,
            _spec_round,
            _spec_suffix_prefill,
        )
        from rocket_tpu.serve import Completed, Request, ServingLoop

        pair = _tiny_pair(prompt=12)
        store = self._store()
        prompt = _prompts(1, 12)[0]

        def serve(p):
            loop = ServingLoop(lambda: _batcher(pair),
                               max_batch=B, queue_capacity=8,
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

    def test_decode_round_reads_and_dispatches_equal_cache_off(self,
                                                               devices):
        # Was "armed round <= 1.05 x bare round".  The store works at
        # admission and at completion; a decode round it slows would be
        # one it makes read the device or dispatch once more.
        from rocket_tpu.observe.trace import Tracer

        pair = _tiny_pair(prompt=12)
        bare_fetches, bare_dispatches = _serve_rounds(
            pair, Tracer(enabled=True), n_requests=1)
        fetches, dispatches = _serve_rounds(
            pair, Tracer(enabled=True), n_requests=1, kvstore=self._store())
        assert (fetches, dispatches) == (bare_fetches, bare_dispatches)
        assert dispatches == 8

    def test_cache_hit_prefills_the_prompt_less_the_matched_pages(
            self, devices):
        # Was "cached TTFT p50 drops by >= 0.35 x the shared fraction".
        # The first token comes sooner because fewer tokens go through
        # the model at admission: on a hit `_spec_suffix_prefill` takes
        # the prompt less the matched pages.  The `serve/admit` span
        # carries both numbers and the counters sum the second.
        import numpy as np

        from rocket_tpu.observe.trace import Tracer
        from rocket_tpu.serve import Request, ServingLoop

        # 56 of 64 prompt tokens shared (87.5 %), seven pages of eight
        PROMPT, PAGE, SHARED, NEW, TURNS = 64, 8, 56, 8, 7
        pair = _tiny_pair(prompt=PROMPT, max_seq=PROMPT + 16)
        header = np.random.default_rng(5).integers(1, 64, size=SHARED)

        def turn(t):
            tail = np.random.default_rng(100 + t).integers(
                1, 64, size=PROMPT - SHARED)
            return np.concatenate([header, tail]).astype(np.int32)

        def run(store):
            tracer = Tracer(capacity=4096, enabled=True)
            loop = ServingLoop(
                lambda: _batcher(pair, total_len=PROMPT + NEW),
                max_batch=1, queue_capacity=4, kvstore=store,
                tracer=tracer)
            for t in range(TURNS):
                loop.submit(Request(rid=t, prompt=turn(t)))
                loop.run_until_idle(max_rounds=1_000_000)
            loop.close()
            admits = [e[5] for e in tracer.events() if e[1] == "serve/admit"]
            prefilled = [a["prompt_len"] - a["kv_hit_tokens"]
                         for a in admits]
            return loop.counters, prefilled

        cold, cold_prefilled = run(None)
        assert cold.kv_hits == 0
        assert cold_prefilled == [PROMPT] * TURNS
        hot, prefilled = run(self._store(PAGE))
        # every turn after the first matches the whole shared header
        assert hot.kv_hits == TURNS - 1
        assert hot.kv_hit_tokens == (TURNS - 1) * SHARED
        assert prefilled == [PROMPT] + [PROMPT - SHARED] * (TURNS - 1)


@pytest.mark.trainserve
class TestSwapGuard:
    """Live weight hot-swap guard (ISSUE 17 acceptance): the whole point
    of swapping in place is that it beats tearing the replica down — the
    swap adds ZERO jit traces (params are a jit argument: same
    shapes/dtypes/shardings) and builds no batcher, so nothing a cold
    rebuild pays for (a factory call, a compile) happens."""

    def test_swap_zero_retrace_and_no_rebuild(self, devices, tmp_path):
        import numpy as np

        from rocket_tpu.models.generate import _spec_round
        from rocket_tpu.serve.types import Request
        from rocket_tpu.testing import workers as tw

        path = tw.save_tiny_publication(str(tmp_path), step=10,
                                        seed_target=5)
        loop = tw.build_tiny_loop()

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
        bat_before = loop._bat
        assert loop.swap_weights(path)
        serve_one("post")
        assert _spec_round._cache_size() == traces_before, (
            "hot-swap retraced — the swapped params changed a jit "
            "signature (shape/dtype/sharding leak)"
        )
        # Was "swap < half a cold rebuild" on the clock: a rebuild is a
        # new batcher from the factory; the swap kept the one it had.
        assert loop._bat is bat_before
        assert loop.counters.swaps == 1
        assert loop.counters.weights_version == 10
        assert loop.counters.watchdog_trips == 0


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

