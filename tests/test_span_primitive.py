"""ISSUE 24: one span primitive on the profiler's clock; the start-up record;
first dispatches; the goodput ledger's device-time booking; the serving
loop's fetch helper and result stamps.

Counts and names only, never times: everything here runs on the CPU.
"""

import glob
import logging
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import rocket_tpu as rt  # noqa: E402
from rocket_tpu.core.attributes import Attributes  # noqa: E402
from rocket_tpu.models.generate import ContinuousBatcher, HostReads  # noqa: E402
from rocket_tpu.models.objectives import cross_entropy  # noqa: E402
from rocket_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    TransformerLM,
)
from rocket_tpu.observe import ledger as ledger_mod  # noqa: E402
from rocket_tpu.observe import trace as trace_mod  # noqa: E402
from rocket_tpu.observe.trace import StartupRecord, Tracer  # noqa: E402
from rocket_tpu.serve import (  # noqa: E402
    Completed,
    DeadlineExceeded,
    Request,
    ServingLoop,
)

from test_pipeline import MLP, synthetic_classification  # noqa: E402

pytestmark = pytest.mark.tracing


# -- helpers -----------------------------------------------------------------


def _profiled(tmp_path, body):
    """Run ``body`` inside a short CPU ``jax.profiler`` session (host
    TraceMes on, Python tracer off, as the benchmark traces) and return the
    host plane's event names as the benchmark's own loader reads them."""
    from benchmark.trace_reduce import load_xplane

    directory = str(tmp_path / "profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert files, "the profiler wrote no .xplane.pb"
    return [e[2] for e in load_xplane(files[0]) if e[0] == "/host:CPU"]


def _lm(seed):
    cfg = TransformerConfig(vocab_size=64, hidden=32, n_layers=2, n_heads=4,
                            max_seq=64)
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(seed),
        {"tokens": np.zeros((1, 8), np.int32),
         "positions": np.zeros((1, 8), np.int32)})["params"]
    return model, params


@pytest.fixture(scope="module")
def models():
    model, params = _lm(1)
    draft, dparams = _lm(7)
    return model, draft, params, dparams


def _serving_loop(models, tracer=None, **kw):
    model, draft, params, dparams = models
    return ServingLoop(
        lambda: ContinuousBatcher(model, draft, params, dparams,
                                  total_len=24, n_draft=2),
        max_batch=2, tracer=tracer, **kw)


def _requests(n, **kw):
    rng = np.random.default_rng(3)
    return [Request(rid=i, prompt=rng.integers(1, 64, size=6 + i % 2)
                    .astype(np.int32), max_new_tokens=5, **kw)
            for i in range(n)]


def _trainer(tmp_path, tracing=False, epochs=1):
    data = synthetic_classification(n=128)
    module = rt.Module(MLP(), capsules=[
        rt.Loss(cross_entropy(labels_key="label"), name="ce"),
        rt.Optimizer(learning_rate=1e-2)])
    looper = rt.Looper(capsules=[
        rt.Dataset(rt.ArraySource(data), batch_size=64, shuffle=False),
        module, rt.Tracker("memory"), rt.Checkpointer(save_every=100),
    ], progress=False)
    return rt.Launcher(capsules=[looper], tag="spans", num_epochs=epochs,
                       project_root=str(tmp_path), tracing=tracing)


@pytest.fixture()
def program_log():
    """Messages the program logs (its ``rocket_tpu`` logger does not
    propagate to the root, so ``caplog`` never sees them)."""
    messages = []

    class _Collect(logging.Handler):
        def emit(self, record):
            messages.append(record.getMessage())

    handler, log = _Collect(level=logging.INFO), logging.getLogger("rocket_tpu")
    level = log.level
    log.addHandler(handler)
    log.setLevel(logging.INFO)
    yield messages
    log.removeHandler(handler)
    log.setLevel(level)


@pytest.fixture()
def startup():
    """The process's start-up record, emptied for one test and restored."""
    record = trace_mod.get_startup()
    kept, logged, cache = record.events(), record.logged, record.cache
    record.clear()
    yield record
    record.clear()
    for name, ts, dur, fields in kept:
        record.mark(name, ts, ts + dur, **fields)
    record.logged, record.cache = logged, cache


# -- the span primitive ------------------------------------------------------


class TestSpanPrimitive:
    def test_armed_span_lands_in_the_ring_with_its_fields(self):
        t = Tracer(capacity=8, enabled=True)
        with t.span("serve/fetch", what="n_tok", bytes=8) as sp:
            sp.add(extra=1)
        (kind, name, _ts, dur, _tid, fields), = t.events()
        assert (kind, name) == ("X", "serve/fetch") and dur >= 0
        assert fields == {"what": "n_tok", "bytes": 8, "extra": 1}

    def test_disarmed_span_fills_no_ring_and_still_closes(self):
        t = Tracer(capacity=8, enabled=False)
        with t.span("serve/fetch", what="n_tok") as sp:
            sp.add(extra=1)
        assert t.events() == []

    @pytest.mark.parametrize("enabled", [True, False])
    def test_exception_in_the_body_propagates_and_closes_the_span(
            self, enabled):
        t = Tracer(capacity=8, enabled=enabled)
        with pytest.raises(ValueError):
            with t.span("looper/x/iter"):
                raise ValueError("boom")
        events = t.events()
        assert len(events) == (1 if enabled else 0)
        if enabled:
            assert "boom" in events[0][5]["error"]
        with t.span("looper/x/iter"):   # the annotation stack is intact
            pass

    def test_nested_spans_close_inner_first_and_lie_inside(self):
        t = Tracer(capacity=8, enabled=True)
        with t.span("serve/round"):
            with t.span("serve/dispatch"):
                pass
            with t.span("serve/fetch"):
                pass
        names = [e[1] for e in t.events()]
        assert names == ["serve/dispatch", "serve/fetch", "serve/round"]
        inner, _, outer = t.events()
        assert outer[2] <= inner[2]
        assert inner[2] + inner[3] <= outer[2] + outer[3]

    @pytest.mark.parametrize("enabled", [True, False])
    def test_one_trace_annotation_per_span_armed_or_not(self, tmp_path,
                                                        enabled):
        t = Tracer(capacity=64, enabled=enabled)

        def body():
            for _ in range(3):
                with t.span("probe/outer", k=1):
                    with t.span("probe/inner"):
                        pass

        names = _profiled(tmp_path, body)
        assert names.count("probe/outer") == 3
        assert names.count("probe/inner") == 3
        assert len(t.events()) == (6 if enabled else 0)

    def test_no_second_primitive_is_left(self):
        from rocket_tpu.core import module
        from rocket_tpu.observe import profile

        assert not hasattr(profile, "annotate")
        assert not hasattr(module, "trace_span")
        for gone in ("emit_gauges", "set_step_cost", "executable_cost"):
            assert not hasattr(ledger_mod, gone)


class TestHotLoopSpansReachTheProfile:
    def test_trainer_spans_with_the_tracer_disarmed(self, tmp_path, devices):
        launcher = _trainer(tmp_path)
        assert not trace_mod.get_tracer().enabled
        # what an earlier test of this worker left in the process's ring
        # is not this test's business (which files share a worker changes
        # from run to run)
        trace_mod.get_tracer().clear()
        names = _profiled(tmp_path, launcher.launch)
        iters = names.count("looper/TRAIN/iter")
        assert iters == 2
        for capsule in ("Dataset", "Module", "Tracker", "Checkpointer",
                        "Loss", "Optimizer"):
            assert names.count(f"{capsule}.launch") == iters, capsule
        assert names.count("train/step_dispatch") == iters
        assert "Looper.launch" in names and "Module.setup" in names
        assert trace_mod.get_tracer().events() == []   # ring stayed empty

    def test_serving_spans_through_a_handed_tracer(self, tmp_path, models):
        tracer = Tracer(capacity=4096, enabled=True)
        loop = _serving_loop(models, tracer=tracer)
        for req in _requests(3):
            loop.submit(req)
        tracer.clear()
        before = loop.counters.host_fetches
        names = _profiled(tmp_path, loop.run_until_idle)
        ring = [e[1] for e in tracer.events() if e[0] == "X"]
        rounds = ring.count("serve/round")
        assert rounds >= 2
        for name in ("serve/round", "serve/dispatch", "serve/fetch",
                     "serve/harvest", "serve/admit", "serve/shed",
                     "serve/policy"):
            assert ring.count(name) >= 1, name
            assert names.count(name) == ring.count(name), name
        assert ring.count("serve/dispatch") == rounds
        # every blocking read went through the one helper
        assert loop.counters.host_fetches - before == ring.count("serve/fetch")
        assert ring.count("serve/fetch") >= 5 * rounds
        assert loop.counters.round_gap_ms_ema > 0.0
        snap = loop.counters.snapshot()
        assert {"host_fetches", "round_gap_ms_ema"} <= set(snap)


# -- the start-up record -----------------------------------------------------


class TestStartupRecord:
    def test_bounded_and_summed_once(self):
        rec = StartupRecord(capacity=4)
        for i in range(10):
            rec.mark("startup/first_dispatch", i * 10, i * 10 + 5, edge=i)
        assert len(rec) == 4 and rec.events()[0][3] == {"edge": 6}
        rec = StartupRecord()
        # an import inside an import counts once (the union) ...
        rec.mark("startup/import", 0, 10_000_000_000, package="a")
        rec.mark("startup/import", 2_000_000_000, 3_000_000_000, package="a.b")
        rec.mark("startup/import", 10_000_000_000, 11_000_000_000,
                 package="c")
        # ... and a phase's seconds leave out other phases inside it
        rec.mark("startup/serve_warm_start", 20_000_000_000, 25_000_000_000)
        rec.mark("startup/first_dispatch", 21_000_000_000, 24_000_000_000)
        assert rec.seconds() == {"startup/import": 11.0,
                                 "startup/serve_warm_start": 2.0,
                                 "startup/first_dispatch": 3.0}
        assert rec.seconds(until_ns=20_000_000_000) == {"startup/import": 11.0}
        assert rec.line().startswith(
            "start-up: import 11.0 s, first dispatch 3.0 s, warm start 2.0 s")

    def test_logged_once(self, caplog):
        rec = StartupRecord()
        rec.mark("startup/import", 0, 1_500_000_000)
        log = logging.getLogger("test.startup")
        with caplog.at_level(logging.INFO, logger="test.startup"):
            first, second = rec.log_once(log), rec.log_once(log)
        assert first.startswith("start-up: import 1.5 s") and second is None
        assert sum("start-up:" in r.message for r in caplog.records) == 1

    def test_imports_are_on_the_process_record_with_the_tracer_disarmed(self):
        assert not trace_mod.get_tracer().enabled
        packages = [f.get("package") for name, _ts, _dur, f
                    in trace_mod.get_startup().events()
                    if name == "startup/import"]
        assert {"rocket_tpu", "rocket_tpu.models", "rocket_tpu.serve"} \
            <= set(packages)
        assert len(packages) == len(set(packages))      # stamped once each

    def test_phase_is_recorded_disarmed_and_is_a_span_when_armed(self,
                                                                 startup):
        with startup.phase("startup/build", who="test"):
            pass
        assert [e[0] for e in startup.events()] == ["startup/build"]
        tracer = trace_mod.arm()
        tracer.clear()
        try:
            with startup.phase("startup/build"):
                pass
            assert [e[1] for e in tracer.events()] == ["startup/build"]
            # exported with every dump, outside the ring's window
            meta = Tracer(capacity=4).to_chrome()["metadata"]["startup"]
            assert [e["name"] for e in meta["events"]] == ["startup/build"] * 2
            assert "startup/build" in meta["seconds"]
            assert Tracer(capacity=4).tail_text().startswith("start-up: ")
        finally:
            trace_mod.disarm()
            tracer.clear()

    def test_launcher_records_its_phases_and_logs_the_line(
            self, tmp_path, devices, startup, program_log):
        _trainer(tmp_path).launch()
        names = [e[0] for e in startup.events()]
        assert names.count("startup/runtime") == 1
        assert names.count("startup/build") == 1
        edges = [e[3]["edge"] for e in startup.events()
                 if e[0] == "startup/first_dispatch"]
        assert edges == ["train_step/dispatch/sync"]
        lines = [m for m in program_log if m.startswith("start-up: ")]
        assert len(lines) == 1
        assert "build" in lines[0] and "first dispatch" in lines[0]
        assert "cache hits" in lines[0]

    def test_serving_loop_records_its_warm_start_up_to_serving(
            self, models, startup, program_log):
        ledger_mod._DISPATCHED.clear()
        loop = _serving_loop(models)
        # SERVING is reported from the constructor: the line is out and
        # the record closed before the first request
        assert startup.logged
        assert sum(m.startswith("start-up: ") for m in program_log) == 1
        closed = startup.events()
        for req in _requests(4):            # prompt lengths 6, 7, 6, 7
            loop.submit(req)
        loop.run_until_idle()
        # a new prompt length compiling in service is no start-up
        assert startup.events() == closed
        assert not any(isinstance(k, tuple) for k in ledger_mod._DISPATCHED)
        assert [e[0] for e in closed].count("startup/serve_warm_start") == 1
        firsts = [(e[3]["edge"], e[3].get("shape")) for e in closed
                  if e[0] == "startup/first_dispatch"]
        assert sorted(firsts) == [("generate/spec_prefill", None),
                                  ("generate/spec_round", None)]
        assert all("cache_hit" in e[3] for e in closed
                   if e[0] == "startup/first_dispatch")
        assert sum(m.startswith("start-up: ") for m in program_log) == 1

    def test_closed_once_logged(self, startup):
        startup.mark("startup/import", 0, 1_000_000_000)
        assert startup.cache is None
        line = startup.log_once(logging.getLogger("test.startup"))
        assert startup.logged and line == startup.line()
        startup.mark("startup/first_dispatch", 5, 9, edge="late")
        with startup.phase("startup/build"):
            pass
        assert [e[0] for e in startup.events()] == ["startup/import"]
        assert startup.to_meta()["cache"] == startup.cache
        startup.clear()
        assert not startup.logged and startup.cache is None

    def test_the_line_keeps_the_cache_counts_it_was_logged_with(
            self, startup, monkeypatch):
        from rocket_tpu.tune import compile_cache

        compile_cache.install_listeners()
        monkeypatch.setitem(compile_cache._state, "requests", 15)
        monkeypatch.setitem(compile_cache._state, "hits", 15)
        line = startup.log_once(logging.getLogger("test.startup"))
        assert line.endswith("15 cache hits, 0 misses")
        assert startup.cache == {"hits": 15, "misses": 0}
        monkeypatch.setitem(compile_cache._state, "requests", 71)
        assert startup.line() == line       # mid-service compiles: not here


# -- first dispatches --------------------------------------------------------


class TestFirstDispatch:
    def test_recorded_with_the_sentinel_disarmed_and_not_on_a_warm_edge(
            self, startup):
        assert not ledger_mod.get_retrace_ledger().armed
        fn = jax.jit(lambda x: x + 1)
        x = jnp.ones((4,))
        for _ in range(3):
            ledger_mod.ledger_call(fn, "probe/first_dispatch_edge", x)
        mine = [e for e in startup.events()
                if e[3].get("edge") == "probe/first_dispatch_edge"]
        assert len(mine) == 1 and mine[0][0] == "startup/first_dispatch"
        assert mine[0][3]["cache_hit"] in (True, False)

    def test_a_shaped_edge_is_recorded_once_per_shape(self, startup):
        fn = jax.jit(lambda x: x * 2)
        for n in (4, 4, 8, 4, 8):
            ledger_mod.ledger_call(fn, "probe/shaped_edge", jnp.ones((n,)),
                                   _shape=n)
        shapes = [e[3]["shape"] for e in startup.events()
                  if e[3].get("edge") == "probe/shaped_edge"]
        assert shapes == [4, 8]

    def test_a_raising_first_call_is_recorded_and_raises(self, startup):
        def boom(x):
            raise RuntimeError("no")

        with pytest.raises(RuntimeError):
            ledger_mod.ledger_call(boom, "probe/raising_edge", 1)
        assert [e[3]["edge"] for e in startup.events()] \
            == ["probe/raising_edge"]

    def test_not_recorded_once_the_line_is_logged(self, startup):
        startup.log_once(logging.getLogger("test.startup"))
        known = set(ledger_mod._DISPATCHED)
        fn = jax.jit(lambda x: x - 1)
        for n in (3, 5, 3):
            out = ledger_mod.ledger_call(fn, "probe/late_edge",
                                         jnp.ones((n,)), _shape=n)
            assert out.shape == (n,)
        assert startup.events() == []
        assert set(ledger_mod._DISPATCHED) == known     # no growth in service


def test_compile_cache_listeners_count_under_a_bare_serving_loop(models):
    from rocket_tpu.tune import compile_cache

    loop = _serving_loop(models)
    assert compile_cache._state["listeners"] is True
    before = compile_cache.snapshot()["requests"]
    # a prompt length nothing else in this file admits: a compile request
    loop.submit(Request(rid="odd", prompt=np.arange(1, 12, dtype=np.int32),
                        max_new_tokens=2))
    loop.run_until_idle()
    after = compile_cache.snapshot()
    assert after["requests"] > before
    assert after["misses"] + after["hits"] == after["requests"]
    hits, misses = compile_cache.hits_and_misses()
    assert (hits, misses) == (after["hits"], after["misses"])


# -- goodput: device time, not lag-window waits ------------------------------


class _Leaf:
    """A stand-in for a step's output whose readiness the test decides."""

    def __init__(self, ready):
        self.ready = ready
        self.asked = 0

    def is_ready(self):
        self.asked += 1
        return self.ready


class _FakeStep(rt.Capsule):
    """Dispatches nothing: publishes a leaf and stamps the dispatch as an
    ``_AnnotatedStep`` would, after a little host work."""

    def __init__(self, ready):
        super().__init__(statefull=False)
        self.leaf = _Leaf(ready)

    def launch(self, attrs=None):
        sum(range(20000))                      # host work before dispatch
        ledger_mod.get_goodput().mark_dispatch()
        sum(range(20000))                      # and after it
        attrs.step_logs = Attributes(loss=self.leaf)


@pytest.fixture()
def goodput():
    ledger_mod.disarm_ledgers()
    gp = ledger_mod.get_goodput()
    gp.start_run()
    yield gp
    ledger_mod.disarm_ledgers()


class TestGoodputBooksDeviceTime:
    def _run(self, ready, iters=30):
        step = _FakeStep(ready)
        looper = rt.Looper(capsules=[step], repeats=iters, progress=False)
        looper.bind(rt.Runtime())
        attrs = Attributes()
        looper.setup(attrs)
        looper.launch(attrs)
        looper.reset(attrs)
        return step

    @staticmethod
    def _booked(goodput, monkeypatch):
        """Iterations that booked time into each bucket: the ledger's
        ``add`` calls with more than nothing."""
        import collections

        booked = collections.Counter()
        add = goodput.add

        def spy(bucket, seconds, **kw):
            booked[bucket] += seconds > 0.0
            return add(bucket, seconds, **kw)

        monkeypatch.setattr(goodput, "add", spy)
        return booked

    def test_a_not_ready_leaf_books_productive(self, goodput, monkeypatch):
        booked = self._booked(goodput, monkeypatch)
        step = self._run(ready=False)
        # only the first iteration (nothing dispatched before it) is dry:
        # it alone books host-blocked time, every one books productive
        assert step.leaf.asked == 29
        assert booked["host_blocked"] == 1 and booked["productive"] == 30

    def test_a_ready_leaf_books_host_blocked_until_the_dispatch(
            self, goodput, monkeypatch):
        booked = self._booked(goodput, monkeypatch)
        step = self._run(ready=True)
        assert step.leaf.asked == 29
        # dry every iteration: blocked up to the dispatch, productive after
        assert booked["host_blocked"] == 30 and booked["productive"] == 30

    def test_buckets_still_sum_to_wall_within_1pct(self, goodput):
        self._run(ready=True)
        goodput.end_run()
        snap = goodput.snapshot()
        booked = sum(v for k, v in snap.items()
                     if k.endswith("_s") and k not in ("total_s",
                                                       "unattributed_s"))
        assert booked <= 1.01 * snap["total_s"]
        assert booked + snap["unattributed_s"] == pytest.approx(
            snap["total_s"], rel=0.01)

    def test_a_real_launcher_books_productive_time(self, tmp_path, devices):
        _trainer(tmp_path, epochs=2).launch()
        snap = ledger_mod.get_goodput().snapshot()
        assert snap["productive_s"] > 0.0
        assert snap["compile_s"] > 0.0


# -- results carry their instants --------------------------------------------


class TestResultStamps:
    def test_completed_carries_ordered_stamps_and_due_at(self, models):
        clock_t = [100.0]

        def clock():
            clock_t[0] += 0.25
            return clock_t[0]

        loop = _serving_loop(models, clock=clock)
        for req in _requests(3, due_at=42.5):
            loop.submit(req)
        results = loop.run_until_idle()
        assert len(results) == 3
        for res in results:
            assert isinstance(res, Completed)
            assert res.due_at == 42.5          # copied through untouched
            assert res.submitted_at <= res.admitted_at \
                < res.first_token_at <= res.finished_at
        # the third request waited for a row: admitted after the others
        waits = sorted(r.admitted_at - r.submitted_at for r in results)
        assert waits[-1] > waits[0]

    def test_evicted_request_carries_its_stamps(self, models):
        clock_t = [0.0]

        def clock():
            clock_t[0] += 1.0
            return clock_t[0]

        loop = _serving_loop(models, clock=clock)
        loop.submit(Request(rid="late", prompt=np.arange(1, 7, dtype=np.int32),
                            max_new_tokens=12, deadline=8.0, due_at=0.5))
        (res,) = loop.run_until_idle()
        assert isinstance(res, DeadlineExceeded) and res.stage == "decode"
        assert res.due_at == 0.5
        assert res.submitted_at <= res.admitted_at <= res.finished_at

    def test_due_at_defaults_to_none_and_crosses_the_wire(self):
        from rocket_tpu.serve.wire import pack_request, unpack_request

        req = Request(rid=1, prompt=np.arange(4, dtype=np.int32))
        assert req.due_at is None
        req = Request(rid=2, prompt=np.arange(4, dtype=np.int32), due_at=7.25)
        assert unpack_request(pack_request(req)).due_at == 7.25

    def test_host_reads_counts_and_names_every_read(self):
        class Counters:
            host_fetches = 0

        tracer = Tracer(capacity=16, enabled=True)
        reads = HostReads(tracer, Counters())
        assert reads.returned_at is None
        out = reads(jnp.arange(6, dtype=jnp.int32), "n_tok")
        assert isinstance(out, np.ndarray) and out.tolist() == list(range(6))
        assert reads.counters.host_fetches == 1
        assert reads.returned_at is not None
        (_k, name, _ts, _dur, _tid, fields), = tracer.events()
        assert name == "serve/fetch"
        assert fields == {"what": "n_tok", "bytes": 24}
