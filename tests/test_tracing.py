"""Structured-tracing / flight-recorder tests — rocket_tpu.observe end to end.

Four layers, mirroring the ISSUE 4 tentpole:

- units: the Tracer ring (wraparound, span nesting, cross-thread appends,
  error capture), the latency Histogram, Chrome-trace export schema;
- the flight recorder: dump artifacts (trace.json + tail.txt), the
  process-global install/uninstall protocol, SIGTERM chaining;
- automatic instrumentation: Dispatcher capsule spans, Looper iteration
  spans, the DivergenceSentinel's dump hook;
- the serve acceptance path: a StuckStepInjector watchdog trip produces
  a valid Chrome-trace dump whose LAST event is the stuck round's
  ``serve/round`` span (``tripped=True``), and every ``Failed`` result
  carries the dump path.
"""

import json
import os
import signal
import threading

import numpy as np
import pytest

import jax

from rocket_tpu.core.attributes import Attributes
from rocket_tpu.core.capsule import Capsule
from rocket_tpu.core.dispatcher import Dispatcher
from rocket_tpu.engine.sentinel import DivergenceSentinel
from rocket_tpu.launch.loop import Looper
from rocket_tpu.models.generate import ContinuousBatcher, _spec_round
from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
from rocket_tpu.observe import recorder as flightrec
from rocket_tpu.observe.backends import MemoryBackend
from rocket_tpu.observe.recorder import FlightRecorder, active_recorder
from rocket_tpu.observe.trace import (
    Histogram,
    Tracer,
    _main,
    arm,
    disarm,
    get_tracer,
    merge_traces,
)
from rocket_tpu.runtime import Runtime
from rocket_tpu.serve import Completed, Failed, Request, ServingLoop
from rocket_tpu.testing.chaos import StuckStepInjector

pytestmark = pytest.mark.tracing

B, P, TOTAL, NDRAFT = 3, 8, 24, 4


@pytest.fixture()
def armed_global():
    """Arm the process-global tracer for one test, then fully restore it
    (disarmed + empty) so no other test sees leaked events."""
    tracer = arm()
    tracer.clear()
    yield tracer
    disarm()
    tracer.clear()


# -- units: the ring --------------------------------------------------------


class TestTracerRing:
    def test_wraparound_keeps_last_capacity(self):
        t = Tracer(capacity=8, enabled=True)
        for i in range(20):
            t.instant(f"ev{i}")
        events = t.events()
        assert len(events) == 8
        assert [e[1] for e in events] == [f"ev{i}" for i in range(12, 20)]

    def test_span_records_duration_fields_and_kind(self):
        t = Tracer(capacity=16, enabled=True)
        with t.span("work", rid=7) as sp:
            sp.add(extra="mid-span")
        (ev,) = t.events()
        kind, name, ts_ns, dur_ns, tid, fields = ev
        assert kind == "X" and name == "work"
        assert dur_ns >= 0 and tid == threading.get_ident()
        assert fields == {"rid": 7, "extra": "mid-span"}

    def test_nested_spans_close_inner_first(self):
        t = Tracer(capacity=16, enabled=True)
        with t.span("outer"):
            with t.span("inner"):
                pass
        names = [e[1] for e in t.events()]
        assert names == ["inner", "outer"]
        inner, outer = t.events()
        # the outer span brackets the inner one on the timeline
        assert outer[2] <= inner[2]
        assert outer[2] + outer[3] >= inner[2] + inner[3]

    def test_span_captures_escaping_exception(self):
        t = Tracer(capacity=16, enabled=True)
        with pytest.raises(ValueError):
            with t.span("doomed"):
                raise ValueError("boom")
        (ev,) = t.events()
        assert "boom" in ev[5]["error"]

    def test_disabled_tracer_span_is_the_annotation_alone(self):
        t = Tracer(capacity=16, enabled=False)
        a = t.span("x/y", k=1)
        with a:  # holds a TraceAnnotation, stamps no clock, fills no ring
            a.add(ignored=True)
        t.counter("c", 1.0)
        t.instant("i")
        t.health("h", "SERVING")
        assert t.events() == []

    def test_spans_across_threads_carry_distinct_tids(self):
        t = Tracer(capacity=64, enabled=True)

        def worker():
            with t.span("worker-side"):
                pass

        with t.span("caller-side"):
            th = threading.Thread(target=worker)
            th.start()
            th.join()
        by_name = {e[1]: e for e in t.events()}
        assert set(by_name) == {"worker-side", "caller-side"}
        assert by_name["worker-side"][4] != by_name["caller-side"][4]
        assert by_name["caller-side"][4] == threading.get_ident()

    def test_counter_health_instant_kinds(self):
        t = Tracer(capacity=16, enabled=True)
        t.counter("serve/queue_depth", 3)
        t.instant("serve/submit", rid=1)
        t.health("serve/health", "DEGRADED", prev="SERVING")
        kinds = [e[0] for e in t.events()]
        assert kinds == ["C", "I", "H"]
        counter = t.events()[0]
        assert counter[5]["queue_depth"] == 3.0
        health = t.events()[2]
        assert health[5] == {"prev": "SERVING", "state": "DEGRADED"}

    def test_resize_preserves_recent_events(self):
        t = Tracer(capacity=8, enabled=True)
        for i in range(8):
            t.instant(f"ev{i}")
        t.resize(4)
        assert [e[1] for e in t.events()] == ["ev4", "ev5", "ev6", "ev7"]
        with pytest.raises(ValueError):
            t.resize(0)

    def test_arm_disarm_global(self, armed_global):
        assert get_tracer() is armed_global and armed_global.enabled
        armed_global.instant("armed")
        assert len(armed_global.events()) == 1
        disarm()
        armed_global.instant("dropped")
        assert len(armed_global.events()) == 1


# -- units: chrome export ---------------------------------------------------


class TestChromeExport:
    def test_dump_json_is_valid_catapult(self, tmp_path):
        t = Tracer(capacity=32, enabled=True)
        with t.span("phase", rid=1):
            pass
        t.counter("depth", 2)
        t.instant("mark", note=object())  # unserializable -> default=str
        t.health("health", "SERVING")
        t.set_anchor()
        path = t.dump_json(str(tmp_path / "trace.json"))
        with open(path) as f:
            doc = json.load(f)
        assert set(doc) == {"traceEvents", "displayTimeUnit", "metadata"}
        assert doc["displayTimeUnit"] == "ms"
        meta = doc["metadata"]
        assert meta["process_index"] == jax.process_index()
        assert "anchor_wall_s" in meta and "anchor_perf_us" in meta
        events = doc["traceEvents"]
        assert [e["ph"] for e in events] == ["X", "C", "i", "i"]
        for ev in events:
            assert {"name", "ph", "ts", "pid", "tid"} <= set(ev)
        assert events[0]["dur"] >= 0  # complete spans carry a duration
        assert events[3]["s"] == "p" and events[3]["cat"] == "health"

    def test_tail_text_is_human_readable(self):
        t = Tracer(capacity=32, enabled=True)
        with t.span("serve/round", round=3):
            pass
        t.health("serve/health", "DEGRADED")
        txt = t.tail_text()
        assert "span  serve/round" in txt
        assert "health serve/health -> DEGRADED" in txt
        # an empty ring prints no event lines (the process's start-up
        # line, where a start-up was recorded, still heads the text)
        empty = Tracer(capacity=4).tail_text()
        assert "span" not in empty and "event" not in empty


# -- units: histogram -------------------------------------------------------


class TestHistogram:
    def test_nearest_rank_percentiles(self):
        h = Histogram()
        for v in (10.0, 20.0, 30.0, 40.0):
            h.record(v)
        assert h.percentile(0) == 10.0
        assert h.percentile(50) == 30.0  # nearest rank of 4 samples
        assert h.percentile(95) == 40.0
        assert h.percentile(100) == 40.0

    def test_empty_emits_nothing(self):
        h = Histogram()
        assert h.percentile(50) is None
        assert h.summary("ttft_ms") == {}

    def test_window_bounded_count_lifetime(self):
        h = Histogram(capacity=4)
        for v in range(10):
            h.record(float(v))
        assert len(h) == 4 and h.count == 10
        # window holds the most recent samples only
        assert h.percentile(0) == 6.0
        s = h.summary("lat")
        assert set(s) == {"lat/p50", "lat/p95", "lat/p99", "lat/count"}
        assert s["lat/count"] == 10.0


# -- units: multi-host merge ------------------------------------------------


def _host_doc(pid, wall_s, perf_us, events):
    return {
        "traceEvents": [
            {"name": n, "ph": "i", "s": "t", "ts": ts, "pid": pid,
             "tid": 1, "args": {}}
            for n, ts in events
        ],
        "displayTimeUnit": "ms",
        "metadata": {
            "process_index": pid,
            "anchor_wall_s": wall_s,
            "anchor_perf_us": perf_us,
        },
    }


class TestMergeTraces:
    def test_aligns_on_barrier_anchor(self, tmp_path):
        # host 0 anchored at wall=100.0s with perf=1000us; host 1 at
        # wall=100.5s with perf=5000us — its events land 0.5s later on
        # the merged timeline regardless of its raw clock origin.
        d0 = tmp_path / "a-p0"
        d1 = tmp_path / "b-p1"
        d0.mkdir(), d1.mkdir()
        with open(d0 / "trace.json", "w") as f:
            json.dump(_host_doc(0, 100.0, 1000.0, [("h0", 1000.0)]), f)
        with open(d1 / "trace.json", "w") as f:
            json.dump(_host_doc(1, 100.5, 5000.0, [("h1", 5000.0)]), f)
        doc = merge_traces(str(tmp_path))
        assert doc["metadata"]["merged_from"] == 2
        assert doc["metadata"]["hosts"] == [0, 1]
        assert doc["metadata"]["unanchored_files"] == []
        by_name = {e["name"]: e for e in doc["traceEvents"]}
        assert by_name["h0"]["ts"] == 0.0
        assert by_name["h1"]["ts"] == pytest.approx(0.5e6)
        assert by_name["h0"]["pid"] == 0 and by_name["h1"]["pid"] == 1
        # merged stream is time-sorted
        ts = [e["ts"] for e in doc["traceEvents"]]
        assert ts == sorted(ts)

    def test_unanchored_dump_kept_and_flagged(self, tmp_path):
        doc0 = _host_doc(0, 50.0, 0.0, [("anchored", 10.0)])
        doc1 = _host_doc(1, None, None, [("raw", 77.0)])
        del doc1["metadata"]["anchor_wall_s"], doc1["metadata"]["anchor_perf_us"]
        with open(tmp_path / "p0.json", "w") as f:
            json.dump(doc0, f)
        with open(tmp_path / "p1.json", "w") as f:
            json.dump(doc1, f)
        doc = merge_traces(str(tmp_path))
        assert doc["metadata"]["unanchored_files"] == ["p1.json"]
        by_name = {e["name"]: e for e in doc["traceEvents"]}
        assert by_name["raw"]["ts"] == 77.0  # raw clock, unshifted

    def test_empty_dir_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            merge_traces(str(tmp_path))

    def test_cli_writes_merged_json(self, tmp_path, capsys):
        with open(tmp_path / "p0.json", "w") as f:
            json.dump(_host_doc(0, 1.0, 0.0, [("ev", 5.0)]), f)
        assert _main([str(tmp_path)]) == 0
        out_path = tmp_path / "merged.json"
        assert out_path.is_file()
        with open(out_path) as f:
            merged = json.load(f)
        assert merged["metadata"]["merged_from"] == 1
        assert "merged 1 dump(s)" in capsys.readouterr().out


# -- flight recorder --------------------------------------------------------


class TestFlightRecorder:
    def test_dump_writes_trace_and_tail(self, tmp_path):
        tracer = Tracer(capacity=64, enabled=True)
        with tracer.span("serve/round", round=1):
            pass
        rec = FlightRecorder(tracer, out_dir=str(tmp_path / "fr"), tail=8)
        path = rec.dump("watchdog trip!")
        assert rec.last_dump == path and os.path.isdir(path)
        base = os.path.basename(path)
        assert "watchdog-trip" in base  # reason slugified into the name
        assert base.endswith(f"-p{jax.process_index()}")
        with open(os.path.join(path, "trace.json")) as f:
            doc = json.load(f)
        assert doc["metadata"]["dump_reason"] == "watchdog trip!"
        assert doc["traceEvents"][0]["name"] == "serve/round"
        with open(os.path.join(path, "tail.txt")) as f:
            txt = f.read()
        assert "reason: watchdog trip!" in txt and "serve/round" in txt
        # successive dumps never collide, even within one second
        assert rec.dump("again") != path

    def test_disabled_tracer_still_dumps_empty_ring(self, tmp_path):
        rec = FlightRecorder(Tracer(capacity=8), out_dir=str(tmp_path))
        path = rec.dump()
        with open(os.path.join(path, "trace.json")) as f:
            assert json.load(f)["traceEvents"] == []

    def test_install_uninstall_global(self, tmp_path):
        rec = FlightRecorder(Tracer(capacity=8), out_dir=str(tmp_path))
        try:
            assert flightrec.install(rec, sigterm=False) is rec
            assert active_recorder() is rec
        finally:
            flightrec.uninstall()
        assert active_recorder() is None

    def test_sigterm_dumps_then_chains_previous_handler(self, tmp_path):
        calls = []
        orig = signal.getsignal(signal.SIGTERM)
        tracer = Tracer(capacity=8, enabled=True)
        tracer.instant("pre-sigterm")
        rec = FlightRecorder(tracer, out_dir=str(tmp_path))
        try:
            signal.signal(signal.SIGTERM, lambda s, f: calls.append(s))
            flightrec.install(rec, sigterm=True)
            handler = signal.getsignal(signal.SIGTERM)
            assert handler is flightrec._on_sigterm
            handler(signal.SIGTERM, None)
            assert rec.last_dump is not None
            assert calls == [signal.SIGTERM]  # previous handler still fired
            # re-install must not re-chain onto our own hook
            flightrec.install(rec, sigterm=True)
            assert flightrec._PREV_SIGTERM["handler"] is not handler
        finally:
            flightrec.uninstall()
            signal.signal(signal.SIGTERM, orig)
            flightrec._PREV_SIGTERM["handler"] = None


# -- automatic instrumentation ---------------------------------------------


class _Probe(Capsule):
    """Capsule whose launch records nothing — the spans under test come
    from the Dispatcher/Looper wrapping, not from the capsule itself."""

    def launch(self, attrs=None):
        pass


class TestAutomaticInstrumentation:
    def test_dispatcher_wraps_lifecycle_in_spans(self, devices,
                                                 armed_global):
        runtime = Runtime(tracing=True)
        disp = Dispatcher(capsules=[_Probe()])
        disp.bind(runtime)
        disp.setup(None)
        disp.set(None)
        disp.launch(None)
        disp.reset(None)
        disp.destroy(None)
        names = [e[1] for e in armed_global.events()]
        assert names == [
            "_Probe.setup", "_Probe.set", "_Probe.launch",
            "_Probe.reset", "_Probe.destroy",
        ]
        assert all(e[5] == {"cat": "capsule"} for e in armed_global.events())

    def test_dispatcher_spans_follow_the_tracer_not_a_runtime_flag(
            self, devices, armed_global):
        # one path through capsules: the span is always opened; whether
        # it lands in the ring is the tracer's to say, armed or not
        runtime = Runtime(tracing=False)
        disp = Dispatcher(capsules=[_Probe()])
        disp.bind(runtime)
        armed_global.enabled = False
        disp.setup(None)
        disp.launch(None)
        assert armed_global.events() == []
        armed_global.enabled = True
        disp.launch(None)
        disp.destroy(None)
        assert [e[1] for e in armed_global.events()] == [
            "_Probe.launch", "_Probe.destroy"]

    def test_looper_iteration_spans(self, devices, armed_global):
        runtime = Runtime(tracing=True)
        looper = Looper(capsules=[_Probe()], repeats=3, progress=False)
        looper.bind(runtime)
        attrs = Attributes()
        looper.setup(attrs)
        looper.launch(attrs)
        names = [e[1] for e in armed_global.events()]
        assert names.count("looper/TRAIN/iter") == 3
        assert names.count("_Probe.launch") >= 3
        # the capsule span closes before its enclosing iteration span
        first_iter = names.index("looper/TRAIN/iter")
        assert names[first_iter - 1] == "_Probe.launch"

    def test_sentinel_divergence_marks_and_dumps(self, tmp_path,
                                                 armed_global):
        rec = FlightRecorder(armed_global, out_dir=str(tmp_path))
        sent = DivergenceSentinel(policy="warn")
        try:
            flightrec.install(rec, sigterm=False)
            sent._act(float("nan"))
        finally:
            flightrec.uninstall()
        assert sent.events == 1
        instants = [e for e in armed_global.events()
                    if e[1] == "sentinel/divergence"]
        assert len(instants) == 1
        assert instants[0][5]["policy"] == "warn"
        assert rec.last_dump is not None
        assert "sentinel-warn" in os.path.basename(rec.last_dump)


# -- the serve acceptance path ----------------------------------------------


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


class TestServeTracing:
    def test_request_spans_and_latency_percentiles(self, models, prompts):
        tracer = Tracer(capacity=512, enabled=True)
        sink = MemoryBackend()
        loop = ServingLoop(_factory(models), max_batch=B, queue_capacity=8,
                           tracer=tracer, sink=sink, flush_every=1)
        for i in range(3):
            assert loop.submit(Request(rid=i, prompt=prompts[i])) is None
        results = loop.run_until_idle()
        loop.close()
        assert all(isinstance(r, Completed) for r in results)

        names = [e[1] for e in tracer.events()]
        assert names.count("serve/submit") == 3
        assert names.count("serve/admit") == 3
        assert names.count("serve/round") >= 1
        assert names.count("serve/complete") == 3
        admit = next(e for e in tracer.events() if e[1] == "serve/admit")
        assert admit[5]["prompt_len"] == P

        # TTFT/TPOT/e2e percentiles computed and flushed as trace/* scalars
        summary = loop.latency.summary()
        for key in ("queue_wait_ms/p50", "ttft_ms/p50", "ttft_ms/p99",
                    "tpot_ms/p50", "e2e_ms/p95"):
            assert key in summary
        assert summary["ttft_ms/count"] == 3.0
        _step, last = sink.scalars[-1]
        assert "trace/ttft_ms/p50" in last and "serve/completed" in last
        assert last["trace/e2e_ms/p50"] >= last["trace/ttft_ms/p50"] >= 0.0

    def test_tracing_adds_no_step_traces(self, models, prompts):
        bare = _factory(models)()
        bare.start(prompts[:B])
        while not bare.all_done:
            bare.step()
        traces_before = _spec_round._cache_size()
        tracer = Tracer(capacity=512, enabled=True)
        loop = ServingLoop(_factory(models), max_batch=B, queue_capacity=8,
                           tracer=tracer)
        for i in range(3):
            loop.submit(Request(rid=i, prompt=prompts[i]))
        results = loop.run_until_idle()
        loop.close()
        assert len(results) == 3
        # armed tracing recorded spans but traced ZERO new step bodies
        assert _spec_round._cache_size() == traces_before
        assert any(e[1] == "serve/round" for e in tracer.events())

    def test_watchdog_trip_dumps_flight_recorder(self, models, prompts,
                                                 tmp_path):
        """ISSUE 4 acceptance: a StuckStepInjector trip produces a valid
        Chrome-trace dump whose last event is the stuck round's span, and
        the Failed results carry the dump path."""
        tracer = Tracer(capacity=512, enabled=True)
        rec = FlightRecorder(tracer, out_dir=str(tmp_path / "flightrec"))
        instances = {"n": 0}
        base_factory = _factory(models)

        def factory():
            bat = base_factory()
            instances["n"] += 1
            if instances["n"] == 1:
                return StuckStepInjector(bat, hang_on=(2,), hang_s=8.0)
            return bat

        loop = ServingLoop(factory, max_batch=B, queue_capacity=4,
                           watchdog_timeout=0.4, recover_rounds=2,
                           tracer=tracer, recorder=rec)
        for i in range(2):
            loop.submit(Request(rid=i, prompt=prompts[i]))
        loop.run_round()                   # proxy step #1: fine
        loop.run_round()                   # proxy step #2: wedged
        results = loop.drain_results()
        loop.close()

        assert loop.watchdog.trips == 1
        failed = [r for r in results if isinstance(r, Failed)]
        assert sorted(r.rid for r in failed) == [0, 1]
        dump = failed[0].dump_path
        assert dump is not None and os.path.isdir(dump)
        assert all(r.dump_path == dump for r in failed)
        assert rec.last_dump == dump

        with open(os.path.join(dump, "trace.json")) as f:
            doc = json.load(f)
        assert doc["metadata"]["dump_reason"] == "watchdog-trip"
        events = doc["traceEvents"]
        # the stuck round's span closed BEFORE the dump, so it is the
        # ring's final event — exactly what the operator reads first
        assert events[-1]["name"] == "serve/round"
        assert events[-1]["ph"] == "X"
        assert events[-1]["args"].get("tripped") is True
        with open(os.path.join(dump, "tail.txt")) as f:
            txt = f.read()
        assert "watchdog-trip" in txt and "serve/round" in txt
        # the failure instants landed AFTER the dump: in the ring but not
        # in the dumped artifact
        assert not any(e["name"] == "serve/failed" for e in events)
        assert any(e[1] == "serve/failed" for e in tracer.events())
