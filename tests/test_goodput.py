"""Goodput ledger, retrace sentinel, and metrics export (ISSUE 9).

Covers the tentpole's acceptance criteria beyond the trace and read
counts in tests/test_overhead_counts.py::TestGoodputGuard:

- goodput buckets (plus the explicit ``unattributed`` remainder) sum to
  the measured wall window within 1% on a real instrumented Looper run;
- an injected shape-change retrace escalates into EXACTLY ONE sentinel
  flight dump naming the executable and the offending shapes — deduped
  per (edge, signature), suppressed by ``exempt`` / ``expect_compile``;
- the new gauge/counter events round-trip through the Chrome-trace
  schema, and ``memory_stats()`` telemetry is a silent no-op on CPU;
- ``/metrics`` serves parseable Prometheus text (version 0.0.4) and the
  export CLI merges per-replica snapshots (counters sum, percentiles
  take the worst replica);
- flight-dump retention keeps the newest N dirs, and registered dump
  writers drop ``goodput.json`` into every dump.
"""

import json
import os
import re
import sys
import urllib.request

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture()
def clean_ledgers():
    """Pristine global ledgers on entry AND exit — earlier suite tests
    (any Launcher run arms them) must not leak counts in either
    direction."""
    from rocket_tpu.observe.ledger import (
        disarm_ledgers,
        get_retrace_ledger,
    )

    def _pristine():
        disarm_ledgers()
        get_retrace_ledger().reset()
        get_retrace_ledger().set_recorder(None)

    _pristine()
    yield
    _pristine()


# -- retrace sentinel -------------------------------------------------------


@pytest.mark.goodput
class TestRetraceSentinel:
    def _dump_dirs(self, out_dir):
        from rocket_tpu.observe.recorder import FlightRecorder

        if not os.path.isdir(out_dir):
            return []
        return sorted(
            e for e in os.listdir(out_dir)
            if FlightRecorder._DUMP_DIR.match(e)
        )

    def test_shape_change_triggers_exactly_one_dump(
        self, devices, tmp_path, clean_ledgers
    ):
        import jax
        import jax.numpy as jnp

        from rocket_tpu.observe.ledger import (
            arm_ledgers,
            get_retrace_ledger,
            ledger_call,
        )
        from rocket_tpu.observe.recorder import FlightRecorder
        from rocket_tpu.observe.trace import Tracer

        tracer = Tracer(capacity=256, enabled=True)
        rec = FlightRecorder(tracer=tracer, out_dir=str(tmp_path))
        arm_ledgers(recorder=rec)
        ledger = get_retrace_ledger()

        fn = jax.jit(lambda x: x * 2.0)
        ledger_call(fn, "probe/sentinel", jnp.ones((2,)))   # cold compile
        ledger_call(fn, "probe/sentinel", jnp.ones((2,)))   # marks warm
        assert ledger.sentinel_dumps == 0
        assert not self._dump_dirs(tmp_path)

        # the injected shape change: one retrace, one dump
        ledger_call(fn, "probe/sentinel", jnp.ones((3,)))
        assert ledger.retraces == 1
        assert ledger.sentinel_dumps == 1
        dumps = self._dump_dirs(tmp_path)
        assert len(dumps) == 1
        # the dump names the executable in its directory slug...
        assert "retrace-probe-sentinel" in dumps[0]
        # ...and the trace.json carries the sentinel instant with the
        # executable name and the offending shapes
        with open(tmp_path / dumps[0] / "trace.json") as f:
            doc = json.load(f)
        sentinels = [e for e in doc["traceEvents"]
                     if e["name"] == "ledger/retrace"]
        assert len(sentinels) == 1
        assert sentinels[0]["ph"] == "i"
        assert sentinels[0]["args"]["executable"] == "probe/sentinel"
        assert "float32[3]" in sentinels[0]["args"]["shapes"]

        # dedup: the SAME (edge, signature) retracing again — here via a
        # fresh executable dispatched under the same ledger name — must
        # not produce a second dump
        fn2 = jax.jit(lambda x: x * 2.0)
        ledger_call(fn2, "probe/sentinel", jnp.ones((3,)))
        assert ledger.retraces == 2
        assert ledger.sentinel_dumps == 1
        assert len(self._dump_dirs(tmp_path)) == 1

        # the ledger recorded both the cold compile and the retrace
        recs = [(r.name, r.retrace) for r in ledger.records()]
        assert ("probe/sentinel", False) in recs
        assert ("probe/sentinel", True) in recs

    def test_exempt_and_expected_compiles_do_not_dump(
        self, devices, tmp_path, clean_ledgers
    ):
        import jax
        import jax.numpy as jnp

        from rocket_tpu.observe.ledger import RetraceLedger
        from rocket_tpu.observe.recorder import FlightRecorder
        from rocket_tpu.observe.trace import Tracer

        rec = FlightRecorder(tracer=Tracer(enabled=False),
                             out_dir=str(tmp_path))
        ledger = RetraceLedger()
        ledger.armed = True
        ledger.set_recorder(rec)

        # exempt edge: per-prompt-length polymorphism is by design
        fn = jax.jit(lambda x: x + 1.0)
        ledger.exempt("probe/poly")
        ledger.call(fn, "probe/poly", jnp.ones((2,)))
        ledger.call(fn, "probe/poly", jnp.ones((2,)))    # warm
        ledger.call(fn, "probe/poly", jnp.ones((3,)))    # retrace, exempt
        assert ledger.retraces == 1 and ledger.sentinel_dumps == 0

        # expect_compile scope: the serve loop's deliberate inline compile
        g = jax.jit(lambda x: x - 1.0)
        ledger.call(g, "probe/ladder", jnp.ones((2,)))
        ledger.call(g, "probe/ladder", jnp.ones((2,)))   # warm
        with ledger.expect_compile("probe/ladder"):
            ledger.call(g, "probe/ladder", jnp.ones((3,)))
        assert ledger.retraces == 2 and ledger.sentinel_dumps == 0
        # outside the scope the same edge escalates again
        ledger.call(g, "probe/ladder", jnp.ones((4,)))
        assert ledger.sentinel_dumps == 1
        assert not os.path.isdir(tmp_path) or len(os.listdir(tmp_path)) == 1


# -- goodput accounting -----------------------------------------------------


@pytest.mark.goodput
class TestGoodputAccounting:
    def test_buckets_sum_to_wall_time_within_1pct(
        self, devices, clean_ledgers
    ):
        import jax
        import jax.numpy as jnp

        from rocket_tpu.core.attributes import Attributes
        from rocket_tpu.core.capsule import Capsule
        from rocket_tpu.launch.loop import Looper
        from rocket_tpu.observe.ledger import (
            arm_ledgers,
            disarm_ledgers,
            get_goodput,
        )
        from rocket_tpu.runtime import Runtime

        from rocket_tpu.engine.step import _annotated_dispatch

        class JitProbe(Capsule):
            """A step as the Module dispatches one: through an
            ``_AnnotatedStep`` (which stamps the dispatch's return on the
            goodput ledger), its logs published as ``step_logs``."""

            def __init__(self):
                super().__init__()
                self.fn = _annotated_dispatch(
                    jax.jit(lambda x: x * 2.0 + 1.0), "probe/dispatch")
                self.x = jnp.ones((256, 256), jnp.float32)

            def launch(self, attrs=None):
                self.x = self.fn(self.x)
                attrs.step_logs = Attributes(loss=self.x)

        arm_ledgers()
        probe = JitProbe()
        looper = Looper(capsules=[probe], repeats=40, progress=False)
        looper.bind(Runtime())
        attrs = Attributes()
        looper.setup(attrs)
        for _ in range(3):
            looper.launch(attrs)
            jax.block_until_ready(probe.x)
            looper.reset(attrs)
        disarm_ledgers()

        snap = get_goodput().snapshot()
        assert snap["total_s"] > 0.0
        # the instrumented cycles actually fed the measured buckets
        assert snap["productive_s"] > 0.0
        assert snap["compile_s"] > 0.0  # the warmup trace was charged
        attributed = sum(
            v for k, v in snap.items()
            if k.endswith("_s") and k not in ("total_s",)
        )
        # ISSUE 9 acceptance: buckets sum to wall time within 1% — by
        # construction the identity is exact (unattributed_s is the
        # remainder), so this also guards against double-counting pushing
        # the attributed total PAST the window
        assert abs(attributed - snap["total_s"]) <= 0.01 * snap["total_s"]
        assert 0.0 <= snap["goodput_frac"] <= 1.0

    def test_snapshot_freezes_after_end_run(self, clean_ledgers):
        import time

        from rocket_tpu.observe.ledger import GoodputLedger

        gp = GoodputLedger()
        gp.start_run()
        gp.add("productive", 0.010)
        gp.end_run()
        total1 = gp.snapshot()["total_s"]
        time.sleep(0.02)
        snap = gp.snapshot()
        assert snap["total_s"] == total1
        # the remainder keeps the identity exact even on a tiny window
        assert snap["productive_s"] == pytest.approx(0.010)
        gp.end_run()  # idempotent
        assert gp.snapshot()["total_s"] == total1

    def test_save_and_table(self, tmp_path, clean_ledgers):
        from rocket_tpu.observe.ledger import GoodputLedger

        gp = GoodputLedger()
        gp.start_run()
        gp.add("productive", 0.5)
        gp.note_preemption_loss(0.25, steps_replayed=3)
        gp.end_run()
        path = gp.save(str(tmp_path / "proj" / "goodput.json"))
        with open(path) as f:
            doc = json.load(f)
        assert doc["productive_s"] == pytest.approx(0.5)
        assert doc["preemption_loss_s"] == pytest.approx(0.25)
        text = gp.table()
        assert "goodput over" in text and "productive" in text


# -- device telemetry -------------------------------------------------------


@pytest.mark.goodput
class TestDeviceTelemetry:
    def test_memory_watermarks_cpu_emits_nothing(self, devices):
        from rocket_tpu.observe.ledger import memory_watermarks
        from rocket_tpu.observe.trace import Tracer

        # conftest forces JAX_PLATFORMS=cpu: no memory_stats() there —
        # the contract is "emit nothing", never crash
        t = Tracer(capacity=64, enabled=True)
        out = memory_watermarks(tracer=t)
        assert out == {}
        assert t.events() == []


# -- metrics export ---------------------------------------------------------

_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]* (NaN|[-+]?[0-9.]+(e[-+]?\d+)?)$"
)


def _assert_prometheus_parses(text):
    lines = [l for l in text.splitlines() if l]
    assert lines, "empty exposition"
    for line in lines:
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            continue
        assert _PROM_SAMPLE.match(line), f"unparseable sample: {line!r}"
    # every sample is declared
    assert any(l.startswith("# TYPE ") and l.endswith(" gauge")
               for l in lines)


@pytest.mark.goodput
class TestMetricsExport:
    def test_prometheus_text_parses(self, clean_ledgers):
        from rocket_tpu.observe.export import prometheus_text

        text = prometheus_text({
            "goodput/productive_s": 1.5,
            "serve/latency/p99": 0.25,
            "ledger/compiles": 3.0,
        })
        _assert_prometheus_parses(text)
        assert "rocket_tpu_goodput_productive_s 1.5" in text
        assert "rocket_tpu_serve_latency_p99 0.25" in text

    def test_live_collect_exports(self, clean_ledgers):
        from rocket_tpu.observe.export import collect, prometheus_text
        from rocket_tpu.observe.ledger import arm_ledgers, get_goodput

        arm_ledgers()
        get_goodput().add("productive", 0.1)
        snap = collect()
        assert snap["goodput/productive_s"] == pytest.approx(0.1)
        assert "ledger/compiles" in snap
        _assert_prometheus_parses(prometheus_text(snap))

    def test_register_source_and_failure_isolation(self, clean_ledgers):
        from rocket_tpu.observe.export import (
            collect,
            register_source,
            unregister_source,
        )

        register_source("probe", lambda: {"hits": 7})
        register_source("broken", lambda: 1 / 0)
        try:
            snap = collect()
            assert snap["probe/hits"] == 7.0
            assert not any(k.startswith("broken/") for k in snap)
        finally:
            unregister_source("probe")
            unregister_source("broken")

    def test_merge_counters_sum_and_percentile_max(self):
        from rocket_tpu.observe.export import merge_counters

        merged = merge_counters([
            {"serve/ok": 10.0, "serve/latency/p99": 0.5,
             "serve/latency/p50": 0.1,
             "serve_kvpool/fetches": 4.0,
             "serve_kvpool/occupancy_bytes": 1024.0,
             "serve_kvpool/capacity_bytes": 4096.0,
             "serve_kvstore/occupancy_bytes": 100.0},
            {"serve/ok": 5.0, "serve/latency/p99": 0.9,
             "serve/latency/p50": 0.05,
             "serve_kvpool/fetches": 3.0,
             "serve_kvpool/occupancy_bytes": 768.0,
             "serve_kvpool/capacity_bytes": 4096.0,
             "serve_kvstore/occupancy_bytes": 50.0},
        ])
        assert merged["serve/ok"] == 15.0           # counters SUM
        assert merged["serve/latency/p99"] == 0.9   # percentiles MAX
        assert merged["serve/latency/p50"] == 0.1
        # the pool is a singleton: its gauges MAX, its counters still SUM
        assert merged["serve_kvpool/fetches"] == 7.0
        assert merged["serve_kvpool/occupancy_bytes"] == 1024.0
        assert merged["serve_kvpool/capacity_bytes"] == 4096.0
        # per-replica kvstore occupancies are distinct stores — SUM
        assert merged["serve_kvstore/occupancy_bytes"] == 150.0

    def test_metrics_endpoint(self, clean_ledgers):
        from rocket_tpu.observe.export import MetricsServer
        from rocket_tpu.observe.ledger import arm_ledgers

        arm_ledgers()
        srv = MetricsServer(port=0).start()
        try:
            assert srv.running and srv.port > 0
            url = f"http://127.0.0.1:{srv.port}"
            with urllib.request.urlopen(f"{url}/metrics", timeout=5) as r:
                assert r.status == 200
                assert "version=0.0.4" in r.headers["Content-Type"]
                _assert_prometheus_parses(r.read().decode())
            with urllib.request.urlopen(f"{url}/metrics.json",
                                        timeout=5) as r:
                doc = json.loads(r.read().decode())
                assert "goodput/total_s" in doc
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"{url}/bogus", timeout=5)
        finally:
            srv.stop()
        assert not srv.running

    def test_export_cli_merges_snapshots(self, tmp_path, capsys):
        from rocket_tpu.observe.export import _main

        a = tmp_path / "replica0.json"
        b = tmp_path / "replica1.json"
        a.write_text(json.dumps(
            {"serve/ok": 10.0, "serve/latency/p99": 0.5,
             "serve_kvpool/bytes_moved": 2048.0,
             "serve_kvpool/occupancy_bytes": 512.0}))
        b.write_text(json.dumps(
            {"serve/ok": 5.0, "serve/latency/p99": 0.9,
             "serve_kvpool/bytes_moved": 1024.0,
             "serve_kvpool/occupancy_bytes": 640.0}))
        out = tmp_path / "fleet.json"
        assert _main([str(a), str(b), "--format", "json",
                      "-o", str(out)]) == 0
        with open(out) as f:
            merged = json.load(f)
        assert merged["serve/ok"] == 15.0
        assert merged["serve/latency/p99"] == 0.9
        assert merged["serve_kvpool/bytes_moved"] == 3072.0      # SUM
        assert merged["serve_kvpool/occupancy_bytes"] == 640.0   # MAX
        # prom format to stdout parses too
        capsys.readouterr()  # drain the first call's "wrote ..." notice
        assert _main([str(a), str(b)]) == 0
        _assert_prometheus_parses(capsys.readouterr().out)


# -- flight-dump retention + goodput rider ----------------------------------


@pytest.mark.goodput
class TestDumpRetention:
    def test_keep_last_prunes_oldest(self, tmp_path):
        from rocket_tpu.observe.recorder import FlightRecorder
        from rocket_tpu.observe.trace import Tracer

        rec = FlightRecorder(tracer=Tracer(enabled=False),
                             out_dir=str(tmp_path), keep_last=3)
        for i in range(5):
            rec.dump(f"round-{i}")
        dirs = sorted(os.listdir(tmp_path))
        assert len(dirs) == 3
        # lexicographic name order is creation order: the survivors are
        # the NEWEST three (seq 003..005), oldest two pruned
        assert [d.split("-")[2] for d in dirs] == ["003", "004", "005"]
        assert all("round" in d for d in dirs)

    def test_keep_last_zero_is_unbounded(self, tmp_path):
        from rocket_tpu.observe.recorder import FlightRecorder
        from rocket_tpu.observe.trace import Tracer

        rec = FlightRecorder(tracer=Tracer(enabled=False),
                             out_dir=str(tmp_path), keep_last=0)
        for i in range(5):
            rec.dump(f"round-{i}")
        assert len(os.listdir(tmp_path)) == 5

    def test_goodput_rides_along_in_dumps(self, tmp_path, clean_ledgers):
        from rocket_tpu.observe.ledger import (
            get_goodput,
            goodput_dump_writer,
        )
        from rocket_tpu.observe.recorder import (
            FlightRecorder,
            add_dump_writer,
            remove_dump_writer,
        )
        from rocket_tpu.observe.trace import Tracer

        gp = get_goodput()
        gp.start_run()
        gp.add("productive", 0.125)
        add_dump_writer(goodput_dump_writer)
        add_dump_writer(goodput_dump_writer)  # idempotent
        try:
            rec = FlightRecorder(tracer=Tracer(enabled=False),
                                 out_dir=str(tmp_path))
            path = rec.dump("watchdog")
            with open(os.path.join(path, "goodput.json")) as f:
                doc = json.load(f)
            assert doc["productive_s"] == pytest.approx(0.125)
            # core dump artifacts still present alongside the rider
            assert os.path.exists(os.path.join(path, "trace.json"))
            assert os.path.exists(os.path.join(path, "tail.txt"))
        finally:
            remove_dump_writer(goodput_dump_writer)
            remove_dump_writer(goodput_dump_writer)  # tolerant
