"""The readers of the program's own record (PR 24): its spans in the trace's
host plane, its start-up record, its compile-cache counters and its goodput
ledger.  Against the trace recorded on the chip (``data/probe.xplane.pb``)
or a small list of events; counts and shares, never a time of this machine.
A reader that finds nothing to read returns ``None`` and does not raise:
the parent commit, which lacks what PR 24 adds, has to pass through them."""

import logging
import os
import sys

import pytest

from benchmark import harness, trace_reduce
from benchmark.archs import decoder
from benchmark.readers import (_program, compile_cache_count, goodput_share,
                               host_gap_p50, idle_unattributed,
                               kernel_roofline, span_ratio, startup_seconds)
from benchmark.trace_reduce import MODULES, OPS, Reduced

DATA = os.path.join(os.path.dirname(__file__), "data")
DEV, HOST = "/device:TPU:0", "/host:CPU"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1e6


@pytest.fixture(scope="module")
def probe():
    return trace_reduce.load_xplane(os.path.join(DATA, "probe.xplane.pb"))


def ctx_of(events, **more):
    ctx = {"trace": Reduced(events, chips=1), "peaks": PEAKS, "run": {}}
    ctx.update(more)
    return ctx


def serving_events():
    """Three rounds of a serving loop: a program on the device, then the
    host's fetches, harvest and the next dispatch; one runtime TraceMe."""
    events = []
    for i in range(3):
        t = i * 200 * MS
        events += [
            (DEV, MODULES, "jit__spec_round(1)", t + 2 * MS, 180 * MS),
            (HOST, "python", "serve/round", t, 186 * MS),
            (HOST, "python", "serve/dispatch", t, 1 * MS),
            (HOST, "python", "serve/fetch", t + 1 * MS, 182 * MS),
            (HOST, "python", "serve/fetch", t + 183 * MS, 1 * MS),
            (HOST, "worker/7", "serve/fetch", t + 184 * MS, 2 * MS),
            (HOST, "python", "serve/harvest", t + 187 * MS, 3 * MS),
            (HOST, "python", "serve/fetch", t + 188 * MS, 1 * MS),
            (HOST, "python", "PjitFunction(_spec_round)", t, 1 * MS),
        ]
    return events


# -- which host events are the program's -------------------------------------


@pytest.mark.parametrize("name,ours", [
    ("serve/fetch", True), ("looper/TRAIN/iter", True),
    ("train/step_dispatch", True), ("Optimizer.launch", True),
    ("Module.setup", True), ("startup/first_dispatch", True),
    ("PjitFunction(sync_step)", False), ("ExecuteOnLocalDevices", False),
    ("$profiler.py:91 start_trace", False), ("Thread.run", False),
])
def test_program_span_names(name, ours):
    events = [(HOST, "python", name, 0.0, 5.0),
              (HOST, "python", "bench/anchor", 0.0, 1.0)]
    spans = _program.program_spans(Reduced(events))
    assert [s[0] for s in spans] == ([name] if ours else [])


def test_program_spans_read_every_line_of_the_host_plane(probe):
    spans = _program.program_spans(Reduced(probe))
    assert [s[0] for s in spans] == ["bench/step"] * 5
    assert spans == sorted(spans, key=lambda s: s[1])
    by_name = _program.program_spans(Reduced(serving_events()), "serve/fetch")
    assert len(by_name) == 12          # the worker thread's three included


# -- idle the program's spans cannot name ------------------------------------


def test_idle_unattributed_share_of_the_recorded_trace(probe):
    # each bench/step span covers the short gap between jit_big and
    # jit_small; the host's sleeps between iterations lie outside them
    share = idle_unattributed.read(ctx_of(probe))
    assert 99.0 < share <= 100.0
    reduced = Reduced(probe)
    lo, hi = reduced.window_ns
    covered = list(probe) + [(HOST, "python", "Tracker.launch", lo, hi - lo)]
    assert idle_unattributed.read(ctx_of(covered)) == 0.0


@pytest.mark.parametrize("name", [
    "looper/TRAIN/iter", "looper/EVAL/iter", "serve/round", "Looper.launch"])
def test_a_span_that_encloses_the_loop_names_no_idle(probe, name):
    # such a span tiles the host's timeline: counted, it would read 0 by
    # construction whatever the program leaves unnamed inside a turn
    bare = idle_unattributed.read(ctx_of(probe))
    lo, hi = Reduced(probe).window_ns
    enclosed = list(probe) + [(HOST, "python", name, lo, hi - lo)]
    assert idle_unattributed.read(ctx_of(enclosed)) == bare


def test_idle_unattributed_share_of_a_small_list():
    events = [
        (DEV, MODULES, "jit_a(1)", 0.0, 10.0),
        (DEV, MODULES, "jit_a(1)", 20.0, 10.0),     # gap 10..20, mid 15
        (DEV, MODULES, "jit_a(1)", 60.0, 10.0),     # gap 30..60, mid 45
        (HOST, "python", "Optimizer.launch", 12.0, 6.0),    # covers 15
        (HOST, "python", "PjitFunction(a)", 0.0, 100.0),    # the runtime's
    ]
    assert idle_unattributed.read(ctx_of(events)) == pytest.approx(75.0)
    no_gaps = events[:1]
    assert idle_unattributed.read(ctx_of(no_gaps)) is None
    assert idle_unattributed.read(ctx_of([])) is None       # a CPU run


# -- the serving host loop ---------------------------------------------------


def test_host_gap_is_last_fetch_to_next_dispatch():
    # round i's last fetch ends at t+189 ms; round i+1 dispatches at t+200
    assert host_gap_p50.read(ctx_of(serving_events())) == pytest.approx(11.0)
    # the first dispatch has no fetch before it and is left out
    one_round = serving_events()[:9]
    assert host_gap_p50.read(ctx_of(one_round)) is None
    assert host_gap_p50.read(ctx_of([])) is None


def test_fetches_per_round():
    ctx = ctx_of(serving_events())
    assert span_ratio.read(ctx, "serve/fetch", "serve/round") == 4.0
    assert span_ratio.read(ctx, "serve/fetch", "serve/no_such") is None
    assert span_ratio.read(ctx_of([]), "serve/fetch", "serve/round") is None


# -- one kernel's roofline ---------------------------------------------------


class _Cell:
    family = decoder
    arch = {"heads": 16, "head_dim": 64, "layers": 24}
    traffic = {"batch": 8, "seq": 1024}


def kernel_events(per_call_ns):
    text = ("%{name}.{i} = bf16[8,16,1024,64]{{3,2,1,0}} custom-call(%a, %b), "
            'custom_call_target="tpu_custom_call"')
    events = [(DEV, MODULES, "jit_sync_step(9)", 0.0, 1e9),
              (DEV, MODULES, "jit_sync_step(9)", 2e9, 1e9),
              (DEV, OPS, "%fusion.3 = bf16[8,1024] fusion(%x)", 0.0, 5e8)]
    for step in (0, 1):
        for layer in range(24):
            for k, name in enumerate(("flash_fwd", "flash_dq", "flash_dkv")):
                events.append((DEV, OPS, text.format(name=name, i=layer),
                               step * 2e9 + layer * 1e6 + k * 1e5,
                               per_call_ns * (k + 1)))
    return events


def test_kernel_roofline_tells_the_three_kernels_apart():
    from benchmark import counts

    ctx = ctx_of(kernel_events(1e5), cell=_Cell)
    cost = _Cell.family.counts.flash_kernel_cost(_Cell.arch, 8, 1024)
    got = {k: kernel_roofline.read(ctx, k, f"flash_{k}")
           for k in ("fwd", "dq", "dkv")}
    for i, k in enumerate(("fwd", "dq", "dkv")):
        spent = 2 * 24 * 1e5 * (i + 1) / 1e9
        want = 100 * 2 * counts.roofline_seconds(cost[k], PEAKS) / spent
        assert got[k] == pytest.approx(want)
    # a step with a second Pallas kernel in it does not disturb them
    extra = kernel_events(1e5) + [(
        DEV, OPS, '%int8_matmul.1 = bf16[8,64] custom-call(%a), '
        'custom_call_target="tpu_custom_call"', 5e6, 1e9)]
    assert kernel_roofline.read(ctx_of(extra, cell=_Cell), "dq",
                                "flash_dq") == pytest.approx(got["dq"])


def test_kernel_roofline_reads_nothing_from_unnamed_kernels(probe):
    # the parent's Mosaic calls carry the flax module's name (%attn.5)
    unnamed = [(p, l, n.replace("flash_fwd", "attn"), s, d)
               for p, l, n, s, d in kernel_events(1e5)]
    assert kernel_roofline.read(ctx_of(unnamed, cell=_Cell), "fwd",
                                "flash_fwd") is None
    assert kernel_roofline.read(ctx_of(probe, cell=_Cell), "fwd",
                                "flash_fwd") is None
    no_peaks = ctx_of(kernel_events(1e5), cell=_Cell, peaks=None)
    assert kernel_roofline.read(no_peaks, "fwd", "flash_fwd") is None


# -- the program's own records, in process -----------------------------------


@pytest.fixture()
def startup():
    from rocket_tpu.observe.trace import get_startup

    record = get_startup()
    kept, logged, cache = record.events(), record.logged, record.cache
    record.clear()
    yield record
    record.clear()
    for name, ts, dur, fields in kept:
        record.mark(name, ts, ts + dur, **fields)
    record.logged, record.cache = logged, cache


def test_startup_seconds_before_the_window(startup, monkeypatch):
    monkeypatch.setattr(harness, "PROCESS_START", 100.0)
    s = 1_000_000_000
    startup.mark("startup/import", 101 * s, 106 * s, package="rocket_tpu")
    startup.mark("startup/runtime", 106 * s, 107 * s)
    startup.mark("startup/build", 107 * s, 110 * s)
    startup.mark("startup/first_dispatch", 110 * s, 114 * s, edge="a")
    startup.mark("startup/first_dispatch", 130 * s, 131 * s, edge="late")
    ctx = {"run": {"setup_s": 20.0}}            # the window opened at 120
    assert startup_seconds.read(ctx, ["startup/import"]) == 5.0
    assert startup_seconds.read(ctx, ["startup/runtime",
                                      "startup/build"]) == 4.0
    assert startup_seconds.read(ctx, ["startup/first_dispatch"]) == 4.0
    assert startup_seconds.read(ctx, ["startup/serve_warm_start"]) is None
    assert startup_seconds.read({"run": {}}, ["startup/first_dispatch"]) == 5.0


def test_cache_counts_are_those_of_the_start_up_line(startup, monkeypatch):
    from rocket_tpu.tune import compile_cache

    compile_cache.install_listeners()
    assert compile_cache_count.read({}, "misses") is None   # not logged yet
    monkeypatch.setitem(compile_cache._state, "requests", 20)
    monkeypatch.setitem(compile_cache._state, "hits", 17)
    startup.log_once(logging.getLogger("test.readers"))
    # what compiles after the line (the reference, the harness) is not start-up
    monkeypatch.setitem(compile_cache._state, "requests", 99)
    assert compile_cache_count.read({}, "misses") == 3.0
    assert compile_cache_count.read({}, "hits") == 17.0
    assert compile_cache_count.read({}, "no_such_counter") is None


def test_cache_counts_read_nothing_without_the_programs_listeners(
        startup, monkeypatch):
    from rocket_tpu.tune import compile_cache

    monkeypatch.setitem(compile_cache._state, "listeners", False)
    startup.log_once(logging.getLogger("test.readers"))
    assert compile_cache_count.read({}, "misses") is None


def test_goodput_share_reads_the_ledgers_two_loop_buckets():
    from rocket_tpu.observe.ledger import disarm_ledgers, get_goodput

    disarm_ledgers()
    gp = get_goodput()
    gp.start_run()
    try:
        assert goodput_share.read({}) is None        # nothing booked yet
        gp.add("productive", 9.0)
        gp.add("host_blocked", 1.0)
        gp.add("compile", 30.0, nested=True)         # booked elsewhere
        assert goodput_share.read({}) == pytest.approx(90.0)
    finally:
        disarm_ledgers()


@pytest.mark.parametrize("module", [
    "startup_seconds", "compile_cache_count", "goodput_share"])
def test_in_process_readers_read_nothing_from_a_program_without_the_record(
        module, monkeypatch):
    # the parent commit: the import the reader needs is not there
    for name in ("rocket_tpu.observe.trace", "rocket_tpu.observe.ledger",
                 "rocket_tpu.tune.compile_cache", "rocket_tpu.tune"):
        monkeypatch.setitem(sys.modules, name, None)
    reader = {"startup_seconds": startup_seconds,
              "compile_cache_count": compile_cache_count,
              "goodput_share": goodput_share}[module]
    args = {"startup_seconds": (["startup/import"],),
            "compile_cache_count": ("misses",), "goodput_share": ()}[module]
    assert reader.read({"run": {"setup_s": 1.0}}, *args) is None
