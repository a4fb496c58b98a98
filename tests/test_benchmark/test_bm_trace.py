"""The trace reduction, checked against a small trace recorded on the chip
(``data/probe.xplane.pb``, see ``data/README.txt``).  Counts and shares
only: every expectation is a fact of that recording, not a time of this
machine."""

import os

import pytest

from benchmark import trace_reduce
from benchmark.trace_reduce import Reduced

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def events():
    return trace_reduce.load_xplane(os.path.join(DATA, "probe.xplane.pb"))


@pytest.fixture(scope="module")
def reduced(events):
    return Reduced(events, chips=1)


def test_recorded_trace_has_one_device_plane_and_ten_programs(reduced):
    assert reduced.planes == ["/device:TPU:0"]
    assert len(reduced.program_seconds("jit_big")) == 5
    assert len(reduced.program_seconds("jit_small")) == 5


def test_per_program_time(reduced):
    big = reduced.program_seconds("jit_big")
    small = reduced.program_seconds("jit_small")
    assert all(3.6e-4 < s < 3.9e-4 for s in big), big
    assert all(2.0e-5 < s < 3.0e-5 for s in small), small
    assert trace_reduce.p50(big) == sorted(big)[2]
    assert reduced.program_seconds("no_such_program") == []


def test_busy_and_idle_share(reduced):
    # five iterations of ~0.4 ms of device work, ~11.6 ms apart
    total = sum(reduced.program_seconds("jit_big")) \
        + sum(reduced.program_seconds("jit_small"))
    assert reduced.busy_s <= total
    assert reduced.busy_s > 0.95 * total
    assert 0.045 < reduced.window_s < 0.05
    assert 0.95 < reduced.idle_share < 0.97


def test_gaps_before_a_program(reduced):
    # jit_small follows jit_big at once; jit_big waits for the host's sleep
    after_big = reduced.program_gaps("jit_small")
    after_sleep = reduced.program_gaps("jit_big")
    assert len(after_big) == 5 and len(after_sleep) == 4
    assert max(after_big) < 1e-5
    assert min(after_sleep) > 1e-2


def test_gap_attribution_by_host_span_and_by_neighbours(reduced):
    by_neighbours = dict(reduced.idle_gaps())
    assert "jit_small_-_jit_big" in by_neighbours
    assert by_neighbours["jit_small_-_jit_big"] > 0.04
    mods = sorted((e for e in reduced.events
                   if e[1] == trace_reduce.MODULES), key=lambda e: e[3])
    lo, hi = mods[1][3] + mods[1][4], mods[2][3]
    spans = [("serve/wait_for_request", lo - 10, hi + 10)]
    by_span = dict(reduced.idle_gaps(spans))
    assert by_span["serve/wait_for_request"] == pytest.approx(
        (hi - lo) / 1e9)
    assert by_span["jit_small_-_jit_big"] == pytest.approx(
        by_neighbours["jit_small_-_jit_big"] - (hi - lo) / 1e9)


def test_breakdown_names_ops_by_opcode_name_and_shape(reduced):
    top = reduced.top_ops()
    assert 1 <= len(top) <= 10
    assert top[0][0] == "fusion_fusion_bf16_2048_2048_"
    assert top == sorted(top, key=lambda kv: -kv[1])
    bd = reduced.breakdown()
    assert set(bd) == {"device_ops", "idle_gaps"}


def test_host_annotations_are_in_the_trace(events):
    steps = [e for e in events if e[2] == "bench/step"]
    assert len(steps) == 5 and {e[0] for e in steps} == {"/host:CPU"}


def test_window_can_be_given_and_clips_nothing(events):
    r = Reduced(events, chips=1, window_ns=(0.0, 1e8))
    assert r.window_s == pytest.approx(0.1)
    assert r.idle_share > 0.97


@pytest.mark.parametrize("intervals,want", [
    ([], 0.0),
    ([(0, 10)], 1e-8),
    ([(0, 10), (5, 20)], 2e-8),
    ([(0, 10), (20, 30)], 2e-8),
    ([(20, 30), (0, 10), (2, 4)], 2e-8),
])
def test_union_seconds(intervals, want):
    assert trace_reduce.union_seconds(intervals) == pytest.approx(want)


@pytest.mark.parametrize("text,want", [
    ("jit__spec_round(123)", "jit__spec_round"),
    ("jit_sync_step(9)", "jit_sync_step"),
    ("plain", "plain"),
])
def test_program_name(text, want):
    assert trace_reduce.program_name(text) == want


@pytest.mark.parametrize("text,want", [
    ("%fusion.3 = bf16[2048,2048]{1,0:T(8,128)(2,1)} fusion(bf16[8] %x), "
     "kind=kOutput", "fusion_fusion_bf16_2048_2048_"),
    ("%copy-done = bf16[24,4100,8,128]{3,2,1,0} copy-done(%copy-start)",
     "copy-done_copy-done_bf16_24_4100_8_128_".replace("-", "_")),
    ("%multiply_reduce_fusion.7 = f32[24,4100,32]{2,1,0} fusion(%a)",
     "fusion_multiply_reduce_fusion_f32_24_4100_32_"),
])
def test_op_label(text, want):
    assert trace_reduce.op_label(text) == want


def test_no_device_plane_reads_nothing():
    r = Reduced([("/host:CPU", "python", "x", 0.0, 5.0)], chips=1)
    assert r.busy_s == 0.0 and r.idle_share is None
    assert r.program_seconds("x") == [] and r.top_ops() == []
