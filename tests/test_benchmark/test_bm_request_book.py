"""``readers/request_book.py`` over a hand-built record of terminated
requests: the harness's ``tpot_p80_ms`` population (completed inside the
window, more than one output token), and the three quantities the chat
cell reports from the serving loop's admission book."""

import sys
import types

import pytest

from benchmark.harness import percentile
from benchmark.readers import request_book

T0, T1 = 10.0, 20.0


def entry(rid, first_s, end_s, out, stall_ms=0.0, turns=0,
          outcome="complete"):
    return {"rid": rid, "outcome": outcome, "first_s": first_s,
            "end_s": end_s, "out": out, "stalled_turns": turns,
            "e2e_ms": (end_s - first_s) * 1e3 + 50.0,
            "segments": {"admit_stall": stall_ms}}


RECORD = [
    entry("a", 11.0, 12.0, 51, stall_ms=100.0, turns=2),  # 1000 ms, 50 gaps
    entry("b", 12.0, 14.0, 101, stall_ms=300.0, turns=3),  # 2000 ms, 100
    entry("c", 13.0, 13.5, 26),                            # 500 ms, 25
    entry("early", 8.0, 9.5, 40, stall_ms=999.0, turns=9),  # before t0
    entry("late", 19.0, 20.5, 40, stall_ms=999.0, turns=9),  # after t1
    entry("one", 14.0, 14.0, 1, stall_ms=999.0, turns=9),   # out == 1
    entry("evicted", 14.0, 15.0, 40, stall_ms=999.0, turns=9,
          outcome="evict"),
]


@pytest.fixture
def record():
    from rocket_tpu.observe.trace import get_requests

    book = get_requests()
    book.reset()
    for e in RECORD:
        book.add(e)
    yield book
    book.reset()      # the record is the process's: leave it empty


def ctx():
    return {"run": {"t0": T0, "t1": T1}}


@pytest.mark.parametrize("what,expected", [
    # (1000 - 100) / 50, (2000 - 300) / 100, 500 / 25
    ("tpot_clean_p80", percentile([18.0, 17.0, 20.0], 80)),
    ("admit_stall_share", 100.0 * 400.0 / 3500.0),
    ("admit_stall_per_turn", 400.0 / 5),
])
def test_the_three_quantities_over_the_window(record, what, expected):
    assert request_book.read(ctx(), what) == pytest.approx(expected)


@pytest.mark.parametrize("what", [
    "tpot_clean_p80", "admit_stall_share", "admit_stall_per_turn"])
def test_an_empty_record_reads_nothing(what):
    from rocket_tpu.observe.trace import get_requests

    get_requests().reset()
    assert request_book.read(ctx(), what) is None


def test_the_window_and_the_token_count_choose_the_population():
    # only the out-of-population entries: nothing to read
    rest = [e for e in RECORD if e["rid"] in
            ("early", "late", "one", "evicted")]
    for what in ("tpot_clean_p80", "admit_stall_share",
                 "admit_stall_per_turn"):
        assert request_book.quantity(rest, T0, T1, what) is None
    # the window's ends are inside it
    edge = [entry("at_t1", 19.0, T1, 11, stall_ms=20.0, turns=1)]
    assert request_book.quantity(edge, T0, T1, "tpot_clean_p80") == \
        pytest.approx((1000.0 - 20.0) / 10)


def test_no_stalled_turn_reads_no_cost_a_turn():
    clean = [entry("c", 13.0, 13.5, 26)]
    assert request_book.quantity(clean, T0, T1, "admit_stall_share") == 0.0
    assert request_book.quantity(clean, T0, T1,
                                 "admit_stall_per_turn") is None


def test_an_unknown_quantity_is_an_error():
    with pytest.raises(ValueError, match="no quantity"):
        request_book.quantity(RECORD, T0, T1, "tpot_p99")


def test_a_program_without_the_record_reads_nothing(monkeypatch):
    # the parent commit: observe.trace has no get_requests
    monkeypatch.setitem(sys.modules, "rocket_tpu.observe.trace",
                        types.ModuleType("rocket_tpu.observe.trace"))
    assert request_book.read(ctx(), "tpot_clean_p80") is None
