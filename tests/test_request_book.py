"""The serving loop's book of what other requests' admissions cost a
decoding row, and critpath's rules over it.

A toy batcher with no device stands under a real ``ServingLoop``: its
rows count tokens on the host, and each admission and each round moves a
stepped clock (the loop's ``clock=``) by a fixed amount, an admission
more than a round.  So every turn's length is known exactly, and so is
what the book must put on each row."""

import numpy as np
import pytest

from rocket_tpu.observe.critpath import SEGMENTS, analyze_events
from rocket_tpu.observe.trace import get_requests
from rocket_tpu.serve import Request, ServingLoop

pytestmark = pytest.mark.serving

ROUND_S = 0.020     # a round moves the clock this much
ADMIT_S = 0.050     # an admission this much
PROMPT = 8


class StepClock:
    def __init__(self) -> None:
        self.t = 100.0

    def __call__(self) -> float:
        return self.t


class ToyBatcher:
    """The batcher API the loop drives, on the host: ``admit`` writes a
    row's prompt, ``step`` adds one token to each live row; each moves
    the clock by its cost."""

    def __init__(self, clock: StepClock, rows: int,
                 total_len: int = 256) -> None:
        self.clock = clock
        self.n_draft = 1
        self.total_len = total_len
        self.prefix_cache_ok = True
        self.reads = None
        self.state = (np.zeros((rows, total_len), np.int32),
                      np.zeros(rows, np.int32), np.zeros(rows, bool))
        self.live = np.zeros(rows, bool)
        self.admits = 0

    def start(self, prompts: np.ndarray) -> None:
        self.state[0][:, :prompts.shape[1]] = prompts
        self.state[1][:] = prompts.shape[1]
        self.live[:] = True

    def retire(self, row: int) -> None:
        self.live[row] = False

    def admit(self, row: int, prompt: np.ndarray) -> None:
        self.clock.t += ADMIT_S
        self.admits += 1
        n = prompt.shape[1]
        self.state[0][row, :n] = prompt[0]
        self.state[1][row] = n
        self.live[row] = True

    def step(self):
        self.clock.t += ROUND_S
        buf, n_tok, done = self.state
        for row in np.flatnonzero(self.live):
            buf[row, n_tok[row]] = 7
            n_tok[row] += 1
        return n_tok, done

    def row_tokens(self, row: int):
        return self.state[0][row].copy(), int(self.state[1][row])


@pytest.fixture
def book():
    """A fresh process-wide record, left empty for other files."""
    get_requests().reset()
    yield get_requests()
    get_requests().reset()


def make_loop(rows: int = 4):
    clock = StepClock()
    loop = ServingLoop(lambda: ToyBatcher(clock, rows), max_batch=rows,
                       clock=clock)
    return loop, clock


def request(rid, new=12, cls="standard"):
    return Request(rid=rid, prompt=np.arange(1, PROMPT + 1, dtype=np.int32),
                   max_new_tokens=new, slo_class=cls)


def entries(record):
    return {e["rid"]: e for e in record.snapshot()}


def test_an_admission_turn_books_its_excess_on_the_decoding_rows_only(book):
    loop, clock = make_loop()
    loop.submit(request("a", new=20))
    loop.submit(request("b", new=20))
    for _ in range(4):                   # one admitting turn, three clean
        loop.run_round()
    loop.submit(request("c", new=6))
    loop.run_round()                     # admits c: ADMIT_S longer
    loop.run_until_idle()
    got = entries(book)
    for rid in ("a", "b"):
        assert got[rid]["stalled_turns"] == 1
        assert got[rid]["segments"]["admit_stall"] == \
            pytest.approx(ADMIT_S * 1e3, abs=1e-6)
    assert got["c"]["stalled_turns"] == 0
    assert got["c"]["segments"]["admit_stall"] == 0.0


def test_two_admissions_in_one_turn_book_both(book):
    loop, clock = make_loop()
    loop.submit(request("a", new=20))
    for _ in range(3):
        loop.run_round()
    loop.submit(request("b", new=4))
    loop.submit(request("c", new=4))
    loop.run_round()
    loop.run_until_idle()
    got = entries(book)
    assert got["a"]["stalled_turns"] == 1
    assert got["a"]["segments"]["admit_stall"] == \
        pytest.approx(2 * ADMIT_S * 1e3, abs=1e-6)


def test_a_clean_only_run_books_nothing(book):
    loop, clock = make_loop()
    for rid in ("a", "b", "c"):
        loop.submit(request(rid, new=9))
    loop.run_until_idle()               # all three in the first turn
    got = entries(book)
    assert sorted(got) == ["a", "b", "c"]
    for e in got.values():
        assert e["stalled_turns"] == 0
        assert e["segments"]["admit_stall"] == 0.0


def test_nothing_is_booked_before_a_clean_turn_is_seen(book):
    loop, clock = make_loop()
    loop.submit(request("a", new=10))
    loop.run_round()                    # admits a: the first turn, unmeasured
    loop.submit(request("b", new=4))
    loop.run_round()                    # admits b: no clean turn yet
    loop.run_until_idle()
    got = entries(book)
    assert got["a"]["stalled_turns"] == 0
    assert got["a"]["segments"]["admit_stall"] == 0.0


def test_an_idle_stretch_is_no_turn(book):
    loop, clock = make_loop()
    loop.submit(request("a", new=4))
    loop.run_until_idle()
    assert loop.run_round() is False    # idle: the next turn starts afresh
    clock.t += 30.0                     # a long quiet
    loop.submit(request("b", new=12))
    loop.run_round()                    # unmeasured: it has no start
    for _ in range(3):
        loop.run_round()                # clean turns of ROUND_S
    loop.submit(request("c", new=3))
    loop.run_until_idle()
    got = entries(book)
    assert got["b"]["segments"]["admit_stall"] == \
        pytest.approx(ADMIT_S * 1e3, abs=1e-6)


def _mixed_run(loop, clock, seed):
    """Requests arriving at random rounds, of random lengths, with time
    between submit and the next turn (queue wait)."""
    rng = np.random.default_rng(seed)
    rid = 0
    for _ in range(60):
        for _ in range(int(rng.poisson(0.4))):
            loop.submit(request(f"r{rid}", new=int(rng.integers(2, 25))))
            rid += 1
        clock.t += float(rng.uniform(0.0, 0.004))
        loop.run_round()
    loop.run_until_idle()
    return rid


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_each_request_is_booked_once_and_its_segments_sum_to_e2e(
        book, seed):
    loop, clock = make_loop(rows=3)
    n = _mixed_run(loop, clock, seed)
    record = book.snapshot()
    assert sorted(e["rid"] for e in record) == \
        sorted(f"r{i}" for i in range(n))
    assert any(e["segments"]["admit_stall"] > 0 for e in record)
    for e in record:
        assert set(e["segments"]) == set(SEGMENTS)
        assert sum(e["segments"].values()) == \
            pytest.approx(e["e2e_ms"], abs=1e-3)     # 1 microsecond
        assert e["end_s"] >= e["first_s"]
        assert e["outcome"] == "complete" and e["out"] >= 2


def test_a_preempted_request_keeps_its_book_through_the_resume(book):
    """A batch row preempted by an interactive arrival and resumed after
    it: one entry, parked time booked, the segments still sum to e2e."""
    loop, clock = make_loop(rows=1)
    loop.submit(request("batch", new=10, cls="batch"))
    for _ in range(3):
        loop.run_round()
    loop.submit(request("chat", new=3, cls="interactive"))
    loop.run_until_idle()
    got = entries(book)
    assert sorted(got) == ["batch", "chat"]
    batch = got["batch"]
    assert batch["out"] == 10
    # parked from the preemption until the turn that resumed it
    assert batch["segments"]["preempt_parked"] > 0.0
    for e in got.values():
        assert sum(e["segments"].values()) == \
            pytest.approx(e["e2e_ms"], abs=1e-3)


# -- critpath's rules over a ring --------------------------------------------


def _ring(events_ms):
    return [("X" if dur else "I", name, int(t * 1e6), int(dur * 1e6), 1,
             dict(fields)) for name, t, dur, fields in events_ms]


def test_critpath_reads_the_book_off_the_terminal():
    ring = _ring([
        ("serve/submit", 0, 0, {"rid": "r"}),
        ("serve/admit", 2, 1, {"rid": "r", "queue_wait_ms": 2.0}),
        ("serve/first_token", 50, 0, {"rid": "r", "ttft_ms": 50.0}),
        ("serve/complete", 150, 0, {"rid": "r", "e2e_ms": 150.0,
                                    "prefill_ms": 48.0,
                                    "admit_stall_ms": 30.0}),
    ])
    (p,) = analyze_events(ring)
    s = p.segments
    # the admission's device time is prefill, not the 1 ms dispatch
    assert s["prefill"] == pytest.approx(48.0)
    assert s["admit_stall"] == pytest.approx(30.0)
    assert s["decode_rounds"] == pytest.approx(150 - 50 - 30)
    assert sum(s.values()) == pytest.approx(p.e2e_ms)


def test_critpath_reads_an_old_dump_by_the_old_rules():
    """A terminal without ``prefill_ms`` (an older worker's) keeps the
    admit span as prefill and decodes from its end."""
    ring = _ring([
        ("serve/submit", 0, 0, {"rid": "r"}),
        ("serve/admit", 2, 1, {"rid": "r", "queue_wait_ms": 2.0}),
        ("serve/first_token", 50, 0, {"rid": "r", "ttft_ms": 50.0}),
        ("serve/complete", 150, 0, {"rid": "r", "e2e_ms": 150.0}),
    ])
    (p,) = analyze_events(ring)
    s = p.segments
    assert s["prefill"] == pytest.approx(1.0)
    assert s["decode_rounds"] == pytest.approx(150 - 3)
    assert s["admit_stall"] == 0.0


def test_critpath_over_the_loops_own_ring_agrees_with_its_record(book):
    """The analyser over the toy loop's ring (the tracer's clock) and the
    loop's record (its own clock) give the same stall and the same prefill
    and queue wait: one set of rules."""
    from rocket_tpu.observe.trace import Tracer

    clock = StepClock()
    tracer = Tracer(capacity=1 << 14, enabled=True)
    loop = ServingLoop(lambda: ToyBatcher(clock, 3), max_batch=3,
                       clock=clock, tracer=tracer)
    _mixed_run(loop, clock, seed=5)
    record = entries(book)
    paths = {p.rid: p for p in analyze_events(tracer.events())}
    assert sorted(paths) == sorted(str(r) for r in record)
    for rid, e in record.items():
        s = paths[str(rid)].segments
        for seg in ("queue_wait", "prefill", "admit_stall"):
            assert s[seg] == pytest.approx(e["segments"][seg], abs=1e-6)
