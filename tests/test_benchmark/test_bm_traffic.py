"""The traffic generators: the same requests for the same seed, the same
work in another order for another seed, and a lateness report."""

import json
import os

import numpy as np
import pytest

from benchmark import traffic

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark")


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name", ["serve-offline", "serve-chat"])
def test_same_seed_same_requests(name):
    a = traffic.serving_requests(mix(name), 32000, 2 ** 31 + 5, 100)
    b = traffic.serving_requests(mix(name), 32000, 2 ** 31 + 5, 100)
    assert [r.rid for r in a] == list(range(100))
    for x, y in zip(a, b):
        assert (x.prompt == y.prompt).all()
        assert (x.max_new, x.due_s, x.warm) == (y.max_new, y.due_s, y.warm)


@pytest.mark.parametrize("name", ["serve-offline", "serve-chat"])
def test_other_seed_same_sizes_in_another_order(name):
    m = mix(name)
    n, warm = m["cycle"], m["initial_in_service"]
    a = traffic.serving_requests(m, 32000, 1, warm + n)
    b = traffic.serving_requests(m, 32000, 2, warm + n)
    prompts = lambda rs: sorted(len(r.prompt) for r in rs[:n])  # noqa: E731
    assert prompts(a) == prompts(b)
    assert [len(r.prompt) for r in a[:n]] != [len(r.prompt) for r in b[:n]]
    if m["kind"] == "open":
        gaps = lambda rs: np.sort(np.diff(  # noqa: E731
            [0.0] + [r.due_s for r in rs[warm:warm + n]]))
        assert np.allclose(gaps(a), gaps(b))
        assert np.mean(gaps(a)) == pytest.approx(1 / m["rate_per_s"])


@pytest.mark.parametrize("name", ["serve-offline", "serve-chat"])
def test_sizes_keep_to_the_mix(name):
    m = mix(name)
    reqs = traffic.serving_requests(m, 32000, 7, 200)
    lo, hi = m["output_lognormal"]["min"], m["output_lognormal"]["max"]
    for r in reqs:
        assert len(r.prompt) in m["prompt_ladder"]
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 32000
        assert lo <= r.max_new <= hi
        assert len(r.prompt) + r.max_new <= m["max_total"]
    warm = [r for r in reqs if r.warm]
    assert len(warm) == m["initial_in_service"]
    assert all(r.due_s is None for r in warm)
    rest = [r for r in reqs if not r.warm]
    if m["kind"] == "open":
        dues = [r.due_s for r in rest]
        assert dues == sorted(dues) and dues[0] > 0
    else:
        assert all(r.due_s is None for r in rest)


def test_ladder_counts_follow_the_lognormal():
    ladder = [64, 128, 256, 512, 1024, 2048, 3072]
    counts = traffic.ladder_counts(ladder, 512, 1.0, 64)
    assert sum(counts) == 64 and all(c > 0 for c in counts)
    assert counts[3] == max(counts)          # the median's own step
    assert counts[0] < counts[2] and counts[-1] < counts[4]


def test_output_quantiles_are_clipped_whole_numbers():
    out = traffic.lognormal_quantiles(128, 0.7, 64, 16, 512)
    assert out.min() >= 16 and out.max() <= 512
    assert list(out) == sorted(out)
    assert 120 <= np.median(out) <= 136
    assert 140 < out.mean() < 170


def test_exponential_gaps_have_the_rate():
    gaps = traffic.exponential_gaps(0.88, 64)
    assert gaps.mean() == pytest.approx(1 / 0.88)
    assert (gaps > 0).all()


def test_lateness_is_reported_in_ms_and_never_negative():
    late = traffic.lateness_ms([1.0, 2.5, 2.9], [1.0, 2.0, 3.0])
    assert late == pytest.approx([0.0, 500.0, 0.0])


def test_markov_tokens_same_seed_same_rows_all_rows_differ():
    a = traffic.markov_tokens(64, 128, 50257, 2 ** 31 + 9)
    b = traffic.markov_tokens(64, 128, 50257, 2 ** 31 + 9)
    c = traffic.markov_tokens(64, 128, 50257, 3)
    assert a.dtype == np.int32 and a.shape == (64, 128)
    assert (a == b).all() and not (a == c).all()
    assert a.min() >= 0 and a.max() < 50257
    assert len({row.tobytes() for row in a}) == 64
