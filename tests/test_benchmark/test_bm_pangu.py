"""The ``pangu_moe`` architecture through the harness: a toy-width
configuration of it (``pangu_toy/``: a share of four of sixteen experts, a
dense first layer, the multi-token-prediction draft) served through
``closed`` on the CPU is ``correct``, three planted faults are not, and the
counts at the real cell's sizes are what ISSUE 28 reckoned by hand."""

import os

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "pangu_toy")
SEED = 2 ** 31 + 29


@pytest.fixture(autouse=True)
def work_dir_of_its_own(tmp_path, monkeypatch):
    """Other files run cells too, in other xdist workers: keep this file's
    traces out of the checkout's one ``.benchwork/``."""
    def work_dir(name):
        path = tmp_path / name
        path.mkdir(exist_ok=True)
        return str(path)

    monkeypatch.setattr(harness, "work_dir", work_dir)


@pytest.fixture(autouse=True)
def weights_large_enough_to_tell(monkeypatch):
    """At the benchmark's normal(0, 0.02) and the toy's widths every
    attention score is a few hundredths and the softmax is flat whatever
    its scale; at 0.3 the scores are of order one, as they are at the
    published widths, and a wrong scale serves other tokens.  Program and
    reference draw through the same ``weights`` module."""
    from benchmark import weights
    from rocket_tpu.models import moe

    monkeypatch.setattr(weights, "INIT_STD", 0.3)
    # both paths of the expert layer: a round's 8 tokens through every held
    # expert, a 16-token prompt's slots grouped
    monkeypatch.setattr(moe, "DENSE_BELOW", 12)
    weights.release()
    yield
    weights.release()


def toy_cell():
    manifest = harness.load_json(os.path.join(TOY, "BENCHMARK.json"))
    return harness.resolve_cell("pangu-closed", manifest, bench_dir=TOY)


def test_toy_cell_is_correct_and_reads_its_counters():
    from benchmark.archs import pangu_moe

    cell = toy_cell()
    assert cell.family is pangu_moe          # the benchmark's own module
    result = harness.run_cell(cell, SEED, 0.5, True)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    # four rows x two tokens x top-4, a quarter of the sixteen experts held
    assert 20.0 < got["toy_held_slot_share"] < 30.0
    assert 1.5 < got["toy_expert_tokens_per_round"] < 2.5
    assert 1.0 <= got["toy_expert_load_max_over_mean"] < 4.0
    assert 0.0 <= got["toy_accept_rate"] < 20.0     # random weights


def _without_shared(monkeypatch, family):
    real = family.program
    monkeypatch.setattr(family, "program", lambda arch, **kw: real(
        dict(arch, shared=0), **kw))


def _share_shifted(monkeypatch, family):
    real = family.program
    monkeypatch.setattr(family, "program", lambda arch, **kw: real(
        dict(arch, held_start=arch["held_start"] + 1), **kw))


def _absorbed_scaled_by_nope_alone(monkeypatch, family):
    from rocket_tpu.models import transformer

    real = transformer.dot_attention

    def wrong(q, k, v=None, **kw):
        if v is None:               # the absorbed path: 1/sqrt(nope)
            kw["scale"] = 8 ** -0.5
        return real(q, k, v, **kw)

    monkeypatch.setattr(transformer, "dot_attention", wrong)


@pytest.mark.parametrize("fault", [
    _without_shared, _share_shifted, _absorbed_scaled_by_nope_alone])
def test_a_planted_fault_is_not_correct(monkeypatch, fault):
    """The shared expert left out, the held share shifted by one expert,
    the absorbed path scaled by ``1/sqrt(nope)``: each serves tokens the
    reference would not have, and ``served_gap`` says so."""
    cell = toy_cell()
    fault(monkeypatch, cell.family)
    result = harness.run_cell(cell, SEED, 0.5, False)
    assert result["correct"] is False
    pair = result["compared"]["served_gap"]
    assert pair["value"] > pair["limit"]


def test_counts_at_the_cells_sizes():
    """12.08 GB of weights and 6.9 KB of cache a token (ISSUE 28), from the
    real cell's configuration file."""
    cell = harness.resolve_cell("pangu718b-serve-reasoning")
    arch, counts = cell.arch, cell.family.counts
    draft = cell.family.draft(arch, cell.config["serving"])
    assert counts.attention_params(arch) == 196_575_232          # 196.6 M
    assert counts.expert_params(arch) == 47_185_920              # 47.2 M
    assert counts.layer_params(arch, False) == 621_248_512       # 621.2 M
    assert round(counts.weights_bytes(arch, draft) / 1e9, 2) == 12.08
    assert counts.cache_bytes_per_token(arch, draft) == 6912     # 6.9 KB
    # every matrix the program holds, and nothing else but the norms
    shapes = {**cell.family.leaf_shapes(arch),
              **cell.family.leaf_shapes(draft, "draft.")}
    import math

    matrices = sum(math.prod(s) for s in shapes.values() if len(s) > 1)
    assert matrices * 2 == counts.weights_bytes(arch, draft)
    # a round: the weights but the embedding table, the head twice, and the
    # live latents once a pass and a layer
    live, rows = 32 * 1000.0, 32
    cost = counts.decode_round_cost(arch, draft, 1, live, rows)
    table = arch["hidden"] * arch["vocab_padded"] * 2
    assert cost["bytes"] == counts.weights_bytes(arch, draft) - table \
        + table + live * 6912
    assert abs(cost["bytes"] / 819e9 - 0.0150) < 0.0005
    # a token meets 8 x 16/256 routed experts here on average
    per_token = counts.layer_params_per_token(arch, True)
    assert per_token == counts.attention_params(arch) + 7680 * 256 \
        + 1.5 * counts.expert_params(arch)
