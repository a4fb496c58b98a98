"""The decoder architecture's ``counts`` against FLOPs and bytes worked out
by hand for both configurations, and the peaks table."""

import json
import os

import pytest

from benchmark import harness
from benchmark.archs import decoder as family
from benchmark.counts import roofline_seconds

counts = family.counts

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark")


def arch(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as fh:
        return family.normalise(json.load(fh))


def test_gpt2_medium_parameters_by_hand():
    a = arch("gpt2-medium")
    # one block: q,k,v,o 4 x 1024^2 and the MLP 2 x 1024 x 4096
    assert counts.layer_params(a) == 4 * 1024 ** 2 + 2 * 1024 * 4096
    assert counts.layer_params(a) == 12_582_912
    # 24 blocks + the tied head over the padded vocabulary
    assert counts.matmul_params(a) == 24 * 12_582_912 + 1024 * 50304
    # held: blocks + table + positions (355 M with biases and norms)
    assert counts.total_params(a) == 301_989_888 + 51_511_296 + 1024 * 1024
    assert 353e6 < counts.total_params(a) < 356e6


def test_gpt2_medium_train_flops_per_token_by_hand():
    a = arch("gpt2-medium")
    dense = 6 * (301_989_888 + 51_511_296)
    # causal attention: 4*S*S*H*D/2 per layer and row, over S tokens, x3
    attn = 3 * 24 * (4 * 1024 * 1024 * 16 * 64 / 2) / 1024
    assert counts.train_flops_per_token(a, 1024) == pytest.approx(dense + attn)
    assert 2.2e9 < counts.train_flops_per_token(a, 1024) < 2.4e9


def test_flash_kernel_cost_by_hand():
    a = arch("gpt2-medium")
    cost = counts.flash_kernel_cost(a, batch=8, seq=1024)
    prod = 2 * 1024 * 1024 * 64 / 2 * 16 * 8 * 24      # one causal product
    tensor = 8 * 1024 * 16 * 64 * 2 * 24               # one bf16 operand
    assert cost["fwd"] == {"flops": 2 * prod, "bytes": 4 * tensor}
    assert cost["dq"] == {"flops": 3 * prod, "bytes": 6 * tensor}
    assert cost["dkv"] == {"flops": 4 * prod, "bytes": 7 * tensor}
    # nine products of 2*S*S*D/2 flops: three times the forward's pair
    total = sum(c["flops"] for c in cost.values())
    assert total == pytest.approx(
        3 * 24 * 8 * counts.attention_flops(a, 1024, 1024, True) * 9 / 6)


def test_mistral_l8_parameters_and_cache_by_hand():
    a = arch("mistral-7b-l8")
    per_layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert counts.layer_params(a) == per_layer == 218_103_808
    assert counts.matmul_params(a) == 8 * per_layer + 4096 * 32000
    # held: 8 layers, embedding and untied head: 2.0 B parameters
    assert counts.total_params(a) == 8 * per_layer + 2 * 4096 * 32000
    assert 2.0e9 < counts.total_params(a) < 2.02e9
    # K and V of one token: 2 x 8 heads x 128 x 2 bytes x 8 layers
    assert counts.kv_bytes_per_token(a) == 32768


def test_decode_round_cost_by_hand():
    a = arch("mistral-7b-l8")
    d = family.draft(a, {"draft_layers": 2})
    live = 24 * 1000.0
    cost = counts.decode_round_cost(a, d, n_draft=4, live_tokens=live,
                                    rows=24)
    table = 4096 * 32000 * 2
    t_w = counts.total_params(a) * 2 - table
    d_w = counts.total_params(d) * 2 - table
    t_kv, d_kv = live * 32768, live * 32768 / 4
    assert cost["bytes"] == pytest.approx(t_w + t_kv + 5 * (d_w + d_kv))
    t_fl = 2 * counts.matmul_params(a) * 5 * 24 + 8 * 4 * 5 * live * 4096
    d_fl = 2 * counts.matmul_params(d) * 24 + 2 * 4 * live * 4096
    assert cost["flops"] == pytest.approx(t_fl + 5 * d_fl)
    peak = harness.peaks_for("TPU v5 lite")
    # bandwidth bounds a decode round: several GB against a few TFLOP
    assert cost["bytes"] / peak["hbm_bytes_per_s"] \
        > cost["flops"] / peak["bf16_flops_per_s"]
    assert roofline_seconds(cost, peak) == pytest.approx(
        cost["bytes"] / 819e9)


def test_serve_flops_by_hand():
    a = arch("mistral-7b-l8")
    got = counts.serve_flops(a, prompt_tokens=1000, output_tokens=100,
                             context_token_products=5e5)
    want = 2 * counts.matmul_params(a) * 1100 + 8 * 4 * 5e5 * 4096
    assert got == pytest.approx(want)


def test_peaks_table_names_its_source_and_refuses_unknown_kinds():
    peak = harness.peaks_for("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9 and peak["hbm_bytes"] == 16e9
    with open(os.path.join(BENCH, "peaks.json")) as fh:
        assert "TPU v5e" in json.load(fh)["source"]
    for kind in ("cpu", "TPU v9 mega", "source"):
        with pytest.raises(harness.BenchmarkError):
            harness.peaks_for(kind)
