"""The ``keye_moe`` architecture through the harness: a toy-width
configuration of it (``keye_toy/``: a share of four of sixteen softmax-routed
experts, an indexer that keeps 4 keys a query, prompts admitted in chunks of
4, a two-model draft; float32 weights, since of four keys a query a bfloat16
near tie in the index scores swaps a quarter of what a query attends, where
of the published 2,048 it swaps a two-thousandth) served through ``closed``
on the CPU is ``correct`` and reads its counters, three planted faults are
not, the manifest's new entries resolve, and the counts at the real cell's
sizes are what ISSUE 33 reckoned by hand."""

import json
import math
import os

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.normpath(os.path.join(HERE, "..", ".."))
TOY = os.path.join(HERE, "keye_toy")
CELL = "keye30b-serve-longctx"
SEED = 2 ** 31 + 33


@pytest.fixture(autouse=True)
def work_dir_of_its_own(tmp_path, monkeypatch):
    """Other files run cells too, in other xdist workers: keep this file's
    traces out of the checkout's one ``.benchwork/``."""
    def work_dir(name):
        path = tmp_path / name
        path.mkdir(exist_ok=True)
        return str(path)

    monkeypatch.setattr(harness, "work_dir", work_dir)


@pytest.fixture
def weights_large_enough_to_tell(monkeypatch):
    """At the benchmark's normal(0, 0.02) and the toy's widths every score
    is a few hundredths and both softmaxes are flat whatever they are
    given; at 0.3 the scores are of order one, as at the published widths,
    and a wrong selection or share serves other tokens."""
    from benchmark import weights
    from rocket_tpu.models import moe
    from rocket_tpu.observe.trace import get_rounds

    monkeypatch.setattr(weights, "INIT_STD", 0.3)
    # both paths of the expert layer: a round's 8 tokens through every held
    # expert, a 4-token chunk's 16 slots grouped
    monkeypatch.setattr(moe, "DENSE_BELOW", 3)
    weights.release()
    get_rounds().reset()
    yield
    weights.release()
    # the record is the process's: leave it as other files' tests expect it
    get_rounds().reset()


def toy_cell():
    manifest = harness.load_json(os.path.join(TOY, "BENCHMARK.json"))
    return harness.resolve_cell("keye-closed", manifest, bench_dir=TOY)


def test_toy_cell_is_correct_and_reads_its_counters(
        weights_large_enough_to_tell):
    from benchmark.archs import keye_moe

    cell = toy_cell()
    assert cell.family is keye_moe           # the benchmark's own module
    result = harness.run_cell(cell, SEED, 0.5, True)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    # 4 keys of contexts of 15 to 43
    assert 9.0 < got["toy_selected_key_share"] < 27.0
    # a quarter of the sixteen experts held, top-4 of an even router
    assert 15.0 < got["toy_held_slot_share"] < 35.0
    assert 1.0 < got["toy_expert_tokens_per_round"] < 3.0
    # the CPU's trace has no device plane: no device time to share out
    assert "toy_admit_busy_share" not in got


def _selection_ignored(monkeypatch, family):
    """Every key attended: ``top_k`` as long as the slab."""
    real = family.program
    monkeypatch.setattr(family, "program", lambda arch, **kw: real(
        dict(arch, select_top_k=4096), **kw))


def _share_shifted(monkeypatch, family):
    real = family.program
    monkeypatch.setattr(family, "program", lambda arch, **kw: real(
        dict(arch, held_start=arch["held_start"] + 1), **kw))


def _sigmoid_router(monkeypatch, family):
    from rocket_tpu.models import moe

    real = moe.ExpertsConfig

    def sigmoid(**kw):
        return real(**dict(kw, router="sigmoid"))

    monkeypatch.setattr(moe, "ExpertsConfig", sigmoid)


@pytest.mark.parametrize("fault", [
    _selection_ignored, _share_shifted, _sigmoid_router])
def test_a_planted_fault_is_not_correct(monkeypatch, fault,
                                        weights_large_enough_to_tell):
    """Attention over every key, the held share shifted by one expert, a
    sigmoid router: each serves tokens the reference would not have, and
    ``served_gap`` says so."""
    cell = toy_cell()
    fault(monkeypatch, cell.family)
    result = harness.run_cell(cell, SEED, 0.5, False)
    assert result["correct"] is False
    pair = result["compared"]["served_gap"]
    assert pair["value"] > pair["limit"]


def test_a_program_without_the_selection_is_refused_cleanly(monkeypatch):
    """On a checkout whose program knows no ``SelectConfig`` (this PR's
    parent under this PR's benchmark files) the architecture refuses as the
    benchmark refuses: a ``BenchmarkError``, exit 2, no hang."""
    from rocket_tpu.models import transformer

    cell = harness.resolve_cell(CELL)
    monkeypatch.delattr(transformer, "SelectConfig")
    with pytest.raises(harness.BenchmarkError, match="keye_moe"):
        cell.family.program(cell.arch, max_seq=64)


# -- the manifest's new entries ----------------------------------------------


def test_the_cell_its_files_and_its_metrics_resolve():
    manifest = harness.load_manifest()
    cell = harness.resolve_cell(CELL, manifest)
    assert cell.chips == 1 and cell.kind == "closed"
    assert all(hasattr(cell.family, name) for name in harness.FAMILY_NAMES)
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer()}
    assert names >= {
        "keye_round_ms_p50", "keye_round_gap_ms_p50", "keye_admit_device_ms",
        "keye_admit_busy_share", "keye_tokens_per_round_row",
        "serve_step_mfu.keye", "decode_round_roofline.keye",
        "device_idle_share.keye", "idle_unattributed_share.keye",
        "keye_host_gap_ms_p50", "keye_fetches_per_round",
        "keye_selected_key_share", "keye_expert_tokens_per_round",
        "keye_expert_load_max_over_mean", "keye_held_slot_share",
        "keye_setup_import_s", "keye_setup_first_dispatch_s",
        "keye_setup_warm_start_s", "keye_setup_cache_misses",
        "keye_select_attention_roofline", "compiles_in_window"}
    for name in names:
        spec = harness.load_json(os.path.join(
            harness.HERE, "metrics", name + ".json"))
        harness._load_module(harness.HERE, "readers", spec["reader"])
    limits = harness.load_json(os.path.join(
        harness.HERE, "limits", CELL + ".json"))
    assert set(limits) == {"served_gap"} and 0 < limits["served_gap"] < 1


def test_the_configuration_keeps_every_published_width():
    """Against the catalog row the driver drew: every number of its
    ``config`` under the same key, but those in ``reduced``; nested groups
    whole."""
    catalog = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "num_local_experts": 128,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    config = harness.resolve_cell(CELL).config
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts",
                       "num_local_experts", "vocab_size"}
    for key, value in catalog.items():
        if key in reduced:
            assert config["published"][key] == value
            assert config[key] < value
        else:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 6 and config["num_experts"] == 16
    assert config["vocab_size"] * 8 == 151936
    deployment = config["deployment"]
    assert deployment["chips_per_layer"] == deployment["pipeline_stages"] == 8
    assert deployment["router_width"] == 128
    assert {"qk_norm", "indexer_input", "indexer_key_norm", "indexer_rope",
            "indexer_scale", "chunks", "departures", "serving"} \
        <= set(config["assumed"])


def test_the_traffic_file_holds_the_named_parameters():
    mix = harness.resolve_cell(CELL).traffic
    mix = {k: v for k, v in mix.items() if k != "why"}
    assert mix == {
        "kind": "closed", "cycle": 16,
        "prompt_ladder": [4096, 8192, 16384],
        "prompt_lognormal": {"median": 8192, "sigma": 0.6},
        "output_lognormal": {"median": 768, "sigma": 0.7, "min": 128,
                             "max": 3072},
        "max_total": 20480, "initial_in_service": 16, "backlog": 2,
        "expected_per_s": 3, "trace_seconds": 8}
    from benchmark import traffic

    assert traffic.ladder_counts(
        mix["prompt_ladder"], 8192, 0.6, 16) == [5, 7, 4]


# -- counts against hand sums --------------------------------------------------


def test_counts_at_the_cells_sizes():
    """ISSUE 33's arithmetic from the real cell's configuration file: 21.4 M
    a layer outside its experts, 4.72 M an expert, 96.9 M a layer held
    here, 2,176 B of cache a token a layer."""
    cell = harness.resolve_cell(CELL)
    arch, counts = cell.arch, cell.family.counts
    draft = cell.family.draft(arch, cell.config["serving"])
    assert counts.attention_params(arch) == 18_874_368           # 18.87 M
    assert counts.indexer_params(arch) == 2_260_992              # 2.26 M
    assert counts.expert_params(arch) == 4_718_592               # 4.72 M
    assert counts.layer_params(arch) == 18_874_368 + 2_260_992 \
        + 2048 * 128 + 16 * 4_718_592                            # 96.9 M
    assert round(counts.layer_params(arch) * 2 / 1e6) == 194     # MB a layer
    assert counts.cache_bytes_per_token_layer(arch) == 2176
    assert counts.cache_bytes_per_token(arch, draft) == 17408
    assert round(counts.weights_bytes(arch, draft) / 1e9, 2) == 1.86
    # every matrix the program holds, and nothing else but the norms
    shapes = {**cell.family.leaf_shapes(arch),
              **cell.family.leaf_shapes(draft, "draft.")}
    matrices = sum(math.prod(s) for s in shapes.values() if len(s) > 1)
    assert matrices * 2 == counts.weights_bytes(arch, draft)
    # a token meets 8 x 16/128 = 1 routed expert here on average
    assert counts.layer_params_per_token(arch) == 18_874_368 + 2_260_992 \
        + 2048 * 128 + 4_718_592
    # a round of 16 rows of 10,000 live tokens each, n_draft 1
    rows, live = 16, 16 * 10000.0
    cost = counts.decode_round_cost(arch, draft, 1, live, rows)
    table = arch["hidden"] * arch["vocab_padded"] * 2
    t_weights = 6 * counts.layer_params(arch) * 2 + table
    d_weights = 2 * counts.layer_params(arch) * 2 + table
    index = live * 64 * 2                  # a layer, a pass
    kv = 2048 * 2 * 4 * 128 * 2            # a query, a layer: 4.2 MB
    assert kv == 4_194_304
    assert cost["bytes"] == t_weights + 6 * (index + 2 * rows * kv) \
        + 2 * (d_weights + 2 * (index + rows * kv))
    # never the whole slab: rows of 1,000 live tokens attend 1,000 keys
    short = counts.decode_round_cost(arch, draft, 1, 16 * 1000.0, rows)
    assert short["bytes"] == t_weights + 6 * (
        16000 * 128 + 2 * rows * 1000 * 2048) \
        + 2 * (d_weights + 2 * (16000 * 128 + rows * 1000 * 2048))
    # the window's useful work: the indexer over every pair, attention over
    # at most 2,048 keys a token
    flops = counts.serve_flops(arch, 8192.0, 100.0, 8192.0 ** 2 / 2)
    per_token = 6 * counts.layer_params_per_token(arch) + 2048 * 18992
    assert flops == 2.0 * per_token * 8292 + 6 * (
        8192.0 ** 2 / 2 * 2 * 16 * 64 + 8292 * 2048 * 4.0 * 32 * 128)


def test_the_admission_kernels_cost_and_its_roofline_reader():
    """A call of the masked-attention kernel for a chunk of 512 of a
    4,096-token admission sees 1, 1, 2, 2, 3, 3, 4, 4 key blocks of 1,024:
    2,560 slots on average; the reader finds the calls by the kernel's
    name, which carries the chunk and the slab."""
    from benchmark.readers import select_kernel_roofline
    from benchmark.trace_reduce import OPS

    cell = harness.resolve_cell(CELL)
    cost = cell.family.counts.select_kernel_cost(cell.arch, 512, 4096)
    assert cost["flops"] == 4.0 * 512 * 2560 * 32 * 128
    assert cost["bytes"] == 2.0 * 512 * 32 * 128 * 2 \
        + 2.0 * 2560 * 4 * 128 * 2 + 2560 * 512
    peaks = harness.peaks_for("TPU v5 lite")
    least = cost["flops"] / peaks["bf16_flops_per_s"]       # compute-bound
    assert least > cost["bytes"] / peaks["hbm_bytes_per_s"]

    class Trace:
        planes = ["/device:TPU:0"]
        events = [
            ("/device:TPU:0", OPS, "%select_attention_s512_t4096.7 = "
             "bf16[1,32,512,128]{3,2,1,0} custom-call(%a, %b)", 0,
             4 * least * 1e9),
            ("/device:TPU:0", OPS, "%select_attention_s512_t4096.9 = "
             "bf16[1,32,512,128]{3,2,1,0} custom-call(%a, %b)", 10,
             4 * least * 1e9),
            ("/device:TPU:0", OPS, "%fusion.3 = f32[8]{0} fusion(%c)", 20,
             1e6),
        ]

    ctx = {"trace": Trace(), "peaks": peaks, "cell": cell}
    assert abs(select_kernel_roofline.read(ctx) - 25.0) < 1e-9
    Trace.events = Trace.events[-1:]
    assert select_kernel_roofline.read(ctx) is None          # no such call
    assert select_kernel_roofline.read(dict(ctx, peaks=None)) is None


def test_the_reference_imports_nothing_of_the_program():
    import ast

    path = os.path.join(ROOT, "benchmark", "reference", "keye_moe.py")
    tree = ast.parse(open(path).read())
    modules = [n.module for n in ast.walk(tree)
               if isinstance(n, ast.ImportFrom)] \
        + [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
           for a in n.names]
    assert modules and not any(m.startswith("rocket_tpu") for m in modules)
    assert json.dumps(sorted(modules)) == json.dumps(sorted(
        ["__future__", "functools", "typing", "jax", "jax.numpy",
         "benchmark.reference.decoder"]))
