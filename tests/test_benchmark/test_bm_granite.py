"""The ``granite_hybrid`` architecture through the harness: a toy-width
configuration of it (``granite_toy/``: the pattern ``[mamba, mamba,
attention, mamba]``, a two-layer ``[mamba, attention]`` draft, float32
weights, embeddings and sublayers at one so that a random draft is refused
and the round has to roll its state back) served through ``closed`` on the
CPU is ``correct`` and reads its counters, three planted faults are not,
the manifest's new entries resolve, and the counts at the real cell's sizes
are the sums worked by hand from the published configuration."""

import importlib
import json
import math
import os

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.normpath(os.path.join(HERE, "..", ".."))
TOY = os.path.join(HERE, "granite_toy")
CELL = "granite4h-serve-concurrent"
SEED = 2 ** 31 + 36


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
    """At the benchmark's normal(0, 0.02) and the toy's widths a state's
    share of a layer's output is small whatever it holds; at 0.3 it is of
    order one, and a stale state serves other tokens."""
    from benchmark import weights

    monkeypatch.setattr(weights, "INIT_STD", 0.3)
    weights.release()
    yield
    weights.release()


def toy_cell():
    manifest = harness.load_json(os.path.join(TOY, "BENCHMARK.json"))
    return harness.resolve_cell("granite-closed", manifest, bench_dir=TOY)


def test_toy_cell_is_correct_and_reads_its_counters(
        weights_large_enough_to_tell):
    from benchmark.archs import granite_hybrid

    cell = toy_cell()
    assert cell.family is granite_hybrid        # the benchmark's own module
    result = harness.run_cell(cell, SEED, 0.5, True)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert 1.0 <= got["granite_tokens_per_round_row"] <= 2.0
    assert got["granite_fetches_per_round"] > 0
    # the CPU's trace has no device plane: no kernel time, no device time
    assert "granite_ssm_decode_roofline" not in got
    assert "granite_admit_busy_share" not in got


def _commit_all(monkeypatch, family):
    """No rollback: every pass takes its whole chunk into the state."""
    generate = importlib.import_module("rocket_tpu.models.generate")
    monkeypatch.setattr(generate, "_commit_kw", lambda model, n: {})


def _admission_keeps_the_old_state(monkeypatch, family):
    """An admission leaves the row's recurrent state as its previous
    occupant left it."""
    import jax

    generate = importlib.import_module("rocket_tpu.models.generate")
    real = generate._scatter_row

    def scatter(batch_cache, one_cache, row):
        new = real(batch_cache, one_cache, row)
        return jax.tree_util.tree_map_with_path(
            lambda p, old, fresh: old if generate._leaf_name(p)
            == "ssm_state" else fresh, batch_cache, new)

    monkeypatch.setattr(generate, "_scatter_row", scatter)


def _default_softmax_scale(monkeypatch, family):
    real = family.program
    monkeypatch.setattr(family, "program", lambda arch, **kw: real(
        dict(arch, attention_multiplier=arch["head_dim"] ** -0.5), **kw))


@pytest.mark.parametrize("fault", [
    _commit_all, _admission_keeps_the_old_state, _default_softmax_scale])
def test_a_planted_fault_is_not_correct(monkeypatch, fault,
                                        weights_large_enough_to_tell):
    """A state that takes in refused drafts, an admission that keeps its
    predecessor's state, the softmax scale of 1/sqrt(head_dim): each serves
    tokens the reference would not have, and ``served_gap`` says so."""
    cell = toy_cell()
    fault(monkeypatch, cell.family)
    result = harness.run_cell(cell, SEED, 0.5, False)
    assert result["correct"] is False
    pair = result["compared"]["served_gap"]
    assert pair["value"] > pair["limit"]


def test_a_program_without_state_space_layers_is_refused_cleanly(
        monkeypatch):
    """On a checkout whose program knows no ``MambaConfig`` (this PR's
    parent under this PR's benchmark files) the architecture refuses as the
    benchmark refuses: a ``BenchmarkError``, exit 2, no hang."""
    from rocket_tpu.models import transformer

    cell = harness.resolve_cell(CELL)
    monkeypatch.delattr(transformer, "MambaConfig")
    with pytest.raises(harness.BenchmarkError, match="granite_hybrid"):
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
    assert names == {
        "granite_round_ms_p50", "granite_round_gap_ms_p50",
        "granite_admit_device_ms", "granite_admit_busy_share",
        "granite_tokens_per_round_row", "serve_step_mfu.granite",
        "decode_round_roofline.granite", "device_idle_share.granite",
        "idle_unattributed_share.granite", "granite_host_gap_ms_p50",
        "granite_fetches_per_round", "granite_setup_import_s",
        "granite_setup_first_dispatch_s", "granite_setup_warm_start_s",
        "granite_setup_cache_misses", "granite_ssm_decode_roofline",
        "compiles_in_window"}
    for name in names:
        spec = harness.load_json(os.path.join(
            harness.HERE, "metrics", name + ".json"))
        harness._load_module(harness.HERE, "readers", spec["reader"])
    limits = harness.load_json(os.path.join(
        harness.HERE, "limits", CELL + ".json"))
    assert set(limits) == {"served_gap"} and 0 < limits["served_gap"] < 1


CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": ["attention" if i in (5, 15, 25, 35) else "mamba"
                    for i in range(40)],
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352}


MOVED = {"embedding_multiplier": 1, "logits_scaling": 96}


def test_the_configuration_keeps_every_published_width():
    """Against the published ``config.json`` (its row of the model
    catalog): every key under the same value but the two scalars that move
    the tied table's scale (``MOVED``), and only they are reduced."""
    config = harness.resolve_cell(CELL).config
    assert config["reduced"] == sorted(MOVED)
    for key, value in CATALOG.items():
        assert config[key] == MOVED.get(key, value), key
    assert config["serving"] == {
        "rows": 48, "total_len": 3072, "n_draft": 1, "draft_layers": 2,
        "draft_layer_types": ["mamba", "attention"],
        "weights_dtype": "bfloat16"}
    assert {"in_proj_split", "conv_activation", "dt_limit", "gated_norm",
            "mlp", "state_dtype", "table_draw", "draft"} \
        <= set(config["assumed"])


def test_the_moved_scalars_are_the_published_model_with_a_smaller_table():
    """Embeddings x 1 and logits / 96 over a table ``T`` are the published
    x 12 and / 8 over ``T / 12``: the same logits from the reference, at the
    toy's widths with the cell's pattern of scalars."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark import weights

    cell = toy_cell()
    family = cell.family
    arch = dict(cell.arch, residual_multiplier=0.22, **{
        k: float(v) for k, v in MOVED.items()})
    published = dict(arch, embedding_multiplier=12.0, logits_scaling=8.0)
    shapes = weights.groups(family.leaf_shapes(arch))
    key = weights.base_key(SEED)

    def getter(table_scale):
        def get(group):
            leaves = weights.make_group(key, group, shapes[group])
            if "embed" in leaves:
                leaves["embed"] = leaves["embed"] * table_scale
            return leaves
        return get

    row = np.arange(40) * 7 % arch["vocab"]
    moved = family.reference.full_logits(arch, "f32", getter(1.0), row)
    theirs = family.reference.full_logits(published, "f32",
                                          getter(1.0 / 12.0), row)
    np.testing.assert_allclose(moved, theirs, rtol=1e-5,
                               atol=1e-5 * float(jnp.std(moved)))


def test_the_traffic_file_holds_the_named_parameters():
    mix = harness.resolve_cell(CELL).traffic
    mix = {k: v for k, v in mix.items() if k != "why"}
    assert mix == {
        "kind": "closed", "cycle": 64,
        "prompt_ladder": [64, 128, 256, 512, 1024, 2048],
        "prompt_lognormal": {"median": 512, "sigma": 1.0},
        "output_lognormal": {"median": 256, "sigma": 0.7, "min": 32,
                             "max": 1024},
        "max_total": 3072, "initial_in_service": 48, "backlog": 2,
        "expected_per_s": 12, "trace_seconds": 6}


# -- counts against hand sums --------------------------------------------------


def test_counts_at_the_cells_sizes():
    """Hand sums from the real cell's configuration file:
    76,182,976 parameters a ``mamba`` layer, 60,821,504 an ``attention``
    one, a table of 205,520,896, 3,191,396,096 in all (6.38 GB in bf16); a
    row's state 2 MiB a layer."""
    cell = harness.resolve_cell(CELL)
    arch, counts = cell.arch, cell.family.counts
    draft = cell.family.draft(arch, cell.config["serving"])
    assert counts.mamba_params(arch) == 17_432_576 + 21_760 + 8_388_608 \
        + 3 * 64 + 4096
    assert counts.layer_params(arch, "mamba") == 76_182_976
    assert counts.layer_params(arch, "attention") == 60_821_504
    assert counts.held_params(arch) == 3_191_396_096
    assert round(counts.held_params(arch) * 2 / 1e9, 2) == 6.38
    assert counts.held_params(draft) == 76_182_976 + 60_821_504 \
        + 205_520_896 + 2048
    shapes = {**cell.family.leaf_shapes(arch),
              **cell.family.leaf_shapes(draft, "draft.")}
    assert sum(math.prod(s) for s in shapes.values()) * 2 \
        == counts.weights_bytes(arch, draft)
    assert counts.state_bytes(arch) == 2 * 2 ** 20
    assert counts.kv_bytes_per_token(arch) == 2048
    # a round of 48 rows of 900 live tokens each, n_draft 1
    rows, live = 48, 48 * 900.0
    cost = counts.decode_round_cost(arch, draft, 1, live, rows)
    window = (3 + 1) * 4352 * 2
    t = counts.held_params(arch) * 2 + 36 * rows * 2 * (2 ** 21 + window) \
        + 4 * live * 2048
    d1 = counts.held_params(draft) * 2 + rows * 2 * (2 ** 21 + window) \
        + live * 2048
    d2 = counts.held_params(draft) * 2 + rows * (2 ** 21 + window) \
        + live * 2048
    assert cost["bytes"] == t + d1 + d2
    assert 15.0e9 < cost["bytes"] < 16.5e9          # about 15.9 GB
    per_token = 2.0 * (counts.held_params(arch) - 2048) \
        + 36 * 4.0 * 64 * 64 * 128
    flops = counts.serve_flops(arch, 700.0, 100.0, 700.0 ** 2 / 2)
    assert flops == per_token * 800 + 4 * 700.0 ** 2 / 2 * 4.0 * 32 * 64


def test_the_kernels_cost_and_its_roofline_reader():
    """A call of the round's state-update kernel over 48 rows reads and
    writes each row's 2 MiB state; the reader finds the calls by the
    kernel's name, which carries the pass's tokens and the rows."""
    from benchmark import counts as roofline
    from benchmark.readers import ssm_kernel_roofline
    from benchmark.trace_reduce import OPS

    cell = harness.resolve_cell(CELL)
    cost = cell.family.counts.ssm_kernel_cost(cell.arch, 2, 48)
    assert cost["bytes"] == 48 * (2 * 2 ** 21 + 2 * (4096 + 256) * 2)
    assert cost["flops"] == 48 * 2 * 4.0 * 64 * 64 * 128
    one = cell.family.counts.ssm_kernel_cost(cell.arch, 1, 48)
    assert one["bytes"] == 48 * (2 * 2 ** 21 + (4096 + 256) * 2)
    peaks = harness.peaks_for("TPU v5 lite")
    least = roofline.roofline_seconds(cost, peaks)
    assert least == cost["bytes"] / peaks["hbm_bytes_per_s"]   # by bytes
    least_one = roofline.roofline_seconds(one, peaks)

    class Trace:
        planes = ["/device:TPU:0"]
        events = [
            ("/device:TPU:0", OPS, "%ssm_decode_s2_r48.7 = "
             "(f32[48,16,4096]{2,1,0}, f32[48,4096,128]{2,1,0}) "
             "custom-call(%a, %b)", 0, 2 * least * 1e9),
            ("/device:TPU:0", OPS, "%ssm_decode_s1_r48.2 = "
             "(f32[48,16,4096]{2,1,0}, f32[48,4096,128]{2,1,0}) "
             "custom-call(%a, %b)", 10, 2 * least_one * 1e9),
            ("/device:TPU:0", OPS, "%fusion.3 = f32[8]{0} fusion(%c)", 20,
             1e6),
        ]

    ctx = {"trace": Trace(), "peaks": peaks, "cell": cell}
    assert abs(ssm_kernel_roofline.read(ctx) - 50.0) < 1e-9
    Trace.events = Trace.events[-1:]
    assert ssm_kernel_roofline.read(ctx) is None          # no such call
    assert ssm_kernel_roofline.read(dict(ctx, peaks=None)) is None


def test_the_reference_imports_nothing_of_the_program():
    import ast

    path = os.path.join(ROOT, "benchmark", "reference", "granite_hybrid.py")
    tree = ast.parse(open(path).read())
    modules = [n.module for n in ast.walk(tree)
               if isinstance(n, ast.ImportFrom)] \
        + [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
           for a in n.names]
    assert modules and not any(m.startswith("rocket_tpu") for m in modules)
    assert json.dumps(sorted(modules)) == json.dumps(sorted(
        ["__future__", "functools", "typing", "jax", "jax.numpy",
         "benchmark.reference.decoder"]))
