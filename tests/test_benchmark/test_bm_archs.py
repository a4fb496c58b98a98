"""The seam for the architecture, rehearsed: the toy benchmark brings a
second architecture (``toy/archs/latent_mix.py``, named by
``toy/configs/toy-mix.json``'s ``reference``) as files of its own, and a
``train`` and a ``closed`` cell on it run through the same kinds, weights,
limits and readers as the dense decoder's, on the CPU: counts and verdicts,
never times."""

import json
import math
import os

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.normpath(os.path.join(HERE, "..", ".."))
TOY = os.path.join(HERE, "toy")


@pytest.fixture(autouse=True)
def work_dir_of_its_own(tmp_path, monkeypatch):
    """``test_bm_harness.py`` runs cells too, under xdist in another worker:
    this file's runs keep their traces and the trainer's files apart from
    the checkout's one ``.benchwork/`` that a real run, alone, uses."""
    def work_dir(name):
        path = tmp_path / name
        path.mkdir(exist_ok=True)
        return str(path)

    monkeypatch.setattr(harness, "work_dir", work_dir)


def toy_cell(name):
    manifest = harness.load_json(os.path.join(TOY, "BENCHMARK.json"))
    return harness.resolve_cell(name, manifest, bench_dir=TOY)


@pytest.mark.parametrize("name,metric", [
    ("mix-train", "train_tokens_per_s"),
    ("mix-closed", "serve_tokens_per_s"),
])
def test_cells_of_the_second_architecture_are_correct(name, metric):
    result = harness.run_cell(toy_cell(name), 2 ** 31 + 29, 0.5, False)
    json.dumps(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {metric, "setup_s"}
    assert result["compared"]
    for pair in result["compared"].values():
        assert pair["value"] <= pair["limit"]


def test_the_second_architecture_is_files_of_the_toy_alone():
    from benchmark.archs import decoder

    mix, dense = toy_cell("mix-closed"), toy_cell("toy-closed")
    # loaded from the toy's directory, with its reference and its counts
    assert mix.family.__file__ == os.path.join(TOY, "archs", "latent_mix.py")
    assert mix.family.reference.__file__ == os.path.join(
        TOY, "reference", "latent_mix.py")
    assert mix.family.counts.__file__ == os.path.join(
        TOY, "counts", "latent_mix.py")
    assert toy_cell("mix-train").family is mix.family   # one module a file
    # the toy's dense configurations name the benchmark's own
    assert dense.family is decoder
    assert dense.config["reference"] == "decoder"
    # and the benchmark's own directories hold nothing of it (what else
    # they hold is later PRs' business: an architecture is added there)
    for package in ("archs", "reference", "counts"):
        assert not os.path.exists(os.path.join(
            ROOT, "benchmark", package, "latent_mix.py"))


def test_it_differs_where_later_configurations_will():
    import jax
    import jax.numpy as jnp

    from benchmark.archs import decoder

    cell = toy_cell("mix-closed")
    family, arch = cell.family, cell.arch
    shapes = family.leaf_shapes(arch)
    # the first layer is of another kind than the rest
    assert "L0.router.w" not in shapes and "L1.router.w" in shapes
    assert len(shapes["L0.up.w"]) == 2
    # a three-dimensional leaf: [experts, in, out]
    assert shapes["L1.up.w"] == (arch["experts"], arch["hidden"],
                                 arch["expert_ffn"])
    assert shapes["L2.down.w"] == (arch["experts"], arch["expert_ffn"],
                                   arch["hidden"])
    # the draft is the dense layer alone, with leaves of its own
    draft = family.draft(arch, cell.config["serving"])
    assert not any(len(s) == 3
                   for s in family.leaf_shapes(draft, "draft.").values())
    # the cache is one latent a token and a layer, not K and V of heads
    model = family.program(arch, max_seq=66, attention="auto")
    params = {k: jnp.zeros(s, jnp.bfloat16) for k, s in shapes.items()}
    cache = jax.eval_shape(
        lambda p: model.apply({"params": p},
                              {"tokens": jnp.zeros((4, 8), jnp.int32)},
                              decode=True, mutable=["cache"])[1]["cache"],
        params)
    assert {k: v.shape for k, v in cache.items()} == {
        f"L{i}.latent": (4, 66, 1, arch["latent"])
        for i in range(arch["layers"])}
    # counts of its own: what its cells' readers call, and no more
    assert family.counts is not decoder.counts
    assert family.counts.cache_bytes_per_token(arch) == (
        2 * arch["latent"] * arch["layers"])
    assert family.counts.total_params(arch) == sum(
        math.prod(s) for s in shapes.values() if len(s) > 1)
    assert not hasattr(family.counts, "flash_kernel_cost")


def test_a_reader_counts_through_the_cells_own_architecture():
    cell = toy_cell("mix-closed")
    result = harness.run_cell(cell, 7, 0.5, True)
    assert result["correct"] is True
    got = result["metrics"]["toy_round_bytes"]["value"]
    serving = cell.config["serving"]
    draft = cell.family.draft(cell.arch, serving)
    weights_only = cell.family.counts.decode_round_cost(
        cell.arch, draft, serving["n_draft"], 0.0, 4)["bytes"]
    full = cell.family.counts.decode_round_cost(
        cell.arch, draft, serving["n_draft"],
        4.0 * serving["total_len"], 4)["bytes"]
    assert weights_only < got < full
    # the dense cells read nothing of it: the metric lists its cell
    assert "toy_round_bytes" not in {
        m["name"] for m in toy_cell("toy-closed").per_layer()}


@pytest.mark.parametrize("reader,args", [
    ("train_mfu", ()),
    ("flash_roofline", ()),
    ("kernel_roofline", ("fwd", "flash_fwd")),
    ("serve_mfu", ()),
])
def test_a_reader_reads_nothing_where_the_family_counts_no_such_thing(
        reader, args):
    """The toy's ``counts`` holds ``decode_round_cost`` alone of what the
    benchmark's five readers ask for: the other four return None on its
    cells (and a number on a dense cell, from the same trace), so a family
    ships no stub for a reader that none of its cells lists."""
    import importlib

    from benchmark import trace_reduce

    dev, ns = "/device:TPU:0", 1e9
    text = ('%flash_fwd.1 = bf16[2,4,16,16]{3,2,1,0} custom-call(%a), '
            'custom_call_target="tpu_custom_call"')
    trace = trace_reduce.Reduced(
        [(dev, trace_reduce.MODULES, "jit_step(1)", 0.0, ns),
         (dev, trace_reduce.OPS, text, 0.0, ns / 10)], chips=1)
    run = {"window_s": 2.0, "tokens": 10, "prompt_tokens": 10,
           "context_products": 100.0}
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    read = importlib.import_module(f"benchmark.readers.{reader}").read
    for name, reads in (("mix-train", False), ("toy-train", True)):
        cell = toy_cell(name)
        got = read({"cell": cell, "trace": trace, "run": run,
                    "peaks": peaks}, *args)
        assert (got is not None and got > 0) if reads else got is None
