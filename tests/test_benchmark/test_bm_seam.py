"""Readings of the yardstick pinned before the architecture moved behind
``archs/<name>.py``: the weights drawn from a seed, the leaves, the
normalised sizes and the counts at the real cells' sizes.

``data/seam_pins.json`` was written on the parent of the PR that made the
seam (PR 26), through the functions as they were then (``harness.arch_of``,
``weights.leaf_shapes``, ``counts.*``); the same values have to come out
through ``cell.family`` now, to the bit for the weights and to the last
digit for the counts.  A change to any of them changes every cell's weights
or the numerator of a roofline share, and is a change to the benchmark.
"""

import hashlib
import json
import os

import numpy as np
import pytest

from benchmark import harness, weights

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy")

with open(os.path.join(HERE, "data", "seam_pins.json")) as _fh:
    PINS = json.load(_fh)


def real_cell(config):
    manifest = harness.load_manifest()
    name = next(w["name"] for w in manifest["workloads"]
                if w["config"] == config)
    return harness.resolve_cell(name, manifest)


def toy_cell(config):
    manifest = harness.load_json(os.path.join(TOY, "BENCHMARK.json"))
    name = next(w["name"] for w in manifest["workloads"]
                if w["config"] == config)
    return harness.resolve_cell(name, manifest, bench_dir=TOY)


def tree_of(cell, name):
    """``(arch, prefix)`` of the cell's own tree or of its ``.draft``."""
    if name.endswith(".draft"):
        return cell.family.draft(cell.arch, cell.config["serving"]), "draft."
    return cell.arch, ""


def sha(leaf) -> str:
    a = np.asarray(leaf)
    return hashlib.sha256(str(a.dtype).encode() + str(a.shape).encode()
                          + a.tobytes()).hexdigest()


@pytest.mark.parametrize("pin", sorted(PINS["leaf_sha256"]))
def test_every_leaf_of_the_seed_is_the_same_to_the_bit(pin):
    name, dtype = pin.split("/")
    cell = toy_cell(name.split(".")[0])
    arch, prefix = tree_of(cell, name)
    leaves = weights.all_leaves(
        weights.base_key(7), cell.family.leaf_shapes(arch, prefix), prefix,
        dtype)
    want = PINS["leaf_sha256"][pin]
    assert list(leaves) == list(want)           # the order of the draws too
    assert {k: sha(v) for k, v in leaves.items()} == want


@pytest.mark.parametrize("pin", sorted(PINS["leaf_shapes"]))
def test_leaf_shapes_and_sizes_of_the_real_configurations(pin):
    cell = real_cell(pin.split(".draft")[0])
    arch, prefix = tree_of(cell, pin)
    assert arch == PINS["arch"][pin]
    shapes = cell.family.leaf_shapes(arch, prefix)
    want = PINS["leaf_shapes"][pin]
    assert list(shapes) == list(want)
    assert {k: list(v) for k, v in shapes.items()} == want


def test_counts_at_the_real_cells_sizes_to_the_last_digit():
    gpt2, mistral = real_cell("gpt2-medium"), real_cell("mistral-7b-l8")
    g, m = gpt2.arch, mistral.arch
    d, _ = tree_of(mistral, "mistral-7b-l8.draft")
    gc, mc = gpt2.family.counts, mistral.family.counts
    got = {
        "train_flops_per_token(gpt2-medium,1024)":
            gc.train_flops_per_token(g, 1024),
        "flash_kernel_cost(gpt2-medium,8,1024)":
            gc.flash_kernel_cost(g, 8, 1024),
        "decode_round_cost(mistral-7b-l8,draft,4,21600,24)":
            mc.decode_round_cost(m, d, 4, 24 * 900, 24),
        "serve_flops(mistral-7b-l8,1e4,1e4,1e7)":
            mc.serve_flops(m, 1e4, 1e4, 1e7),
        "total_params(gpt2-medium)": gc.total_params(g),
        "total_params(mistral-7b-l8)": mc.total_params(m),
        "total_params(mistral-7b-l8.draft)": mc.total_params(d),
    }
    assert got == PINS["counts"]
    # exact, not approximately: repr keeps every digit of a float
    assert {k: repr(v) for k, v in got.items()} == {
        k: repr(v) for k, v in PINS["counts"].items()}
