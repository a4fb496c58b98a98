"""Weights from the seed: the same leaves for the same seed, whoever asks
(the program's whole tree or the reference's one layer), and the reference's
layer equations against a few facts worked by hand."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.archs import decoder as family
from benchmark.reference import decoder

TOY = os.path.join(os.path.dirname(__file__), "toy", "configs")


def arch(name):
    with open(os.path.join(TOY, name + ".json")) as fh:
        return family.normalise(json.load(fh))


def leaves(seed, a, prefix="", dtype="float32"):
    return weights.all_leaves(weights.base_key(seed),
                              family.leaf_shapes(a, prefix), prefix, dtype)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, 2 ** 32 + 1])
def test_same_seed_same_leaves_and_large_seeds_are_keys(seed):
    a = leaves(seed, arch("toy-gpt2"))
    b = leaves(seed, arch("toy-gpt2"))
    assert set(a) == set(family.leaf_shapes(arch("toy-gpt2")))
    for name in a:
        assert (np.asarray(a[name]) == np.asarray(b[name])).all()


def test_other_seed_other_leaves_and_layers_differ():
    a = leaves(1, arch("toy-gpt2"))
    b = leaves(2, arch("toy-gpt2"))
    assert not np.allclose(a["L0.q.w"], b["L0.q.w"])
    assert not np.allclose(a["L0.q.w"], a["L1.q.w"])
    assert 2 ** 31 != 0 and not np.allclose(
        leaves(2 ** 31, arch("toy-gpt2"))["embed"],
        leaves(0, arch("toy-gpt2"))["embed"])


def test_one_group_alone_is_the_same_as_in_the_whole():
    a = arch("toy-mistral")
    key = weights.base_key(11)
    whole = leaves(11, a, dtype="bfloat16")
    shapes = weights.groups(family.leaf_shapes(a))
    assert list(shapes) == ["top", "L0", "L1"]
    alone = weights.make_group(key, "L1", shapes["L1"], "bfloat16")
    assert set(alone) == {k for k in whole if k.startswith("L1.")}
    for name, leaf in alone.items():
        assert leaf.dtype == jnp.bfloat16
        assert (np.asarray(leaf) == np.asarray(whole[name])).all()


def test_scales_sit_near_one_and_the_rest_near_nought():
    a = leaves(3, arch("toy-gpt2"))
    assert abs(float(a["lnf.scale"].mean()) - 1.0) < 0.02
    assert abs(float(a["L0.up.w"].mean())) < 0.005
    assert 0.015 < float(a["L0.up.w"].std()) < 0.025
    assert float(jnp.abs(a["L0.q.b"]).max()) > 0      # biases are not nought


def test_draft_has_leaves_of_its_own():
    a = arch("toy-mistral")
    d = family.draft(a, {"draft_layers": 1})
    target = leaves(5, a)
    draft = leaves(5, d, prefix="draft.")
    assert set(draft) == {"draft." + k for k in target
                          if not k.startswith("L1.")}
    assert not np.allclose(draft["draft.embed"], target["embed"])


def test_leaf_shapes_of_both_dialects():
    g, m = arch("toy-gpt2"), arch("toy-mistral")
    sg, sm = family.leaf_shapes(g), family.leaf_shapes(m)
    assert sg["embed"] == (256, 64) and sg["pos"] == (32, 64)
    assert "head" not in sg and sg["L0.up.b"] == (256,)
    assert sm["head"] == (64, 256) and "pos" not in sm
    assert sm["L0.k.w"] == (64, 2 * 16) and sm["L0.gate.w"] == (64, 128)
    assert not any(k.endswith(".b") or k.endswith(".bias") for k in sm)


# -- the reference's equations ------------------------------------------------


def test_reference_is_causal_and_windowed():
    a = arch("toy-mistral")
    w = leaves(9, a)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 256, 64).astype(np.int32)
    get = lambda g: {k: v for k, v in w.items()  # noqa: E731
                     if weights.group_of(k) == g}
    base = decoder.served_logits(a, "f32", get, tokens, 1, 63, pad_to=64)
    later = tokens.copy()
    later[40:] = (later[40:] + 1) % 256
    moved = decoder.served_logits(a, "f32", get, later, 1, 63, pad_to=64)
    # positions before the change see the same past
    assert np.allclose(base[:39], moved[:39], atol=1e-5)
    assert not np.allclose(base[45:], moved[45:], atol=1e-3)
    # the window is 48: position 63 cannot see token 2, with it could
    early = tokens.copy()
    early[2] = (early[2] + 1) % 256
    seen = decoder.served_logits(a, "f32", get, early, 1, 63, pad_to=64)
    assert not np.allclose(base[10], seen[10], atol=1e-4)
    wide = dict(a, window=None)
    b2 = decoder.served_logits(wide, "f32", get, tokens, 1, 63, pad_to=64)
    s2 = decoder.served_logits(wide, "f32", get, early, 1, 63, pad_to=64)
    gap_windowed = float(jnp.abs(base[62] - seen[62]).max())
    gap_wide = float(jnp.abs(b2[62] - s2[62]).max())
    assert gap_wide > 0
    # two layers of window 48 reach back at most 94 positions: the far
    # token's influence is cut down, not passed on whole
    assert gap_windowed != gap_wide


def test_precisions_are_ordered_f32_bf16_fp8():
    a = jnp.asarray(np.random.default_rng(0).normal(size=(64, 64)), jnp.float32)
    b = jnp.asarray(np.random.default_rng(1).normal(size=(64, 64)), jnp.float32)
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    err = {p: float(np.abs(np.asarray(
        decoder.matmul(p, "ij,jk->ik", a, b), np.float64) - exact).max())
        for p in ("f32", "bf16", "fp8")}
    assert err["f32"] < 1e-4 < err["bf16"] < err["fp8"]
    with pytest.raises(ValueError):
        decoder.matmul("int3", "ij,jk->ik", a, b)


def test_lr_schedule_warms_up_then_decays():
    opt = {"lr_init": 3e-5, "lr_peak": 3e-4, "lr_end": 3e-5,
           "warmup_steps": 100, "decay_steps": 2000}
    assert decoder.lr_at(opt, 0) == pytest.approx(3e-5)
    assert decoder.lr_at(opt, 50) == pytest.approx(3e-5 + 2.7e-4 / 2)
    assert decoder.lr_at(opt, 100) == pytest.approx(3e-4)
    assert decoder.lr_at(opt, 2000) == pytest.approx(3e-5)
    import optax

    sched = optax.warmup_cosine_decay_schedule(3e-5, 3e-4, 100, 2000, 3e-5)
    for step in (0, 1, 2, 99, 100, 500, 1999):
        assert decoder.lr_at(opt, step) == pytest.approx(
            float(sched(step)), rel=1e-5)
