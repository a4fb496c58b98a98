"""Latent attention, the gated expert layer and the hidden-state draft
against the plain reference (``benchmark/reference/pangu_moe.py``), at toy
widths in float32 with seeded weights, on the CPU.

Tolerances: program and reference compute the same float32 sums in another
order, so they differ by a few units in the last place of a logit of order
one (measured here: under 3e-6); ``TOL`` is 2e-5.  The same comparison with
one matrix product rounded to bfloat16 (eight bits of mantissa) differs by
1e-3 to 1e-2, which the tests of the planted faults show.
"""

import dataclasses
import importlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.archs import pangu_moe as family
from benchmark.kinds.train import named_leaves
from benchmark.reference import pangu_moe as reference
from rocket_tpu.models.generate import ContinuousBatcher, beam_search_cached
from rocket_tpu.models.moe import ExpertsConfig, RoutedExperts
from rocket_tpu.models.transformer import (MLAConfig, MTPDraft,
                                           TransformerConfig, TransformerLM)

# the module, not the function of that name the package re-exports
generate_mod = importlib.import_module("rocket_tpu.models.generate")

TOL = 2e-5
VOCAB, MAX_SEQ = 97, 48

ARCH = dict(
    kind="target", hidden=32, layers=3, first_dense=1, heads=4, q_rank=12,
    kv_rank=8, nope=8, rope=4, v_dim=6, ffn=40, expert_ffn=16, router=16,
    held=4, held_start=8, top_k=4, shared=1, norm_topk=True, route_scale=2.5,
    mtp_layers=1, eps=1e-5, rope_theta=25600000.0, vocab=VOCAB,
    vocab_padded=VOCAB, max_pos=MAX_SEQ)


@pytest.fixture(scope="module", autouse=True)
def both_expert_paths():
    """``RoutedExperts`` runs every held expert over every token up to
    ``DENSE_BELOW`` tokens and groups the routed slots above: at 6, this
    file's decode rounds (two rows of two tokens) take the first path and
    its prompts (7 tokens and more) the second.  Set once for the file: the
    jitted rounds are traced with whatever it was then."""
    from rocket_tpu.models import moe

    old, moe.DENSE_BELOW = moe.DENSE_BELOW, 6
    yield
    moe.DENSE_BELOW = old


def seeded(tree, seed):
    """Every leaf normal(0, 0.3); a norm's scale 1 + that: large enough
    that no layer is a rounding error beside the residual stream."""
    leaves, treedef = jax.tree_util.tree_flatten(nn.meta.unbox(tree))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return treedef.unflatten([
        0.3 * jax.random.normal(k, leaf.shape, jnp.float32)
        + (1.0 if leaf.ndim == 1 else 0.0) for k, leaf in zip(keys, leaves)])


@pytest.fixture(scope="module")
def target():
    model = family.program(ARCH, max_seq=MAX_SEQ)
    tokens = jnp.zeros((1, 4), jnp.int32)
    params = seeded(model.init(jax.random.PRNGKey(0),
                               {"tokens": tokens})["params"], 1)
    return model, params


@pytest.fixture(scope="module")
def module():
    draft_arch = family.draft(ARCH, {})
    model = family.program(draft_arch, max_seq=MAX_SEQ)
    assert isinstance(model, MTPDraft)
    tokens = jnp.zeros((1, 4), jnp.int32)
    # it initialises from the tokens alone, and holds no embedding or head
    params = seeded(model.init(jax.random.PRNGKey(0),
                               {"tokens": tokens})["params"], 2)
    assert not {"embed", "head"} & set(params)
    return draft_arch, model, params


def getter(params, prefix=""):
    """``get(group)`` of the reference over a program tree's leaves."""
    from benchmark import weights

    named = {prefix + k: v for k, v in named_leaves(params, family).items()}

    def get(group):
        return {k: v for k, v in named.items()
                if weights.group_of(k, prefix) == group}

    return get


def reference_logits(params, row):
    row = jnp.asarray(row, jnp.int32)
    get = getter(params)
    x = reference.hidden_states(ARCH, "f32", get, row,
                                jnp.arange(row.shape[0], dtype=jnp.int32))
    top = get("top")
    return reference.head(ARCH, "f32", x, top["lnf.scale"], top["head"])


def rows_of(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, size=n).astype(np.int32) for n in lengths]


# -- (a) latent attention: expanded prefill, absorbed decode -----------------


def test_full_forward_equals_the_reference(target):
    model, params = target
    row = rows_of(3, [21])[0]
    got = model.apply({"params": params}, {"tokens": row[None]})["logits"][0]
    np.testing.assert_allclose(got, reference_logits(params, row), atol=TOL)


def test_prefill_expanded_then_decode_absorbed_through_the_cache(target):
    """Two rows at unequal frontiers: each prompt prefilled on the expanded
    path into a cache of its own, the rows then decoded together, two
    tokens a step at each row's own positions, on the absorbed path.  Every
    logit equals the reference's one causal pass over the whole row."""
    model, params = target
    model = type(model)(dataclasses.replace(model.config,
                                            decode_per_row=True))
    prompts, steps = (5, 11), 4
    rows = rows_of(4, [p + 2 * steps for p in prompts])
    caches, got = [], [[], []]
    for r, p in enumerate(prompts):
        prompt = jnp.asarray(rows[r][None, :p])
        out, mut = model.apply(
            {"params": params,
             "cache": generate_mod.zero_cache(model, params, prompt)},
            {"tokens": prompt,
             "positions": jnp.arange(p, dtype=jnp.int32)[None]},
            decode=True, prefill=True, mutable=["cache"])
        caches.append(mut["cache"])
        got[r].append(out["logits"][0])
    cache = jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b]) if a.ndim == 3
        else jnp.maximum(a, b), *caches)
    leaf = cache["block_0"]["attn"]["cached_latent"]
    assert leaf.shape == (2, MAX_SEQ, ARCH["kv_rank"] + ARCH["rope"])
    for step in range(steps):
        starts = np.asarray(prompts) + 2 * step
        out, mut = model.apply(
            {"params": params, "cache": cache},
            {"tokens": jnp.stack([rows[r][s:s + 2]
                                  for r, s in enumerate(starts)]),
             "positions": jnp.asarray(starts[:, None] + np.arange(2),
                                      jnp.int32)},
            decode=True, mutable=["cache"])
        cache = mut["cache"]
        for r in range(2):
            got[r].append(out["logits"][r])
    for r in range(2):
        np.testing.assert_allclose(
            jnp.concatenate(got[r]), reference_logits(params, rows[r]),
            atol=TOL)


def test_a_bfloat16_product_in_the_absorbed_path_is_caught(target,
                                                          monkeypatch):
    """The tolerance is tight enough: the same decode with the cached
    latents rounded to bfloat16 before they are scored differs by more."""
    from rocket_tpu.models import transformer

    model, params = target
    row = rows_of(5, [12])[0]
    real = transformer.dot_attention

    def rounded(q, k, v=None, **kw):
        if v is None:
            k = k.astype(jnp.bfloat16).astype(k.dtype)
        return real(q, k, v, **kw)

    monkeypatch.setattr(transformer, "dot_attention", rounded)
    prompt = jnp.asarray(row[None, :6])
    _, mut = model.apply(
        {"params": params,
         "cache": generate_mod.zero_cache(model, params, prompt)},
        {"tokens": prompt, "positions": jnp.arange(6, dtype=jnp.int32)[None]},
        decode=True, prefill=True, mutable=["cache"])
    out, _ = model.apply(
        {"params": params, "cache": mut["cache"]},
        {"tokens": jnp.asarray(row[None, 6:]),
         "positions": jnp.arange(6, 12, dtype=jnp.int32)[None]},
        decode=True, mutable=["cache"])
    gap = jnp.max(jnp.abs(out["logits"][0] - reference_logits(params, row)[6:]))
    assert gap > 10 * TOL


# -- (b) the expert layer ----------------------------------------------------


def expert_layer(held_start, held, x, weights):
    cfg = ExpertsConfig(n_routed=16, top_k=4, expert_dim=16, n_shared=0,
                        scale=2.5, held_start=held_start, n_held=held)
    sl = slice(held_start, held_start + held)
    params = {"router": weights["router"], "w_gate": weights["eg"][sl],
              "w_up": weights["eu"][sl], "w_down": weights["ed"][sl]}
    y, sown = RoutedExperts(cfg).apply({"params": params}, x,
                                       mutable=["routing"])
    return y, sown["routing"]["top_idx"][0]


def expert_reference(x, weights, held_start=0, held=16, shared=0,
                     prec="f32", router_prec=None):
    arch = dict(ARCH, held_start=held_start, held=held, shared=shared)
    sl = slice(held_start, held_start + held)
    w = {"router.w": weights["router"], "sh_gate.w": weights["sg"],
         "sh_up.w": weights["su"], "sh_down.w": weights["sd"]}
    ew = {k: weights[k][sl] for k in ("eg", "eu", "ed")}
    if router_prec is not None:
        w["router.w"] = w["router.w"].astype(jnp.bfloat16).astype(jnp.float32)
        x_r = x.astype(jnp.bfloat16).astype(jnp.float32)
        real = reference.route
        try:
            reference.route = lambda a, p, _x, r: real(a, p, x_r, r)
            return reference.experts(arch, prec, x, w, ew)
        finally:
            reference.route = real
    return reference.experts(arch, prec, x, w, ew)


@pytest.fixture(params=["grouped", "every_expert"])
def expert_path(request, monkeypatch):
    """The 21 tokens of the layer tests through each of the two paths."""
    from rocket_tpu.models import moe

    monkeypatch.setattr(moe, "DENSE_BELOW",
                        0 if request.param == "grouped" else 128)


@pytest.fixture(scope="module")
def layer_weights():
    keys = jax.random.split(jax.random.PRNGKey(7), 8)
    n = lambda k, *s: 0.3 * jax.random.normal(k, s, jnp.float32)  # noqa: E731
    return {"router": n(keys[0], 32, 16), "eg": n(keys[1], 16, 32, 16),
            "eu": n(keys[2], 16, 32, 16), "ed": n(keys[3], 16, 16, 32),
            "sg": n(keys[4], 32, 16), "su": n(keys[5], 32, 16),
            "sd": n(keys[6], 16, 32),
            "x": jax.random.normal(keys[7], (3, 7, 32), jnp.float32)}


def test_the_shares_add_up_to_the_uncut_layer(layer_weights, expert_path):
    """Four chips of four experts each: their routed parts, plus the shared
    expert counted once, are the reference's uncut layer."""
    x = layer_weights["x"]
    flat = x.reshape(-1, 32)
    parts = [expert_layer(r * 4, 4, x, layer_weights)[0] for r in range(4)]
    whole = expert_reference(flat, layer_weights, shared=1)
    shared = reference._swiglu("f32", flat, layer_weights["sg"],
                               layer_weights["su"], layer_weights["sd"])
    np.testing.assert_allclose(sum(parts).reshape(-1, 32) + shared, whole,
                               atol=TOL)
    # and each share alone is the reference given that share
    for r in (0, 3):
        np.testing.assert_allclose(
            parts[r].reshape(-1, 32),
            expert_reference(flat, layer_weights, held_start=r * 4, held=4),
            atol=TOL)


def test_every_token_on_one_held_expert_loses_none(layer_weights,
                                                   expert_path):
    """No capacity: a router that sends every token to expert 9 first (and
    to 8, 10 and 11 after it) makes every slot a held one, and all of them
    are computed."""
    x = jnp.abs(layer_weights["x"]) + 0.1
    router = jnp.full((32, 16), -1.0).at[:, 8:12].set(0.2).at[:, 9].set(1.0)
    weights = dict(layer_weights, router=router)
    y, top = expert_layer(8, 4, x, weights)
    assert bool(jnp.all(top[..., 0] == 9))
    assert bool(jnp.all((top >= 8) & (top < 12)))        # 21 x 4 held slots
    np.testing.assert_allclose(
        y.reshape(-1, 32),
        expert_reference(x.reshape(-1, 32), weights, held_start=8, held=4),
        atol=TOL)


def test_a_router_rounded_to_bfloat16_is_caught(layer_weights, expert_path):
    """The router's product is float32 end to end: rounded to bfloat16 it
    ranks near ties otherwise, other experts are chosen, and the layer's
    output moves by far more than the tolerance."""
    x = layer_weights["x"]
    flat = x.reshape(-1, 32)
    got = expert_layer(0, 16, x, layer_weights)[0].reshape(-1, 32)
    exact = expert_reference(flat, layer_weights)
    np.testing.assert_allclose(got, exact, atol=TOL)
    rounded = expert_reference(flat, layer_weights, router_prec="bf16")
    assert float(jnp.max(jnp.abs(got - rounded))) > 10 * TOL


# -- (c) ContinuousBatcher with a draft that reads the hidden state ----------


class TableDraft(nn.Module):
    """A draft that knows the answers: ``table`` holds every request's plain
    greedy decoding.  Its cache is the tokens it has been given (``t_{i+1}``
    at slot ``i + 1``, a rank-3 leaf a row, moved like any cache payload);
    it finds the request whose decoding agrees with them from slot 1 on and
    proposes that request's ``t_{i+2} + off`` (``off`` 0: always right; 1:
    never).  It declares ``reads_hidden``, as the batcher expects."""

    config: TransformerConfig
    table: tuple = ()
    off: int = 0
    reads_hidden = True

    @staticmethod
    def tied(target_params):
        return {}

    @nn.compact
    def __call__(self, batch, train=False, decode=False, prefill=False):
        table = jnp.asarray(self.table, jnp.int32)               # [R, T]
        tokens = batch["tokens"]
        B, S = tokens.shape
        T = table.shape[1]
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                         (B, S))
        seen = self.variable("cache", "history", jnp.zeros,
                             (B, self.config.max_seq, 1), jnp.int32)
        seen.value = jax.vmap(
            lambda h, u, s: jax.lax.dynamic_update_slice(h, u, (s, 0)))(
            seen.value, tokens[..., None], positions[:, 0] + 1)
        history = seen.value[:, :T, 0]                           # [B, T]
        slot = jnp.arange(T)[None, None, :]
        known = (slot >= 1) & (slot <= positions[..., None] + 1)  # [B, S, T]
        agrees = jnp.all(
            (table[:, None, None, :] == history[None, :, None, :])
            | ~known[None], axis=-1)                             # [R, B, S]
        row = jnp.argmax(agrees, axis=0)
        nxt = table[row, jnp.clip(positions + 2, 0, T - 1)]
        vocab = self.config.vocab_size
        return {"logits": jax.nn.one_hot((nxt + self.off) % vocab, vocab)}


@pytest.fixture(scope="module")
def served(target):
    """Six requests (prompt lengths differ) and their plain greedy
    decoding, the oracle: ``generate`` at temperature 0, a row at a time."""
    model, params = target
    total = 40
    prompts = rows_of(11, [7, 12, 9, 15, 5, 10])
    plain = [np.asarray(generate_mod.generate(
        model, params, jnp.asarray(p[None]), total - len(p),
        temperature=0.0))[0] for p in prompts]
    # the table draft tells requests apart by their tokens from slot 1 on
    assert len({tuple(p[1:5]) for p in prompts}) == len(prompts)
    return prompts, plain, total


@pytest.mark.parametrize("draft_kind", ["always_right", "never_right", "mtp"])
def test_batcher_with_a_hidden_state_draft_is_plain_greedy(
        target, module, served, draft_kind):
    model, params = target
    prompts, plain, total = served
    if draft_kind == "mtp":
        _, draft, draft_params = module
    else:
        # its config says what a draft's must: max_seq, and no routed layers
        draft = TableDraft(
            dataclasses.replace(model.config, experts=None, first_k_dense=0),
            table=tuple(map(tuple, np.stack(plain).tolist())),
            off=0 if draft_kind == "always_right" else 1)
        draft_params = {}
    bat = ContinuousBatcher(model, draft, params, draft_params,
                            total_len=total, n_draft=1)
    bat.start(np.stack([np.resize(prompts[0], 7), np.resize(prompts[4], 7)]))
    for r in range(2):
        bat.retire(r)
    # rows admitted mid-batch: row 1 joins after row 0 has run three rounds
    order, waiting, in_row, finished = [0, 1, 2, 3, 4, 5], [], {}, {}
    waiting = list(order)
    bat.admit(0, prompts[waiting[0]])
    in_row[0] = waiting.pop(0)
    rounds = 0
    while in_row:
        n_before = np.asarray(bat.state[1]).copy()
        n_tok, done = bat.step()
        rounds += 1
        if draft_kind == "always_right":
            for row in in_row:          # two tokens a row a round
                assert n_tok[row] - n_before[row] == min(
                    2, total - n_before[row])
        for row in list(in_row):
            if done[row]:
                tokens, n = bat.row_tokens(row)
                finished[in_row.pop(row)] = tokens[:n]
        for row in range(2):
            if row not in in_row and waiting and (row == 0 or rounds >= 3):
                bat.admit(row, prompts[waiting[0]])
                in_row[row] = waiting.pop(0)
    for i, want in enumerate(plain):
        np.testing.assert_array_equal(finished[i], want)
    stats = bat.stats()
    assert stats["rounds"] == rounds
    from rocket_tpu.observe.trace import get_rounds

    seen = get_rounds().snapshot()
    assert seen["drafted"] > 0
    if draft_kind == "always_right":
        assert all(stats["accepted"] == stats["drafted"])
    if draft_kind == "never_right":
        assert not stats["accepted"].any()


def test_round_counters_count_what_the_rounds_routed(target, module):
    """The device counters of the rounds, fetched once: every live row's
    two tokens take ``top_k`` slots in each routed layer (the target's two
    and the draft's one), and the held ones are those of experts 8-11."""
    from rocket_tpu.observe.trace import get_rounds

    model, params = target
    _, draft, draft_params = module
    get_rounds().reset()
    bat = ContinuousBatcher(model, draft, params, draft_params,
                            total_len=30, n_draft=1)
    bat.start(np.stack(rows_of(13, [6, 6, 6])))
    for _ in range(5):
        bat.step()
    assert get_rounds().snapshot() == {}            # no round fetched them
    bat.publish_counters()
    seen = get_rounds().snapshot()
    assert seen["rounds"] == 5 and seen["drafted"] == 15
    assert seen["routed_slots"] == 5 * 3 * 2 * ARCH["top_k"] * 3
    tokens = np.asarray(seen["expert_tokens"])
    assert tokens.shape == (3, ARCH["held"])
    assert tokens.sum() == seen["held_slots"] <= seen["routed_slots"]
    bat.publish_counters()                          # started again from 0
    assert get_rounds().snapshot()["rounds"] == 5


# -- (d) what the latent model cannot do yet is refused by name ---------------


@pytest.mark.parametrize("option", [
    dict(kv_cache_int8=True),
    dict(decode_rolling_cache=True, attention_window=8),
    dict(scan_layers=True),
])
def test_config_refuses_by_name(option):
    with pytest.raises(ValueError, match=next(iter(option))):
        TransformerConfig(hidden=32, n_heads=4,
                          mla=MLAConfig(12, 8, 8, 4, 6), **option)


def test_batcher_refuses_by_name(target, module):
    model, params = target
    _, draft, draft_params = module
    kw = dict(total_len=30)
    with pytest.raises(ValueError, match="deeper than one"):
        ContinuousBatcher(model, draft, params, draft_params, n_draft=2, **kw)
    with pytest.raises(ValueError, match="sampled=True"):
        ContinuousBatcher(model, draft, params, draft_params, n_draft=1,
                          sampled=True, temperature=1.0, **kw)
    with pytest.raises(ValueError, match="kv_cache_int8"):
        ContinuousBatcher(model, draft, params, draft_params, n_draft=1,
                          kv_cache_int8=True, **kw)
    bat = ContinuousBatcher(model, draft, params, draft_params, n_draft=1,
                            **kw)
    assert bat.prefix_cache_ok is False
    prompt = rows_of(17, [6])[0]
    for call in (lambda: bat.prefill_handoff(prompt),
                 lambda: bat.admit_prefilled(0, None),
                 lambda: bat.prefill_from_pages(prompt, [])):
        with pytest.raises(ValueError, match="KVHandoff"):
            call()
    bat.start(prompt[None])
    with pytest.raises(ValueError, match="KVHandoff"):
        generate_mod.export_kv_row(bat.state, 0)
    with pytest.raises(ValueError, match="beam search"):
        beam_search_cached(model, params, jnp.asarray(prompt[None]), 4,
                           eos_id=0, beam_size=2)
