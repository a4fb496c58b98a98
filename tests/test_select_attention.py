"""Attention that chooses its keys, the softmax-routed expert layer, M-RoPE
and the chunked admission against the plain reference
(``benchmark/reference/keye_moe.py``) or a plain formula, at toy widths in
float32 with seeded weights, on the CPU.

Tolerances: program and reference compute the same float32 sums in another
order, so they differ by a few units in the last place of a logit of order
one (measured here: under 5e-6); ``TOL`` is 2e-5.  The same comparison with
the cached K rounded to bfloat16 (eight bits of mantissa), or with the
prompt's indexer keys lost, differs by 1e-3 and more, which the
planted-fault tests show.
"""

import dataclasses
import importlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.archs import keye_moe as family
from benchmark.kinds.train import named_leaves
from benchmark.reference import keye_moe as reference
from rocket_tpu.models.generate import ContinuousBatcher, export_kv_row
from rocket_tpu.models.layers import apply_rope, rotary_embedding
from rocket_tpu.models.moe import ExpertsConfig, RoutedExperts
from rocket_tpu.models.transformer import (SelectConfig, TransformerConfig,
                                           TransformerLM)
from rocket_tpu.ops import select_attention
from rocket_tpu.ops.attention import dot_attention

# the module, not the function of that name the package re-exports
generate_mod = importlib.import_module("rocket_tpu.models.generate")

TOL = 2e-5
VOCAB, MAX_SEQ = 97, 48

# top_k 4 of contexts up to 40, chunks of 4: every context passes top_k; one
# query against the 48 slots gathers (4 x GATHER_COST < 48), two and more mask
# (8 x GATHER_COST >= 48)
ARCH = dict(
    hidden=32, layers=3, heads=4, kv_heads=2, head_dim=16, expert_ffn=16,
    router=16, held=4, held_start=8, top_k=4, norm_topk=True, index_heads=2,
    index_dim=8, select_top_k=4, chunk=4, mrope=(2, 3, 3), eps=1e-6,
    rope_theta=10000.0, vocab=VOCAB, vocab_padded=VOCAB, max_pos=MAX_SEQ)


@pytest.fixture(scope="module", autouse=True)
def both_expert_paths():
    """``RoutedExperts`` runs every held expert over every token up to
    ``DENSE_BELOW`` tokens and groups the routed slots above: at 6, this
    file's decode rounds take the first path and its longer chunks the
    second.  Set once for the file (the jitted programs are traced with
    whatever it was then)."""
    from rocket_tpu.models import moe

    old, moe.DENSE_BELOW = moe.DENSE_BELOW, 6
    yield
    moe.DENSE_BELOW = old


def seeded(tree, seed):
    """Every leaf normal(0, 0.3); a norm's scale 1 + that."""
    leaves, treedef = jax.tree_util.tree_flatten(nn.meta.unbox(tree))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return treedef.unflatten([
        0.3 * jax.random.normal(k, leaf.shape, jnp.float32)
        + (1.0 if leaf.ndim == 1 else 0.0) for k, leaf in zip(keys, leaves)])


def build(arch, seed, max_seq=MAX_SEQ):
    model = family.program(arch, max_seq=max_seq)
    params = seeded(model.init(
        jax.random.PRNGKey(0),
        {"tokens": jnp.zeros((1, 4), jnp.int32)})["params"], seed)
    return model, params


@pytest.fixture(scope="module")
def target():
    return build(ARCH, 1)


@pytest.fixture(scope="module")
def draft():
    return build(family.draft(ARCH, {"draft_layers": 1}), 2)


def getter(params):
    """``get(group)`` of the reference over a program tree's leaves."""
    from benchmark import weights

    named = named_leaves(params, family)

    def get(group):
        return {k: v for k, v in named.items()
                if weights.group_of(k) == group}

    return get


def rows_of(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, size=n).astype(np.int32) for n in lengths]


def per_row(model):
    return model.clone(config=dataclasses.replace(model.config,
                                                  decode_per_row=True))


# -- (a) chunked prefill, then decode rounds through the cache ---------------


def test_full_forward_equals_the_reference(target):
    model, params = target
    row = rows_of(3, [37])[0]
    got = model.apply({"params": params}, {"tokens": row[None]})["logits"][0]
    want = reference.full_logits(ARCH, "f32", getter(params), row)
    np.testing.assert_allclose(got, want, atol=TOL)


def _prefill_then_decode(model, params, rows, prompts, steps, chunk=2):
    """Each prompt admitted on its own (chunks of ``config.select.chunk``
    and a ragged rest, a cache of the prompt's own length scattered into
    the row's slab), the rows then decoded together ``chunk`` tokens a step
    at each row's own frontier.  Returns each row's logits from its
    prompt's last position on."""
    model = per_row(model)
    B = len(rows)
    cache = generate_mod.zero_cache(
        model, params, jnp.zeros((B, 1), jnp.int32))
    got = [[] for _ in rows]
    for r, p in enumerate(prompts):
        one, last = generate_mod._row_prefill(
            model, params, jnp.asarray(rows[r][None, :p]))
        leaf = one["block_0"]["attn"]["cached_index_k"]
        assert leaf.shape == (1, ARCH["index_dim"], p)
        cache = generate_mod._scatter_row(cache, one, r)
        got[r].append(last)
    for step in range(steps):
        starts = np.asarray(prompts) + chunk * step
        out, mut = model.apply(
            {"params": params, "cache": cache},
            {"tokens": jnp.stack([rows[r][s:s + chunk]
                                  for r, s in enumerate(starts)]),
             "positions": jnp.asarray(starts[:, None] + np.arange(chunk),
                                      jnp.int32)},
            decode=True, mutable=["cache"])
        cache = mut["cache"]
        for r in range(B):
            got[r].append(out["logits"][r])
    return [jnp.concatenate(g) for g in got]


@pytest.mark.parametrize("chunk", [1, 2])
def test_prefill_in_chunks_then_decode_through_the_cache(target, chunk):
    """Two rows at unequal frontiers, both past ``top_k`` keys: prompts of
    22 (five chunks of 4 and a rest of 2) and 13 tokens, then eight more
    tokens a row, one a step on the gather path (a draft's step) or two a
    step on the mask path (a verify chunk), each query with its own
    selection.  Every logit equals the reference's one causal pass over
    the whole row."""
    model, params = target
    prompts, more = (22, 13), 8
    rows = rows_of(4, [p + more for p in prompts])
    assert select_attention.gathers(chunk, MAX_SEQ, ARCH["select_top_k"]) \
        == (chunk == 1)
    assert not select_attention.gathers(ARCH["chunk"], 22,
                                        ARCH["select_top_k"])
    got = _prefill_then_decode(model, params, rows, prompts, more // chunk,
                               chunk)
    for r, p in enumerate(prompts):
        want = reference.full_logits(ARCH, "f32", getter(params), rows[r])
        np.testing.assert_allclose(got[r], want[p - 1:], atol=TOL)


@pytest.mark.parametrize("leaf,fault", [
    ("cached_k", lambda v: v.astype(jnp.bfloat16).astype(v.dtype)),
    ("cached_index_k", jnp.zeros_like),
])
def test_a_bfloat16_cache_or_a_lost_index_is_caught(target, monkeypatch,
                                                    leaf, fault):
    """The tolerance is tight enough: the same decode with the cached K
    rounded to bfloat16 (other scores), or with the prompt's indexer keys
    lost (another selection: the indexer decides what is attended),
    differs by far more than ``TOL``."""
    model, params = target
    row = rows_of(5, [30])[0]
    real = generate_mod._scatter_row

    def rounded(batch_cache, one_cache, r):
        def walk(node):
            return {k: (fault(v) if k == leaf else walk(v)
                        if isinstance(v, dict) else v)
                    for k, v in node.items()}
        return real(batch_cache, walk(one_cache), r)

    monkeypatch.setattr(generate_mod, "_scatter_row", rounded)
    got = _prefill_then_decode(model, params, [row], (22,), 4)[0]
    want = reference.full_logits(ARCH, "f32", getter(params), row)[21:]
    assert jnp.max(jnp.abs(got[1:] - want[1:])) > 10 * TOL


@pytest.mark.parametrize("p", [ARCH["index_dim"], 13])
def test_an_admission_into_a_used_row_decodes_as_the_reference(target, p):
    """A prompt-length cache scattered into a row that a longer request
    has used takes the head of the row's slab, and the indexer's leaf
    ``[1, index_dim, p]`` goes there by its slots, also where ``p`` equals
    ``index_dim`` and the leaf's second axis matches the slab's.  The row
    then decodes, one token a step beside another row, to the reference's
    logits: the old occupant's slots past the prompt are hidden
    causally."""
    model, params = per_row(target[0]), target[1]
    old, new, other = rows_of(11, [30, p + 6, 16])
    cache = generate_mod.zero_cache(
        model, params, jnp.zeros((2, 1), jnp.int32))

    def admit(cache, row, tokens):
        one, last = generate_mod._row_prefill(model, params,
                                              jnp.asarray(tokens[None]))
        return generate_mod._scatter_row(cache, one, row), last

    cache, _ = admit(cache, 0, old)
    cache, _ = admit(cache, 1, other[:10])
    cache, last = admit(cache, 0, new[:p])
    leaf = cache["block_0"]["attn"]["cached_index_k"]
    assert leaf.shape == (2, ARCH["index_dim"], MAX_SEQ)
    assert float(jnp.abs(leaf[0, :, p:30]).max()) > 0    # the old row's
    got = [last[0]]
    for step in range(6):
        starts = np.asarray([p, 10]) + step
        out, mut = model.apply(
            {"params": params, "cache": cache},
            {"tokens": jnp.asarray([[new[starts[0]]], [other[starts[1]]]]),
             "positions": jnp.asarray(starts[:, None], jnp.int32)},
            decode=True, mutable=["cache"])
        cache = mut["cache"]
        got.append(out["logits"][0, 0])
    want = reference.full_logits(ARCH, "f32", getter(params), new)[p - 1:]
    np.testing.assert_allclose(jnp.stack(got), want, atol=TOL)


def test_the_two_selections_keep_the_same_keys_ties_included():
    """``select_mask`` (a search over bit patterns) and ``select_slots``
    (``lax.top_k``) against the reference's sort, on scores with planted
    ties at the threshold, negative and positive zeros, and dead slots."""
    rng = np.random.default_rng(0)
    scores = rng.normal(size=(3, 5, 40)).astype(np.float32)
    scores[0, 0, 5:30] = 0.25          # a tie across the threshold
    scores[0, 1, ::3] = -0.0           # zeros of both signs
    scores[0, 1, 1::3] = 0.0
    scores[1, :, 20:] = -np.inf        # fewer live keys than top_k
    scores[2, 3, :] = 1.0              # all equal: the lowest slots
    scores = jnp.asarray(scores) + 0.0
    for top_k in (1, 8, 25, 40, 64):
        want = np.stack([np.asarray(reference.select(s, top_k))
                         for s in scores])
        mask = np.asarray(select_attention.select_mask(scores, top_k))
        np.testing.assert_array_equal(mask, want)
        slots, valid = select_attention.select_slots(scores, top_k)
        gathered = np.zeros_like(want)
        b, s, _ = np.indices(slots.shape)
        gathered[b[valid], s[valid], np.asarray(slots)[valid]] = True
        np.testing.assert_array_equal(gathered, want)
    assert want[2, 3].all() and mask[0, 0, :8].sum() >= 5


# -- the admission's masked attention: the kernel, in interpret mode ---------


@pytest.fixture
def masked_kernel_here(monkeypatch):
    """``why_not_masked`` with its refusal of a backend that is no TPU taken
    out: a chunk the kernel would take on the chip takes it here, in
    interpret mode."""
    real = select_attention.why_not_masked

    def why_not(q, k):
        reason = real(q, k)
        return None if reason == "backend" else reason

    monkeypatch.setattr(select_attention, "why_not_masked", why_not)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)])
def test_the_masked_kernel_is_dot_attention_under_the_mask(
        monkeypatch, dtype, tol):
    """Two rows at other frontiers, four key blocks of which the second
    row sees two: the kernel equals ``dot_attention(key_mask=)``, and what
    lies in the blocks past a row's last position is never read (NaNs
    there change nothing)."""
    monkeypatch.setattr(select_attention, "MASK_BLOCK_K", 128)
    B, S, H, KV, D, T = 2, 128, 4, 2, 128, 512
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, S, H, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, T, KV, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, T, KV, D)).astype(dtype)
    q_pos = jnp.asarray([300, 37])[:, None] + jnp.arange(S)[None]
    slots = jnp.arange(T)[None, None]
    mask = (slots <= q_pos[:, :, None]) & (
        (jax.random.uniform(ks[3], (B, S, T)) < 0.3)
        | (slots == q_pos[:, :, None]))
    want = dot_attention(q, k, v, causal=False, key_mask=mask)
    got = select_attention.masked_attention(q, k, v, mask, q_pos[:, -1] + 1)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    dead = (slots[0, 0] >= 256)[None, :, None, None]     # row 1 sees 165
    poisoned = select_attention.masked_attention(
        q, jnp.where(dead & (jnp.arange(B) == 1)[:, None, None, None],
                     jnp.nan, k), v, mask, q_pos[:, -1] + 1)
    np.testing.assert_array_equal(np.asarray(poisoned, np.float32),
                                  np.asarray(got, np.float32))


def test_what_keeps_the_masked_kernel_off_is_named():
    q = jax.ShapeDtypeStruct((1, 512, 32, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16)
    why = select_attention.why_not_masked
    assert why(q, k) == "backend"           # all else fits: the cell's chunk
    short = jax.ShapeDtypeStruct((16, 2, 32, 128), jnp.bfloat16)
    assert why(short, k) == "S=2"
    assert why(q, jax.ShapeDtypeStruct((1, 20481, 4, 128),
                                       jnp.bfloat16)) == "T=20481"
    assert why(jax.ShapeDtypeStruct((1, 512, 32, 64), jnp.bfloat16),
               k) == "D=64"
    assert why(q, jax.ShapeDtypeStruct((1, 16384, 4, 128),
                                       jnp.int8)) == "int8"
    assert select_attention.mask_block_k(4096) == 1024
    assert select_attention.mask_block_k(256) == 256
    assert select_attention.mask_block_k(1100) is None


def test_an_admission_through_the_masked_kernel_is_the_reference(
        masked_kernel_here):
    """Heads of 128 and chunks of 128: a prompt of 256 tokens is admitted in
    two chunks that attend through the kernel (counted: ``path`` kernel),
    and its logits, then two decoded tokens', are the reference's."""
    from rocket_tpu.observe import trace

    arch = dict(ARCH, hidden=32, layers=2, heads=2, kv_heads=1, head_dim=128,
                mrope=(16, 24, 24), select_top_k=16, chunk=128, max_pos=320)
    model, params = build(arch, 6, max_seq=320)
    row = rows_of(10, [258])[0]
    tracer = trace.arm(1024)
    tracer.clear()
    try:
        got = _prefill_then_decode(model, params, [row], (256,), 1)[0]
        paths = [e[5]["path"] for e in tracer.events()
                 if e[1] == "attention/select/prefill"]
    finally:
        trace.disarm()
    assert paths and set(paths) == {"kernel"}
    want = reference.full_logits(arch, "f32", getter(params), row)[255:]
    np.testing.assert_allclose(got, want, atol=5 * TOL)


# -- a round's masked chunk through the decode kernel, in interpret mode -------


def test_the_kernel_route_keeps_the_same_keys_and_output(monkeypatch,
                                                         decode_kernel_here):
    """A round's chunk on the mask branch (two queries a row, bfloat16
    heads of 128, an idle row) keeps the same keys through the decode
    kernel as through ``dot_attention(key_mask=)``, and the live rows'
    outputs agree within bfloat16's rounding."""
    from rocket_tpu.ops import decode_attention

    B, S, H, KV, D, T, top_k = 3, 2, 8, 4, 128, 64, 4
    assert not select_attention.gathers(S, T, top_k)
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, T, KV, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, T, KV, D), jnp.bfloat16)
    q_pos = jnp.asarray([[5, 6], [40, 41], [62, 63]], jnp.int32)
    idle = jnp.asarray([False, True, False])
    q_idx = jax.random.normal(ks[3], (B, S, 2, 8))
    k_idx = jax.random.normal(ks[4], (B, 8, T))
    scores = select_attention.index_scores(
        q_idx, jnp.ones((B, S, 2)), k_idx, q_pos, idle)
    seen = []
    real = decode_attention.decode_attention
    monkeypatch.setattr(decode_attention, "decode_attention",
                        lambda *a, **kw: seen.append(kw) or real(*a, **kw))
    got, kept = select_attention.selected_attention(
        q, k, v, scores, q_pos, top_k, idle)
    assert len(seen) == 1 and seen[0]["key_mask"] is not None
    chosen = select_attention.select_mask(scores, top_k)
    want = dot_attention(q, k, v, causal=False, key_mask=chosen)
    np.testing.assert_array_equal(kept, jnp.sum(chosen, axis=-1))
    assert (np.asarray(kept)[[0, 2]] == top_k).all() and not kept[1].any()
    np.testing.assert_allclose(np.asarray(got[::2], np.float32),
                               np.asarray(want[::2], np.float32),
                               atol=2e-2, rtol=2e-2)


def test_a_round_through_the_decode_kernel_is_the_reference(
        decode_kernel_here):
    """Heads of 128: verify chunks of two tokens a row attend through the
    decode kernel with the selection as its key mask (counted: ``path``
    kernel, and no ``selected`` fallback for them), and the logits are the
    reference's."""
    from rocket_tpu.observe import trace

    arch = dict(ARCH, layers=2, heads=2, kv_heads=1, head_dim=128,
                mrope=(16, 24, 24))
    model, params = build(arch, 7)
    prompts, more = (22, 13), 4
    rows = rows_of(12, [p + more for p in prompts])
    tracer = trace.arm(1024)
    tracer.clear()
    try:
        got = _prefill_then_decode(model, params, rows, prompts, more // 2)
        events = [(e[1], e[5]) for e in tracer.events()]
    finally:
        trace.disarm()
    rounds = [kw for name, kw in events if name == "attention/select/decode"
              and kw["S"] == 2 and kw["T"] == MAX_SEQ]
    assert rounds and {kw["path"] for kw in rounds} == {"kernel"}
    assert not [kw for name, kw in events
                if name == "attention/decode/fallback" and kw["S"] == 2
                and kw["T"] == MAX_SEQ]
    for r, p in enumerate(prompts):
        want = reference.full_logits(arch, "f32", getter(params), rows[r])
        np.testing.assert_allclose(got[r], want[p - 1:], atol=5 * TOL)


# -- (b) a selection that keeps every key is plain attention -----------------


def test_top_k_over_the_context_is_plain_attention_bit_for_bit():
    """With ``top_k`` at least the slab the selecting layer takes the mask
    path, its mask is the causal one, and the logits equal a plain
    ``Attention``'s bit for bit in float32, through prefill and decode (the
    indexer's weights then decide nothing)."""
    base = dict(vocab_size=VOCAB, hidden=32, n_layers=2, n_heads=4,
                n_kv_heads=2, head_width=16, max_seq=MAX_SEQ,
                attention="dot", norm_eps=1e-6)
    plain = TransformerLM(TransformerConfig(**base))
    choosy = TransformerLM(TransformerConfig(
        **base, select=SelectConfig(index_heads=2, index_dim=8,
                                    top_k=MAX_SEQ, chunk=64)))
    tokens = {"tokens": jnp.zeros((1, 4), jnp.int32)}
    params = seeded(choosy.init(jax.random.PRNGKey(0), tokens)["params"], 3)
    shared = seeded(plain.init(jax.random.PRNGKey(0), tokens)["params"], 3)
    # the plain model's leaves from the selecting one's, by name
    flat = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    shared = jax.tree_util.tree_map_with_path(lambda p, _: flat[p], shared)
    row = jnp.asarray(rows_of(6, [24])[0][None])

    def run(model, p):
        model = per_row(model)
        outs, cache = [], generate_mod.zero_cache(model, p, row[:, :1])
        for lo, hi in ((0, 17), (17, 19), (19, 24)):
            out, mut = model.apply(
                {"params": p, "cache": cache},
                {"tokens": row[:, lo:hi],
                 "positions": jnp.arange(lo, hi, dtype=jnp.int32)[None]},
                decode=True, mutable=["cache"])
            cache = mut["cache"]
            outs.append(out["logits"])
        return jnp.concatenate(outs, axis=1)

    np.testing.assert_array_equal(run(choosy, params), run(plain, shared))
    np.testing.assert_array_equal(
        choosy.apply({"params": params}, {"tokens": row})["logits"],
        plain.apply({"params": shared}, {"tokens": row})["logits"])


# -- (c) the shares of the expert layer add up; the softmax router -----------


def _expert_layer(held_start, n_held, n_routed=16):
    return RoutedExperts(ExpertsConfig(
        n_routed=n_routed, top_k=4, expert_dim=16, n_shared=0, scale=1.0,
        norm_topk=True, router="softmax", held_start=held_start,
        n_held=n_held))


@pytest.mark.parametrize("tokens", [5, 11])     # dense path, grouped path
def test_the_eight_shares_add_up_to_the_uncut_layer(tokens):
    """Shares ``held_start`` 0, 2, ... 14 of two experts each, every one
    over the whole router: their outputs add up to what the reference
    gives for the layer with all sixteen held (no shared expert to count
    once)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (1, tokens, 32))
    whole = _expert_layer(0, 16)
    params = seeded(whole.init(jax.random.PRNGKey(0), x)["params"], 4)
    arch = dict(ARCH, held=16, held_start=0)
    want = reference.experts(
        arch, "f32", x[0], {"router.w": params["router"]},
        {"eg": params["w_gate"], "eu": params["w_up"],
         "ed": params["w_down"]})
    np.testing.assert_allclose(
        whole.apply({"params": params}, x)[0], want, atol=TOL)
    total = jnp.zeros_like(want)
    for share in range(8):
        lo = 2 * share
        part = {"router": params["router"],
                **{k: params[k][lo:lo + 2]
                   for k in ("w_gate", "w_up", "w_down")}}
        total = total + _expert_layer(lo, 2).apply({"params": part}, x)[0]
    np.testing.assert_allclose(total, want, atol=TOL)


def test_the_softmax_router_against_the_formula():
    """``p = softmax(W_r z)`` over all experts, top-4, ``p / sum(p)``: the
    weights the layer applies, read off one-hot experts (an expert that
    returns its own index picks out its weight), and the ids it sows."""
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 5, 32))
    layer = _expert_layer(0, 16)
    params = seeded(layer.init(jax.random.PRNGKey(0), x)["params"], 5)
    p = jax.nn.softmax(jnp.einsum(
        "sd,de->se", x[0], params["router"],
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    top, idx = jax.lax.top_k(p, 4)
    _, sown = layer.apply({"params": params}, x, mutable=["routing"])
    np.testing.assert_array_equal(sown["routing"]["top_idx"][0][0], idx)
    want = reference.route(dict(ARCH, router=16), "f32", x[0],
                           params["router"])
    np.testing.assert_allclose(
        jnp.take_along_axis(want, idx, axis=-1),
        top / jnp.sum(top, axis=-1, keepdims=True), atol=1e-6)
    assert float(jnp.abs(jnp.sum(want, axis=-1) - 1.0).max()) < 1e-6
    with pytest.raises(ValueError, match="softmax"):
        ExpertsConfig(n_routed=4, top_k=2, expert_dim=8, router="tanh")


# -- (d) M-RoPE ---------------------------------------------------------------


def test_mrope_with_equal_streams_is_plain_rope_and_else_the_sectioned():
    B, S, H, D = 2, 7, 3, 16
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, H, D))
    pos = jnp.arange(B * S, dtype=jnp.int32).reshape(B, S) * 3
    plain = apply_rope(x, *rotary_embedding(pos, D, 1e4))
    for positions in (pos, jnp.stack([pos] * 3)):
        same = apply_rope(x, *rotary_embedding(
            positions, D, 1e4, mrope_section=(2, 3, 3)))
        np.testing.assert_array_equal(same, plain)
    streams = jnp.stack([pos, 2 * pos + 1, 40 - pos])
    got = apply_rope(x, *rotary_embedding(
        streams, D, 1e4, mrope_section=(2, 3, 3)))
    # the sectioned formula: frequency i turns by the stream of its run
    freqs = 1.0 / (1e4 ** (np.arange(0, D, 2) / D))
    which = np.repeat(np.arange(3), (2, 3, 3))
    ang = np.asarray(streams, np.float64)[which].transpose(1, 2, 0) * freqs
    x1, x2 = np.asarray(x)[..., :D // 2], np.asarray(x)[..., D // 2:]
    cos, sin = np.cos(ang)[:, :, None], np.sin(ang)[:, :, None]
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # and the reference's rotation of one row is the same
    ref = reference.rope(x[0], streams[:, 0], 1e4, (2, 3, 3))
    np.testing.assert_allclose(ref, want[0], atol=2e-5)
    with pytest.raises(ValueError, match="mrope_section"):
        rotary_embedding(streams, D, 1e4, mrope_section=(2, 2, 2))


def test_the_model_takes_three_streams(target):
    """``batch['mrope_positions']`` turn the heads; equal streams give the
    logits of the call without them, other streams the reference's."""
    model, params = target
    row = rows_of(7, [12])[0]
    pos = jnp.arange(12, dtype=jnp.int32)[None]
    plain = model.apply({"params": params}, {"tokens": row[None]})["logits"]
    equal = model.apply(
        {"params": params},
        {"tokens": row[None], "positions": pos,
         "mrope_positions": jnp.stack([pos] * 3)})["logits"]
    np.testing.assert_array_equal(equal, plain)
    streams = jnp.stack([pos, pos // 2, pos % 3])
    got = model.apply(
        {"params": params},
        {"tokens": row[None], "positions": pos,
         "mrope_positions": streams})["logits"][0]
    want = reference.full_logits(ARCH, "f32", getter(params), row,
                                 streams[:, 0])
    np.testing.assert_allclose(got, want, atol=TOL)


# -- (f) a model without SelectConfig traces what it traced before ------------


# sha256[:16] of ``str(make_jaxpr(...))`` of the toy round and admission
# below, taken on the commit before this file existed (11dcaea, PR 31): a
# change that moves them has changed what every dense model's round or
# admission traces, and says so by bringing new values.
ROUND_JAXPR, ADMIT_JAXPR = "3f6d98842c6360a0", "2fc6167348152e32"


def test_a_model_without_a_selection_traces_what_it_traced_before():
    """Its round state has seven entries and no counters, it declares no
    admission chunk, and the jaxprs of ``_spec_round`` and ``_spec_admit``
    at toy size are, letter for letter, those of the tree that knew no
    selection, no counters in the two-model round and no chunked
    admission."""
    import hashlib

    cfg = TransformerConfig(vocab_size=64, hidden=32, n_layers=2, n_heads=4,
                            max_seq=64, decode_per_row=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(1),
                        {"tokens": np.zeros((1, 8), np.int32)})["params"]
    kw = dict(eos_token=None, sampled=False, top_k=None, top_p=None)
    state = generate_mod._spec_prefill_impl(
        model, model, params, params, jnp.ones((2, 8), jnp.int32), None, 0.0,
        max_new_tokens=40, **kw)
    assert len(state) == 7
    assert generate_mod._admission_chunk(model) is None
    assert not generate_mod._round_counts(model, model)
    traced = {
        "round": jax.make_jaxpr(lambda s: generate_mod._spec_round_impl(
            model, model, params, params, s, 0.0, n_draft=2, **kw))(state),
        "admit": jax.make_jaxpr(
            lambda s: generate_mod._spec_admit.__wrapped__(
                model, model, params, params, s, jnp.int32(0),
                jnp.ones((1, 40), jnp.int32), None, 0.0, **kw))(state),
    }
    got = {k: hashlib.sha256(str(v).encode()).hexdigest()[:16]
           for k, v in traced.items()}
    assert got == {"round": ROUND_JAXPR, "admit": ADMIT_JAXPR}


def test_what_a_selection_cannot_run_with_is_refused_by_name(target, draft):
    sel = SelectConfig(index_heads=2, index_dim=8, top_k=4, chunk=4)
    for kw, name in ((dict(kv_cache_int8=True), "kv_cache_int8"),
                     (dict(decode_rolling_cache=True, attention_window=8),
                      "decode_rolling_cache"),
                     (dict(fused_qkv=True), "fused_qkv"),
                     (dict(scan_layers=True), "scan_layers"),
                     (dict(pipeline_microbatches=2), "pipeline_microbatches")):
        with pytest.raises(ValueError, match=name):
            TransformerConfig(select=sel, **kw)
    with pytest.raises(ValueError, match="index_dim"):
        SelectConfig(index_heads=2, index_dim=7, top_k=8)
    model, params = target
    with pytest.raises(ValueError, match="select"):
        generate_mod.beam_search_cached(
            model, params, jnp.ones((1, 4), jnp.int32), 4, 0, beam_size=2)
    bat = ContinuousBatcher(model, draft[0], params, draft[1], total_len=30,
                            n_draft=1)
    assert not bat.prefix_cache_ok
    with pytest.raises(ValueError, match="chooses its keys"):
        bat.prefill_handoff(np.ones(6, np.int32))
    with pytest.raises(ValueError, match="kv_cache_int8"):
        ContinuousBatcher(model, draft[0], params, draft[1], total_len=30,
                          n_draft=1, kv_cache_int8=True)


# -- through ContinuousBatcher: greedy output, counters, spans ----------------


def test_the_batcher_serves_plain_greedy_and_counts_what_it_chose(
        target, draft):
    """Rows admitted mid-batch (chunked admissions of 9 and 22 tokens)
    decode to what ``generate`` gives each prompt alone; the rounds' device
    counters hold the keys kept and seen, and the spans name the paths."""
    from rocket_tpu.observe import trace
    from rocket_tpu.serve.metrics import ServeCounters

    model, params = target
    prompts = rows_of(8, [9, 22, 9])
    total = 30
    want = [np.asarray(generate_mod.generate(
        model, params, jnp.asarray(p[None]), total - len(p),
        temperature=0.0))[0] for p in prompts]
    trace.get_rounds().reset()
    tracer = trace.arm(4096)
    tracer.clear()
    try:
        bat = ContinuousBatcher(model, draft[0], params, draft[1],
                                total_len=total, n_draft=1)
        bat.reads.counters = ServeCounters()
        bat.start(np.zeros((2, 1), np.int32))
        for row in (0, 1):
            bat.retire(row)
        assert len(bat.state) == 8 and "selected_keys" in bat.state[7]
        waiting, in_row, got = [2], {}, {}
        for row in (0, 1):
            bat.admit(row, prompts[row])
            in_row[row] = row
        while in_row:
            _, done = bat.step()
            for row in [r for r in in_row if done[r]]:
                tokens, n = bat.row_tokens(row)
                got[in_row.pop(row)] = np.asarray(tokens)[:n]
                if waiting:
                    bat.admit(row, prompts[waiting[0]])
                    in_row[row] = waiting.pop(0)
        # a chunk longer than a round's counts as an admission's
        long_row = jnp.asarray(prompts[1][None, :12])
        per_row(model).apply(
            {"params": params,
             "cache": generate_mod.zero_cache(model, params, long_row)},
            {"tokens": long_row}, decode=True, mutable=["cache"])
        events = list(tracer.events())
        names = {e[1]: e[5] for e in events}
    finally:
        trace.disarm()
    for i, tokens in enumerate(want):
        np.testing.assert_array_equal(got[i], tokens)
    bat.publish_counters()
    seen = trace.get_rounds().snapshot()
    trace.get_rounds().reset()      # the record is the process's
    assert seen["rounds"] > 0 and seen["routed_slots"] > 0
    assert 0 < seen["selected_keys"] < seen["live_keys"]
    # every query of a live row past 4 keys keeps exactly 4
    assert seen["selected_keys"] % ARCH["select_top_k"] == 0
    assert 10.0 < 100.0 * seen["held_slots"] / seen["routed_slots"] < 45.0
    share = bat.reads.counters.snapshot()["selected_key_share"]
    assert 0.1 < share < 0.6
    decode = [e[5] for e in events if e[1] == "attention/select/decode"]
    assert {d["path"] for d in decode} == {"gather", "mask"}
    assert {(d["S"], d["top_k"], d["index_heads"]) for d in decode} \
        >= {(1, 4, 2), (2, 4, 2)}
    assert {e[5]["chunk"] for e in events
            if e[1] == "attention/select/prefill"} == {12}
    assert names["generate/spec_admit"]["chunks"] in (3, 6)
    reasons = {e[5].get("reason") for e in events
               if e[1] == "attention/decode/fallback"}
    assert "selected" in reasons


def test_export_and_import_carry_the_index_leaf(target, draft):
    """``export_kv_row`` slices the indexer's cache with K and V (``(1,
    index_dim, slots)``), ``_spec_import_row`` puts it back, and the
    imported row decodes as the row it was taken from."""
    model, params = target
    prompts = rows_of(9, [13, 13])
    bat = ContinuousBatcher(model, draft[0], params, draft[1], total_len=30,
                            n_draft=1)
    bat.start(np.stack(prompts))
    handoff = export_kv_row(bat.state, 0)
    leaf = handoff.cache_t["block_0"]["attn"]["cached_index_k"]
    assert leaf.shape == (1, ARCH["index_dim"], MAX_SEQ)
    assert float(jnp.abs(leaf[0, :, :13]).max()) > 0
    while not bat.all_done:
        bat.step()
    want = np.asarray(bat.row_tokens(0)[0])
    bat.state = generate_mod._spec_import_row(
        bat.state, jnp.int32(1), handoff.buf, handoff.n_tok, handoff.done,
        handoff.cache_t, handoff.cache_d)
    assert len(bat.state) == 8
    while not bat.all_done:
        bat.step()
    np.testing.assert_array_equal(np.asarray(bat.row_tokens(1)[0]), want)


def test_pages_cut_the_index_leaf_on_its_slots(target, draft):
    """``split_pages`` and ``from_pages`` cut and join the indexer's leaf
    on its last axis, K on its second: three pages of 4 of a 13-token row
    join to the row's first 12 slots of each, zero past them."""
    model, params = target
    bat = ContinuousBatcher(model, draft[0], params, draft[1], total_len=30,
                            n_draft=1)
    bat.start(np.stack(rows_of(12, [13, 13])))
    handoff = export_kv_row(bat.state, 0).to_host()
    pages = handoff.split_pages(4)
    assert len(pages) == 3
    attn = lambda cache: cache["block_0"]["attn"]  # noqa: E731
    assert attn(pages[0].cache_t)["cached_index_k"].shape \
        == (1, ARCH["index_dim"], 4)
    joined = generate_mod.KVHandoff.from_pages(
        pages, total_len=30, slots_t=MAX_SEQ, slots_d=MAX_SEQ)
    for got, want in ((attn(joined.cache_t), attn(handoff.cache_t)),
                      (attn(joined.cache_d), attn(handoff.cache_d))):
        idx, k = got["cached_index_k"], got["cached_k"]
        assert idx.shape == want["cached_index_k"].shape
        np.testing.assert_array_equal(idx[..., :12],
                                      want["cached_index_k"][..., :12])
        np.testing.assert_array_equal(k[:, :12], want["cached_k"][:, :12])
        assert not idx[..., 12:].any() and not k[:, 12:].any()


# -- the cell's round compiled for a described v5e, with no chip attached -------


@pytest.fixture(scope="module")
def described_chip():
    """A described ``v5e`` chip (``benchmark/offchip.py``): only inside a
    fixture, never at import; skipped where libtpu cannot be had."""
    import os

    from jax.experimental.compilation_cache import compilation_cache

    from benchmark import offchip

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described device's executable cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield offchip.topology_device()
    except Exception as exc:                       # no libtpu, lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


@pytest.fixture(scope="module")
def cells_round(described_chip):
    """``keye30b-serve-longctx``'s ``_spec_round`` as the chip compiles it
    (the Mosaic kernels, the decode kernel's among them, on), the round
    state's leaves of its first layer, and the sizes of what it copies
    (size-1 axes dropped).  Compiled once for the tests below."""
    import re

    from benchmark import harness, offchip
    from rocket_tpu.ops import decode_attention

    cell = harness.resolve_cell("keye30b-serve-longctx")
    state = offchip._serving_state(cell)[-1]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(select_attention, "_on_tpu", lambda: True)
        patch.setattr(decode_attention, "_on_tpu", lambda: True)
        with offchip.mosaic_kernels():
            compiled = offchip.compile_spec_round(cell, described_chip)
    copied = [
        tuple(int(n) for n in dims.split(",") if n != "1")
        for dims in re.findall(r"= \w+\[([\d,]+)\]\S* copy\(",
                               compiled.as_text())]
    assert copied                   # the pattern finds the program's copies
    return compiled, state[3]["block_0"]["attn"], copied


def test_the_cells_round_copies_no_index_leaf(cells_round):
    """No ``copy`` of the round has the sizes of an index leaf, in any
    order of its axes.  A leaf the draft's chain carries in one layout and
    scores in another is copied in, inside and out of the loop: eight
    copies a round and 399.6 MB of scratch."""
    from benchmark import offchip

    compiled, leaves, copied = cells_round
    leaf = leaves["cached_index_k"]
    rows, d, slots = leaf.shape
    assert not [c for c in copied if sorted(c) == sorted((rows, d, slots))]
    # four leaves are 168 MB; the round's scratch held 399.6 MB with the
    # copies and 111.2 MB without
    four = 4 * rows * d * slots * jnp.dtype(leaf.dtype).itemsize
    assert offchip.memory(compiled)["temp_size_in_bytes"] < four


def test_the_cells_verify_pass_reads_k_and_v_as_stored(cells_round):
    """The target's six selecting layers attend their verify chunk through
    the decode kernel with the selection as its key mask (by the masked
    call's name), and no ``copy`` has the sizes of a K or V leaf in any
    order of its axes: XLA's masked pass relaid each leaf inside its
    fusions, and the flat view ``[rows, slots * 4, 128]`` of a leaf whose
    slots are (4, 128) tiles is a copy of it."""
    compiled, leaves, copied = cells_round
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "selected_decode_attention" in line.split("=")[0]]
    assert len(calls) == 6
    kv = leaves["cached_k"].shape
    assert kv == leaves["cached_v"].shape == (16, 20481, 4, 128)
    assert not [c for c in copied if sorted(c) == sorted(kv)]
