"""Int8 KV-cache decode (TransformerConfig.kv_cache_int8) — the oracle
discipline:

- SHORT prompts, plain cache: the int8 cache's logits lie within the
  quantisation's error of the bf16 cache's, and its greedy tokens are
  the oracle's wherever the bf16 margin exceeds that error; under the
  ContinuousBatcher with a mid-batch admit and through cached beam
  search (the beam gather must carry the rank-4 scale leaves with the
  payload) they are TOKEN-IDENTICAL;
- LONG prompts, rolling cache: teacher-forced perplexity through the
  int8 cache stays within a documented tolerance (5% relative) of the
  bf16 cache — the regime where quantization error accumulates over
  many cache reads;
- layout: the cache pytree gains int8 payload + [B, slots, KV, 1] f32
  scale leaves, which is what the decode bench's MBU bytes model reads.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import flax.linen as nn

from rocket_tpu.models.generate import (
    ContinuousBatcher,
    beam_search_cached,
    decode_cache_shapes,
    generate,
    zero_cache,
)
from rocket_tpu.models.transformer import TransformerConfig, TransformerLM


def _cfg(style="gpt2", **kw):
    if style == "gpt2":
        base = dict(
            vocab_size=64, hidden=32, n_layers=2, n_heads=4, max_seq=64,
            norm="layernorm", mlp="gelu", positions="learned",
            tie_embeddings=True, use_bias=True, attention="dot",
        )
    else:  # llama: RoPE + GQA
        base = dict(
            vocab_size=64, hidden=32, n_layers=2, n_heads=4, n_kv_heads=2,
            max_seq=64, attention="dot",
        )
    base.update(kw)
    return TransformerConfig(**base)


def _params(model, prompt, seed=1):
    return nn.meta.unbox(
        model.init(jax.random.PRNGKey(seed), {"tokens": prompt})["params"]
    )


def _teacher_forced_logits(model, params, tokens):
    """``[B, T-1, V]`` float32 logits of ``tokens`` decoded one position
    at a time through the model's KV cache."""
    B, T = tokens.shape
    cache = zero_cache(model, params, tokens[:, :1])
    rows = []
    for t in range(T - 1):
        out, mutated = model.apply(
            {"params": params, "cache": cache},
            {"tokens": tokens[:, t:t + 1],
             "positions": jnp.full((B, 1), t, jnp.int32)},
            decode=True, mutable=["cache"],
        )
        cache = mutated["cache"]
        rows.append(np.asarray(out["logits"][:, -1], np.float32))
    return np.stack(rows, axis=1)


# An int8 page keeps each key and value within 1/254 of its (row, slot,
# head) maximum; through two layers that reads as about 1 % of a position's
# logit spread on these models (measured 0.5-1.3 %).  Eight steps of room.
_INT8_LOGIT_TOL = 8.0 / 254.0


@pytest.mark.parametrize("style", ["gpt2", "llama"])
def test_int8_kv_greedy_matches_bf16_cache_oracle(devices, style):
    """Same params, same prompt: decoding the bf16 cache's own greedy
    tokens through the int8 cache gives its logits within the
    quantisation's error, and the int8 cache's greedy decode emits the
    same tokens until a position whose bf16 margin lies inside that error
    (on random weights near ties are common: a token there may differ, and
    what follows it is another sequence)."""
    cfg = _cfg(style)
    model = TransformerLM(cfg)
    model8 = TransformerLM(dataclasses.replace(cfg, kv_cache_int8=True))
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, size=(2, 8)), jnp.int32
    )
    P = prompt.shape[1]
    params = _params(model, prompt)
    want = generate(model, params, prompt, max_new_tokens=12,
                    temperature=0.0)
    ref = _teacher_forced_logits(model, params, want)
    quant = _teacher_forced_logits(model8, params, want)
    tol = _INT8_LOGIT_TOL * (ref.max(-1) - ref.min(-1))      # [B, T-1]
    err = np.abs(quant - ref).max(-1)
    assert (err <= tol).all(), (err / tol).max()
    # a near tie is where the int8 decode may part from the oracle
    top2 = np.sort(ref, axis=-1)[..., -2:]
    decided = (top2[..., 1] - top2[..., 0]) > 2.0 * tol
    assert np.array_equal(quant.argmax(-1)[decided], ref.argmax(-1)[decided])
    got = np.asarray(generate(model8, params, prompt, max_new_tokens=12,
                              temperature=0.0))
    want = np.asarray(want)
    for row in range(want.shape[0]):
        differ = np.nonzero(got[row] != want[row])[0]
        if differ.size:
            # token t comes from the logits at position t - 1
            assert differ[0] >= P and not decided[row, differ[0] - 1], (
                row, differ[0])


def test_int8_kv_cache_layout(devices):
    """The cache pytree under kv_cache_int8: int8 payload, rank-4 f32
    scales (per row/slot/kv-head), scalar index — the scale rank is the
    contract the batcher's cache-shuffling helpers key on."""
    cfg = _cfg("llama", kv_cache_int8=True)
    model = TransformerLM(cfg)
    prompt = jnp.zeros((2, 4), jnp.int32)
    shapes = decode_cache_shapes(model, _params(model, prompt), prompt)
    leaves = {
        "/".join(str(k.key) for k in path): leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(shapes)
    }
    ks = [v for name, v in leaves.items() if name.endswith("cached_k")]
    scales = [v for name, v in leaves.items()
              if name.endswith("cached_k_scale")]
    assert ks and scales and len(ks) == len(scales) == cfg.n_layers
    for k, s in zip(ks, scales):
        assert k.dtype == jnp.int8
        assert s.dtype == jnp.float32
        assert s.shape == k.shape[:-1] + (1,)  # [B, slots, KV, 1]


def _teacher_forced_ppl(model, params, tokens):
    """Perplexity of ``tokens`` decoded one position at a time through
    the model's KV cache — every cache slot is written and re-read the
    way real decode does it."""
    B, T = tokens.shape
    cache = zero_cache(model, params, tokens[:, :1])
    total = jnp.zeros((B,), jnp.float32)
    for t in range(T - 1):
        pos = jnp.full((B, 1), t, jnp.int32)
        out, mutated = model.apply(
            {"params": params, "cache": cache},
            {"tokens": tokens[:, t:t + 1], "positions": pos},
            decode=True, mutable=["cache"],
        )
        cache = mutated["cache"]
        logp = jax.nn.log_softmax(out["logits"][:, -1].astype(jnp.float32))
        total = total - logp[jnp.arange(B), tokens[:, t + 1]]
    return float(jnp.exp(jnp.mean(total / (T - 1))))


def test_int8_kv_rolling_long_prompt_perplexity_tolerance(devices):
    """Rolling cache, sequence far past the window: every slot gets
    overwritten repeatedly and every read dequantizes — teacher-forced
    perplexity must stay within 5% (relative) of the bf16 cache."""
    cfg = _cfg(
        "gpt2", max_seq=256, attention_window=16,
        decode_rolling_cache=True, decode_rolling_slack=8,
    )
    model = TransformerLM(cfg)
    model8 = TransformerLM(dataclasses.replace(cfg, kv_cache_int8=True))
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, 64, size=(2, 48)), jnp.int32
    )
    params = _params(model, tokens[:, :8])
    ppl = _teacher_forced_ppl(model, params, tokens)
    ppl8 = _teacher_forced_ppl(model8, params, tokens)
    assert abs(ppl8 - ppl) / ppl < 0.05, (ppl, ppl8)


def test_int8_kv_rolling_generate_runs_past_window(devices):
    """End-to-end rolling generate with an int8 cache: a prompt longer
    than the window decodes, emits in-vocab tokens, and matches the
    bf16-cache tokens on this seed."""
    cfg = _cfg(
        "gpt2", max_seq=256, attention_window=32,
        decode_rolling_cache=True, decode_rolling_slack=16,
    )
    model = TransformerLM(cfg)
    model8 = TransformerLM(dataclasses.replace(cfg, kv_cache_int8=True))
    prompt = jnp.asarray(
        np.random.default_rng(3).integers(0, 64, size=(2, 70)), jnp.int32
    )
    params = _params(model, prompt[:, :8])
    want = generate(model, params, prompt, max_new_tokens=20,
                    temperature=0.0)
    got = generate(model8, params, prompt, max_new_tokens=20,
                   temperature=0.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int8_kv_beam_search_cached_matches(devices):
    """Beam search reorders cache rows each step; the scale leaves must
    travel with their payload (same src_beam gather) or scores drift."""
    cfg = _cfg("gpt2")
    model = TransformerLM(cfg)
    model8 = TransformerLM(dataclasses.replace(cfg, kv_cache_int8=True))
    prompt = jnp.asarray(
        np.random.default_rng(4).integers(0, 64, size=(1, 6)), jnp.int32
    )
    params = _params(model, prompt, seed=2)
    want = beam_search_cached(model, params, prompt, 8, 63, beam_size=3)
    got = beam_search_cached(model8, params, prompt, 8, 63, beam_size=3)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))


def test_int8_kv_continuous_batcher_mid_admit_matches(devices):
    """The batcher with kv_cache_int8=True must reproduce the bf16
    batcher's tokens row for row — including a row admitted mid-batch,
    whose prefill scatters int8 pages + scales into a live cache."""
    cfg = _cfg("gpt2")
    model = TransformerLM(cfg)
    prompt0 = jnp.asarray(
        np.random.default_rng(5).integers(0, 64, size=(2, 5)), jnp.int32
    )
    params = _params(model, prompt0)
    admit_prompt = jnp.asarray(
        np.random.default_rng(6).integers(0, 64, size=(1, 4)), jnp.int32
    )

    def run(**kw):
        bat = ContinuousBatcher(model, model, params, params,
                                total_len=20, n_draft=3, **kw)
        bat.start(prompt0)
        for _ in range(3):
            bat.step()
        bat.admit(0, admit_prompt, preempt=True)
        for _ in range(3):
            bat.step()
        return [bat.row_tokens(r) for r in range(2)]

    base = run()
    quant = run(kv_cache_int8=True)
    for (t0, n0), (t1, n1) in zip(base, quant):
        assert n0 == n1
        np.testing.assert_array_equal(
            np.asarray(t0)[:n0], np.asarray(t1)[:n1]
        )


def test_set_kv_cache_int8_rejects_live_batch(devices):
    """Flipping the cache layout mid-flight would discard every row's
    KV state — the batcher must refuse after start()."""
    cfg = _cfg("gpt2")
    model = TransformerLM(cfg)
    prompt = jnp.asarray(
        np.random.default_rng(7).integers(0, 64, size=(1, 5)), jnp.int32
    )
    params = _params(model, prompt)
    bat = ContinuousBatcher(model, model, params, params,
                            total_len=16, n_draft=2)
    bat.set_kv_cache_int8(True)  # before start: fine
    assert bat._model.config.kv_cache_int8
    assert bat._draft_model.config.kv_cache_int8
    bat.start(prompt)
    with pytest.raises(ValueError, match="after start"):
        bat.set_kv_cache_int8(False)


def test_serving_loop_kv_cache_int8_knob(devices):
    """ServingLoop(kv_cache_int8=True) applies the layout to the initial
    batcher AND to a factory rebuild — recovery must not silently drop
    quantization."""
    from rocket_tpu.serve import ServingLoop

    cfg = _cfg("gpt2")
    model = TransformerLM(cfg)
    prompt = jnp.asarray(
        np.random.default_rng(8).integers(0, 64, size=(1, 5)), jnp.int32
    )
    params = _params(model, prompt)

    def factory():
        return ContinuousBatcher(model, model, params, params,
                                 total_len=12, n_draft=2)

    loop = ServingLoop(factory, max_batch=1, kv_cache_int8=True)
    try:
        assert loop._bat._model.config.kv_cache_int8
        rebuilt = loop._build_batcher()
        assert rebuilt._model.config.kv_cache_int8
    finally:
        loop.close()


@pytest.mark.parametrize("cache", ["bf16", "int8"])
def test_gqa_per_row_decode_matches_full_forward(devices, cache):
    """GQA (4 heads over 2 KV heads) through the per-row cache: rows sit
    at different frontiers, chunks of 3, 2 and 1 tokens, and every
    position's logits must match the full-sequence forward — the grouped
    contraction over the whole cache, its per-row causal mask included."""
    cfg = _cfg("llama", decode_per_row=True)
    full = TransformerLM(cfg)
    model = TransformerLM(
        dataclasses.replace(cfg, kv_cache_int8=cache == "int8")
    )
    L = 12
    toks = jnp.asarray(
        np.random.default_rng(5).integers(0, 64, size=(2, L)), jnp.int32
    )
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), _params(full, toks)
    )
    want = np.asarray(
        full.apply({"params": params}, {"tokens": toks})["logits"],
        np.float32,
    )
    state = {"cache": zero_cache(model, params, toks[:, :1])}
    if cache == "bf16":
        assert all(
            leaf.dtype == jnp.bfloat16
            for leaf in jax.tree_util.tree_leaves(state["cache"])
            if leaf.ndim == 4
        )
    # share of the position's logit range: bf16 differs by summation order
    # alone (0 on the CPU), int8 K/V add their rounding (0.013 here); a
    # wrong head grouping reads above 0.5
    tol = 0.01 if cache == "bf16" else 0.05

    def feed(starts, S):
        """Each row r decodes toks[r, starts[r] : starts[r] + S]."""
        pos = jnp.asarray(starts, jnp.int32)[:, None] + jnp.arange(S)
        out, mut = model.apply(
            {"params": params, "cache": state["cache"]},
            {"tokens": jnp.take_along_axis(toks, pos, axis=1),
             "positions": pos},
            decode=True, mutable=["cache"],
        )
        state["cache"] = mut["cache"]
        got = np.asarray(out["logits"], np.float32)
        for r, s in enumerate(starts):
            np.testing.assert_allclose(
                got[r], want[r, s:s + S], atol=tol * np.ptp(want[r, s:s + S]),
                err_msg=f"row {r} positions {s}..{s + S - 1}",
            )

    feed([0, 0], 3)
    feed([3, 1], 2)       # row 1 falls behind: it rewrites slots 1-2 in place
    for i in range(L - 5):
        feed([5 + i, 3 + i], 1)
