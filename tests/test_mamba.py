"""State-space layers (``models/mamba.py``, ``ops/ssm.py``) beside attention
in one stack, at a toy size with the pattern ``[mamba, mamba, attention,
mamba]`` and the Granite scalars: the program against the plain reference
(``benchmark/reference/granite_hybrid.py``, float32, one token at a time),
a round's state kept to the tokens it accepted, admission into a used row,
the kernel against the recurrence, and what is refused by name.

Tolerances: program and reference are both float32 here; they differ by the
order of their sums (the chunked scan against the token-by-token one, XLA's
matmuls against ``precision=HIGHEST``), a few units in the sixth digit of
logits of order one: ``TOL`` is 1e-4.  The kernel's products with the state
are two bfloat16 passes (``ops/ssm.py:_split``), good to about 2**-17 of
the state: ``KERNEL_TOL`` is 1e-4 of the largest number compared."""

import dataclasses
import hashlib
import importlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.archs import granite_hybrid as family
from benchmark.reference import granite_hybrid as reference
from rocket_tpu.models.generate import ContinuousBatcher
from rocket_tpu.models.transformer import MambaConfig, TransformerConfig
from rocket_tpu.observe import trace
from rocket_tpu.ops import ssm

generate_mod = importlib.import_module("rocket_tpu.models.generate")

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "test_benchmark", "granite_toy", "configs",
                   "toy-granite.json")
TOL, KERNEL_TOL = 1e-4, 1e-4
TOTAL = 40


def toy_arch(**changes):
    with open(TOY) as fh:
        return dict(family.normalise(json.load(fh)), **changes)


def named_leaves(arch, seed, std=0.3, long_memory=False, prefix=""):
    """Every leaf of ``arch`` by the benchmark's names, normal(0, ``std``)
    (norm scales 1 + that): activations of order one at the toy's widths.
    ``long_memory``: ``A`` and ``Δ`` in Mamba-2's published init ranges
    (``-A`` in [1, 16], ``Δ`` log-uniform in [0.001, 0.1]) and the ``Δ``
    columns of ``W_in`` small, so a state remembers hundreds of tokens."""
    key = weights.base_key(seed)
    shapes = family.leaf_shapes(arch, prefix)
    out = {}
    for group, members in weights.groups(shapes, prefix).items():
        out.update(weights.make_group(key, group, members))
    out = {k: (1.0 + (v - 1.0) * std / 0.02 if k.endswith(".scale")
               else v * std / 0.02) for k, v in out.items()}
    if long_memory:
        rng = np.random.default_rng(seed)
        E = arch["ssm_heads"] * arch["ssm_head_dim"]
        W = E + 2 * arch["d_state"]
        for name in list(out):
            if name.endswith(".A_log"):
                out[name] = jnp.log(jnp.asarray(
                    rng.uniform(1.0, 16.0, out[name].shape), jnp.float32))
            elif name.endswith(".dt_bias"):
                dt = np.exp(rng.uniform(math.log(1e-3), math.log(1e-1),
                                        out[name].shape))
                out[name] = jnp.asarray(dt + np.log(-np.expm1(-dt)),
                                        jnp.float32)
            elif name.endswith(".in.w"):
                out[name] = out[name].at[:, E + W:].multiply(0.01)
    return out


def build(arch, leaves, prefix="", dtype=jnp.float32):
    """The program's model and its tree from ``leaves``."""
    model = family.program(arch, max_seq=TOTAL + 8)
    abstract = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        {"tokens": jnp.zeros((1, 8), jnp.int32)})["params"]
    import flax.linen as nn

    params = jax.tree_util.tree_map_with_path(
        lambda p, a: leaves[prefix + family.leaf_name(p)].astype(dtype),
        nn.meta.unbox(abstract))
    return model, params


def getter(leaves, prefix=""):
    """The reference's ``get(group)`` over the leaves of one model (the
    draft's under their names without ``prefix``)."""
    short = {k[len(prefix):]: v for k, v in leaves.items()
             if k.startswith(prefix)}
    groups = weights.groups({k: v.shape for k, v in short.items()})
    return lambda g: {k: short[k] for k in groups[g]}


def rows_of(seed, lengths, vocab=256):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]


@pytest.fixture(scope="module")
def pair():
    """Target and draft (``[mamba, attention]``, a table of its own) with
    their leaves by name.  The toy's embeddings and sublayers are at one:
    at the published 12 and 0.22 a random model's tied head mostly repeats
    the token it is given, and every draft would agree with it."""
    arch = toy_arch()
    d_arch = family.draft(arch, {"draft_layer_types": ["mamba", "attention"]})
    t_leaves = named_leaves(arch, 1)
    d_leaves = named_leaves(d_arch, 1, prefix="draft.")
    target = build(arch, t_leaves)
    draft = build(d_arch, d_leaves, prefix="draft.")
    return arch, d_arch, t_leaves, d_leaves, target, draft


# -- (a) the three forms of the scan, and the kernel --------------------------


def _scan_operands(R, L, H=4, P=8, N=128, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (R, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (R, L, H)))
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=-1.0, maxval=2.0))
    B = jax.random.normal(k[3], (R, L, N)).astype(jnp.bfloat16)
    C = jax.random.normal(k[4], (R, L, N)).astype(jnp.bfloat16)
    S0 = jax.random.normal(k[5], (R, H, P, N))
    return x, dt, A, B, C, S0


@pytest.mark.parametrize("S", range(1, 9))
def test_the_kernel_equals_the_recurrence(S):
    """Row ``r`` of the pass holds ``r`` pending inputs (0 to ``S - 1``)
    before its ``S`` new tokens and commits the first of them; the steps
    past ``r + S`` do not count.  The kernel, in interpret mode, gives the
    recurrence's outputs and kept state; a pass that commits nothing keeps
    the state it was given, bit for bit."""
    L = ssm.MAX_CHUNK - 1 + S
    x, dt, A, B, C, S0 = _scan_operands(S, L, seed=S)
    n = jnp.arange(S)
    dt = jnp.where(jnp.arange(L)[None, :, None] < (n + S)[:, None, None],
                   dt, 0.0)
    commit_at = n
    want_y, want_s = ssm.step_scan(S0, x, dt, A, B, C, commit_at)
    got_y, got_s = ssm.ssm_decode(S0, x, dt, A, B, C, commit_at, S=S)
    live = (jnp.arange(L)[None, :] < (n + S)[:, None])[..., None, None]
    scale = float(jnp.max(jnp.abs(want_y)))
    assert float(jnp.max(jnp.where(live, jnp.abs(got_y - want_y), 0.0))) \
        < KERNEL_TOL * scale
    assert float(jnp.max(jnp.abs(got_s - want_s))) \
        < KERNEL_TOL * float(jnp.max(jnp.abs(want_s)))
    if S == 2:
        y0, same = ssm.ssm_decode(S0, x, dt, A, B, C, jnp.full_like(n, -1),
                                  S=S)
        np.testing.assert_array_equal(same, S0)
        np.testing.assert_array_equal(y0, got_y)


def test_the_chunked_scan_equals_the_recurrence():
    """From a state, over 37 steps in chunks of 8 (a ragged last one), with
    steps that do not count in the middle."""
    x, dt, A, B, C, S0 = _scan_operands(2, 37, seed=11)
    dt = dt.at[1, 10:20].set(0.0)
    got_y, got_s = ssm.chunked_scan(x, dt, A, B, C, chunk=8, state=S0)
    want_y, want_s = ssm.step_scan(S0, x, dt, A, B, C, jnp.array([36, 36]))
    np.testing.assert_allclose(got_y, want_y, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(got_s, want_s, rtol=1e-4, atol=1e-4)


# -- (b) the program against the reference's full forward --------------------


@pytest.mark.parametrize("P", [1, 255, 256, 257, 600])
def test_prefill_then_rounds_match_the_reference(P):
    """A prompt of ``P`` tokens (chunks of 256) through the decode path, then
    passes of two tokens that commit the first, started one or two tokens on
    (one or both of the pair accepted): every position's logits are the
    reference's full forward's."""
    arch = toy_arch(chunk=256, embedding_multiplier=12.0,
                    residual_multiplier=0.22)
    leaves = named_leaves(arch, 3)
    model = family.program(arch, max_seq=P + 16)
    import flax.linen as nn

    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                              {"tokens": jnp.zeros((1, 8), jnp.int32)})
    params = jax.tree_util.tree_map_with_path(
        lambda p, a: leaves[family.leaf_name(p)],
        nn.meta.unbox(abstract["params"]))
    row = rows_of(P, [P + 9])[0]
    want = reference.full_logits(arch, "f32", getter(leaves), row)
    model = model.clone(config=dataclasses.replace(model.config,
                                                   decode_per_row=True))
    cache = generate_mod.zero_cache(model, params, jnp.asarray(row[None, :P]))
    out, mut = model.apply(
        {"params": params, "cache": cache},
        {"tokens": jnp.asarray(row[None, :P]),
         "positions": jnp.arange(P, dtype=jnp.int32)[None]},
        decode=True, mutable=["cache"])
    np.testing.assert_allclose(out["logits"][0], want[:P], atol=TOL)
    cache, p0 = mut["cache"], P
    for step in (1, 2, 1, 2, 2):
        pos = jnp.asarray([[p0, p0 + 1]], jnp.int32)
        out, mut = model.apply(
            {"params": params, "cache": cache},
            {"tokens": jnp.asarray(row[None, p0:p0 + 2]), "positions": pos},
            decode=True, mutable=["cache"], commit=1)
        np.testing.assert_allclose(out["logits"][0], want[p0:p0 + 2],
                                   atol=TOL)
        cache, p0 = mut["cache"], p0 + step


# -- (c) a round's state follows what it accepted ----------------------------


def _check_states(arch, leaves, cache, tokens, n_tok, rows, prefix=""):
    """Each row's committed ``ssm_state`` is the reference's state after the
    row's tokens before ``state_pos``; its ``conv_state`` holds the raw
    ``xBC`` of the three tokens before that and then of the tokens held
    pending, up to the row's frontier: the state after exactly the row's
    own tokens, the pending ones applied."""
    get = getter(leaves, prefix)
    for r in rows:
        pos = {i: int(cache[f"block_{i}"]["mamba"]["state_pos"][r])
               for i, k in enumerate(arch["layer_types"]) if k == "mamba"}
        assert len(set(pos.values())) == 1
        p = pos[next(iter(pos))]
        room = cache[f"block_{next(iter(pos))}"]["mamba"]["dt_state"].shape[1]
        assert p <= n_tok[r] - 1 <= p + room
        ref = reference.states_after(arch, get, tokens[r][:n_tok[r] - 1])
        ref_at = reference.states_after(arch, get, tokens[r][:p])
        for i in pos:
            got = cache[f"block_{i}"]["mamba"]
            np.testing.assert_allclose(got["ssm_state"][r], ref_at[i][0],
                                       atol=TOL, rtol=1e-3)
            raw = np.asarray(ref[i][1])
            lo = max(0, p - 3)
            window = np.asarray(got["conv_state"][r])
            np.testing.assert_allclose(window[3 - (p - lo):3], raw[lo:p],
                                       atol=TOL, rtol=TOL)
            held = n_tok[r] - 1 - p
            np.testing.assert_allclose(window[3:3 + held], raw[p:p + held],
                                       atol=TOL, rtol=TOL)


def _draft_for(kind, pair):
    arch, d_arch, t_leaves, d_leaves, target, draft = pair
    if kind == "same":
        return target, arch, t_leaves, ""
    if kind == "perturbed":
        leaves = {k: v + 0.02 * jnp.sin(jnp.arange(v.size).reshape(v.shape))
                  for k, v in t_leaves.items()}
        return build(arch, leaves), arch, leaves, ""
    return draft, d_arch, d_leaves, "draft."


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("kind", ["same", "perturbed", "random"])
def test_a_round_keeps_the_state_of_the_tokens_it_accepted(pair, kind,
                                                           sampled):
    """A draft equal to the target (every draft accepted), a perturbed one
    (some) and one of its own (none), ``n_draft`` 3, greedy and sampled:
    after every round both models' states are those of each row's own
    tokens; greedy rows serve the reference's arg-maxes; a row admitted
    mid-batch starts from its own prompt alone."""
    arch, _, t_leaves, _, (model, params), _ = pair
    (d_model, d_params), d_arch, d_leaves, d_prefix = _draft_for(kind, pair)
    kw = dict(sampled=True, temperature=0.7) if sampled else {}
    bat = ContinuousBatcher(model, d_model, params, d_params,
                            total_len=TOTAL, n_draft=3,
                            rng=jax.random.PRNGKey(5), **kw)
    prompts = rows_of(7, [6, 6, 6])
    bat.start(np.stack(prompts))
    # room for the round's three unconfirmed drafts, and no more
    assert bat.state[3]["block_0"]["mamba"]["dt_state"].shape[1] == 3
    late = rows_of(8, [11])[0]
    for rnd in range(5):
        n_tok, done = bat.step()
        if rnd == 1:
            bat.retire(2)
            bat.admit(2, late)
            n_tok = np.asarray(bat.state[1])
        tokens = np.asarray(bat.state[0])
        _check_states(arch, t_leaves, bat.state[3], tokens, n_tok, range(3))
        _check_states(d_arch, d_leaves, bat.state[4], tokens, n_tok,
                      range(3), d_prefix)
    stats = bat.stats()
    accepted, drafted = (int(np.sum(stats[k])) for k in ("accepted",
                                                        "drafted"))
    if not sampled:
        assert {"same": accepted == drafted > 0,
                "perturbed": 0 < accepted < drafted,
                "random": accepted == 0}[kind]
        get = getter(t_leaves)
        for r in range(3):
            row = tokens[r][:n_tok[r]]
            first = len(late) if r == 2 else 6
            logits = reference.full_logits(arch, "f32", get, row)
            np.testing.assert_array_equal(
                np.argmax(logits[first - 1:-1], axis=-1), row[first:])


def test_a_row_that_stops_keeps_its_state(pair):
    """A row that emits the end token goes idle: later rounds leave its
    state, its window and its frontier as they were."""
    arch, _, t_leaves, _, (model, params), (d_model, d_params) = pair
    prompts = rows_of(9, [5, 5])
    ref = ContinuousBatcher(model, d_model, params, d_params,
                            total_len=TOTAL, n_draft=2)
    ref.start(np.stack(prompts))
    ref.step()
    eos = int(np.asarray(ref.state[0])[0, 6])
    bat = ContinuousBatcher(model, d_model, params, d_params,
                            total_len=TOTAL, n_draft=2, eos_token=eos)
    bat.start(np.stack(prompts))
    kept = None
    for _ in range(6):
        _, done = bat.step()
        if done[0] and kept is None:
            kept = jax.tree_util.tree_map(np.asarray, bat.state[3])
    assert kept is not None
    now = jax.tree_util.tree_map(np.asarray, bat.state[3])
    for i, k in enumerate(arch["layer_types"]):
        if k == "mamba":
            for leaf in ("ssm_state", "conv_state", "dt_state", "state_pos"):
                np.testing.assert_array_equal(
                    now[f"block_{i}"]["mamba"][leaf][0],
                    kept[f"block_{i}"]["mamba"][leaf][0])


def test_admission_into_a_used_row_is_admission_into_a_fresh_one():
    """With states that remember hundreds of tokens, a prompt admitted into
    a row a long request used serves what it serves in a fresh batch: the
    admission replaces the row's state and window whole."""
    arch = toy_arch()
    d_arch = family.draft(arch, {"draft_layer_types": ["mamba", "attention"]})
    t_leaves = named_leaves(arch, 21, long_memory=True)
    d_leaves = named_leaves(d_arch, 21, long_memory=True, prefix="draft.")
    model, params = build(arch, t_leaves)
    d_model, d_params = build(d_arch, d_leaves, prefix="draft.")
    old, new = rows_of(22, [30, 7])
    used = ContinuousBatcher(model, d_model, params, d_params,
                             total_len=TOTAL, n_draft=1)
    used.start(np.stack([old, old]))
    used.step()
    used.retire(1)
    used.admit(1, new)
    fresh = ContinuousBatcher(model, d_model, params, d_params,
                              total_len=TOTAL, n_draft=1)
    fresh.start(np.stack([new, new]))
    for r in range(3):
        used.step()
        fresh.step()
    np.testing.assert_array_equal(np.asarray(used.state[0])[1],
                                  np.asarray(fresh.state[0])[1])
    for cache in (3, 4):
        a, b = used.state[cache], fresh.state[cache]
        for i in a:
            if "mamba" in a[i]:
                for leaf in ("ssm_state", "conv_state", "state_pos"):
                    np.testing.assert_allclose(a[i]["mamba"][leaf][1],
                                               b[i]["mamba"][leaf][1],
                                               rtol=TOL, atol=TOL)


@pytest.fixture
def kernel_here(monkeypatch):
    """``ops.ssm`` with its refusal of a backend that is no TPU taken out:
    a pass the kernel would take on the chip takes it here, in interpret
    mode.  (Patching ``_on_tpu`` instead would ask for Mosaic.)"""
    real = ssm.why_not

    def why_not(state, B, S):
        reason = real(state, B, S)
        return None if reason == "backend" else reason

    monkeypatch.setattr(ssm, "why_not", why_not)


@pytest.fixture
def tracer():
    t = trace.arm(4096)
    t.clear()
    try:
        yield t
    finally:
        trace.disarm()


def _events(tracer, name):
    return [e[5] for e in tracer.events() if e[1] == name]


def test_the_round_takes_the_kernel_and_serves_the_same(kernel_here, tracer,
                                                       monkeypatch):
    """In bfloat16 with a state of 128 (what the kernel takes), the round's
    every ``mamba`` pass is the kernel, counted, and the batcher serves what
    it serves through the recurrence."""
    arch = toy_arch(d_state=128)
    d_arch = family.draft(arch, {"draft_layer_types": ["mamba", "attention"]})
    t_leaves = named_leaves(arch, 31)
    d_leaves = named_leaves(d_arch, 31, prefix="draft.")
    model, params = build(arch, t_leaves, dtype=jnp.bfloat16)
    d_model, d_params = build(d_arch, d_leaves, prefix="draft.",
                              dtype=jnp.bfloat16)
    prompts = np.stack(rows_of(32, [9, 9]))

    def serve():
        jax.clear_caches()          # the round is traced anew each time
        bat = ContinuousBatcher(model, d_model, params, d_params,
                                total_len=24, n_draft=1)
        bat.start(prompts)
        for _ in range(6):
            bat.step()
        return np.asarray(bat.state[0])

    with_kernel = serve()
    # the target's three layers (S 2), the draft chain's one step (S 1):
    # the chain is one scan, its body traced once
    assert sorted((e["S"], e["rows"]) for e in
                  _events(tracer, "ssm/decode/kernel")) == \
        [(1, 2), (2, 2), (2, 2), (2, 2)]
    assert not _events(tracer, "ssm/decode/fallback")
    assert 9 in {e["T"] for e in _events(tracer, "ssm/prefill")}
    monkeypatch.setattr(ssm, "why_not", lambda *a: "backend")
    np.testing.assert_array_equal(serve(), with_kernel)


# -- (d) what cannot run yet, by name ---------------------------------------------


def test_what_state_space_layers_cannot_run_with_is_refused_by_name(pair):
    import re

    from rocket_tpu.models.moe import ExpertsConfig
    from rocket_tpu.models.transformer import (MLAConfig, MTPDraft,
                                               SelectConfig)

    arch, _, _, _, (model, params), (d_model, d_params) = pair
    base = model.config
    for kw, name in (
            (dict(mla=MLAConfig(16, 16, 8, 8, 8)), "mla"),
            (dict(select=SelectConfig(index_heads=2, index_dim=8, top_k=4)),
             "select"),
            (dict(experts=ExpertsConfig(n_routed=4, top_k=2, expert_dim=8)),
             "experts"),
            (dict(n_experts=4), "n_experts"),
            (dict(kv_cache_int8=True), "kv_cache_int8"),
            (dict(decode_rolling_cache=True, attention_window=8),
             "decode_rolling_cache"),
            (dict(attention_window=8), "attention_window"),
            (dict(scan_layers=True), "scan_layers"),
            (dict(pipeline_microbatches=2), "pipeline_microbatches"),
            (dict(causal=False), "causal=False"),
            (dict(fused_qkv=True), "fused_qkv"),
            (dict(layer_types=("mamba",) * 3), "layer_types"),
            (dict(mamba=None), "mamba="),
            (dict(positions="alibi"), "positions")):
        with pytest.raises(ValueError, match=re.escape(name)):
            dataclasses.replace(base, **kw)
    with pytest.raises(ValueError, match="n_groups"):
        MambaConfig(n_groups=2)
    with pytest.raises(ValueError, match="pending=8"):
        MambaConfig(pending=8)

    bat = ContinuousBatcher(model, d_model, params, d_params,
                            total_len=TOTAL, n_draft=1)
    assert not bat.prefix_cache_ok
    prompt = np.ones(6, np.int32)
    with pytest.raises(ValueError, match="recurrent state"):
        bat.prefill_handoff(prompt)
    bat.start(prompt[None])
    with pytest.raises(ValueError, match="recurrent state"):
        bat.admit_prefilled(0, None)
    with pytest.raises(ValueError, match="recurrent state"):
        generate_mod.export_kv_row(bat.state, 0)
    bat.n_draft = 2             # deeper than the caches were sized for
    with pytest.raises(ValueError, match="n_draft=2"):
        bat.step()
    for beam in (generate_mod.beam_search, generate_mod.beam_search_cached):
        with pytest.raises(ValueError, match="mamba"):
            beam(model, params, jnp.ones((1, 4), jnp.int32), 4, 0,
                 beam_size=2)
    with pytest.raises(ValueError, match="mamba"):
        generate_mod.speculative_generate(model, params, d_model, d_params,
                                          jnp.ones((1, 4), jnp.int32), 4)
    with pytest.raises(ValueError, match="n_draft=8"):
        ContinuousBatcher(model, d_model, params, d_params, total_len=TOTAL,
                          n_draft=8)
    with pytest.raises(ValueError, match="kv_cache_int8"):
        ContinuousBatcher(model, d_model, params, d_params, total_len=TOTAL,
                          n_draft=1, kv_cache_int8=True)
    dense = TransformerConfig(vocab_size=256, hidden=32, n_layers=1,
                              n_heads=4, max_seq=TOTAL + 8)
    with pytest.raises(ValueError, match=re.escape("_mtp_*")):
        ContinuousBatcher(model, MTPDraft(dense), params, d_params,
                          total_len=TOTAL, n_draft=1)
    with pytest.raises(ValueError, match="MTPDraft"):
        MTPDraft(base).init(jax.random.PRNGKey(0),
                            {"tokens": jnp.zeros((1, 4), jnp.int32)})


# -- (e) the hybrid pair's round, pinned -------------------------------------------

# sha256[:16] of ``str(make_jaxpr(...))`` of the toy hybrid pair's round
# below (no kernel: the CPU's recurrence): a change that moves it has
# changed what a state-space model's round traces, and says so by bringing
# a new value.  The dense pair's round and admission are pinned in
# ``tests/test_select_attention.py``.
HYBRID_ROUND_JAXPR = "e357eac6534c9f66"


def test_the_hybrid_round_traces_what_it_traced(pair):
    """Seven entries of state (no counters: no experts, no selection), and
    the round's jaxpr letter for letter."""
    _, _, _, _, (model, params), (d_model, d_params) = pair
    per_row = lambda m: m.clone(config=dataclasses.replace(  # noqa: E731
        m.config, decode_per_row=True))
    model, d_model = per_row(model), per_row(d_model)
    kw = dict(eos_token=None, sampled=False, top_k=None, top_p=None)
    state = generate_mod._spec_prefill_impl(
        model, d_model, params, d_params, jnp.ones((2, 8), jnp.int32), None,
        0.0, max_new_tokens=30, **kw)
    assert len(state) == 7
    jaxpr = jax.make_jaxpr(lambda s: generate_mod._spec_round_impl(
        model, d_model, params, d_params, s, 0.0, n_draft=2, **kw))(state)
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16] \
        == HYBRID_ROUND_JAXPR
