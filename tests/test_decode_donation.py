"""The round state is donated: ``_spec_round``, ``_spec_admit``,
``_spec_import_row``, ``_mtp_round`` and ``_mtp_admit`` write their caches
in place, and nothing that outlives a call holds a leaf of the state it took.

Two layers, at toy size on the CPU (whose backend really donates: a donated
array ``is_deleted()`` afterwards and a later read raises):

- the compiled programs: every leaf of ``state`` is donated and nothing
  else is, every cache leaf is aliased to its successor, and JAX finds a
  use for every donated buffer (no "not usable" warning);
- ``ContinuousBatcher``: the previous state's caches are gone after
  ``step()``, ``admit()`` and ``admit_prefilled()`` while the parameters
  and the batcher's key live; a ``KVHandoff`` taken before survives the
  rounds after; the served tokens are what they were.

A leaf read through ``np.asarray`` is a zero-copy view here and silently
keeps that leaf from being donated, so the tests look at a leaf's
``is_deleted()`` only where they have not read it that way.
"""

import importlib
import warnings

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.archs import keye_moe as select_family
from benchmark.archs import pangu_moe as family
from rocket_tpu.models.generate import (ContinuousBatcher, export_kv_row,
                                        speculative_generate_batched)
from rocket_tpu.models.transformer import TransformerConfig, TransformerLM

# the module, not the function of that name the package re-exports
generate_mod = importlib.import_module("rocket_tpu.models.generate")

# a donated buffer that finds no output of its shape is a fault here
pytestmark = pytest.mark.filterwarnings(
    "error:Some donated buffers were not usable")

B, P, TOTAL, NDRAFT = 3, 8, 24, 4
VOCAB, MAX_SEQ = 97, 48

# tests/test_latent_moe.py's toy widths of the latent model
ARCH = dict(
    kind="target", hidden=32, layers=3, first_dense=1, heads=4, q_rank=12,
    kv_rank=8, nope=8, rope=4, v_dim=6, ffn=40, expert_ffn=16, router=16,
    held=4, held_start=8, top_k=4, shared=1, norm_topk=True, route_scale=2.5,
    mtp_layers=1, eps=1e-5, rope_theta=25600000.0, vocab=VOCAB,
    vocab_padded=VOCAB, max_pos=MAX_SEQ)


def _lm(seed, hidden=32, heads=4, max_seq=64):
    cfg = TransformerConfig(vocab_size=64, hidden=hidden, n_layers=2,
                            n_heads=heads, max_seq=max_seq)
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(seed),
        {"tokens": np.zeros((1, P), np.int32),
         "positions": np.zeros((1, P), np.int32)})["params"]
    return model, params


def _seeded(tree, seed):
    leaves, treedef = jax.tree_util.tree_flatten(nn.meta.unbox(tree))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return treedef.unflatten([
        0.3 * jax.random.normal(k, leaf.shape, jnp.float32)
        + (1.0 if leaf.ndim == 1 else 0.0) for k, leaf in zip(keys, leaves)])


@pytest.fixture(scope="module")
def dense():
    """Two language models of one structure and other weights."""
    model, params = _lm(1)
    draft, draft_params = _lm(7)
    return model, draft, params, draft_params


@pytest.fixture
def kernel(decode_kernel_here):
    """Heads of 128 and the backend's refusal taken out: ``_decode_attend``
    takes the decode kernel (interpret mode here).  A ``max_seq`` of its
    own, so that no program traced without the patch is found in its place."""
    model, params = _lm(1, hidden=256, heads=2, max_seq=56)
    draft, draft_params = _lm(7, hidden=256, heads=2, max_seq=56)
    return model, draft, params, draft_params


@pytest.fixture(scope="module")
def latent():
    """The toy latent model and its ``MTPDraft``."""
    tokens = {"tokens": jnp.zeros((1, 4), jnp.int32)}
    model = family.program(ARCH, max_seq=MAX_SEQ)
    params = _seeded(model.init(jax.random.PRNGKey(0), tokens)["params"], 1)
    draft = family.program(family.draft(ARCH, {}), max_seq=MAX_SEQ)
    draft_params = _seeded(
        draft.init(jax.random.PRNGKey(0), tokens)["params"], 2)
    return model, draft, params, draft_params


# tests/test_select_attention.py's toy widths of the selecting model
SELECT_ARCH = dict(
    hidden=32, layers=2, heads=4, kv_heads=2, head_dim=16, expert_ffn=16,
    router=16, held=4, held_start=8, top_k=4, norm_topk=True, index_heads=2,
    index_dim=8, select_top_k=4, chunk=4, mrope=(2, 3, 3), eps=1e-6,
    rope_theta=10000.0, vocab=VOCAB, vocab_padded=VOCAB, max_pos=MAX_SEQ)


@pytest.fixture(scope="module")
def select():
    """The toy model whose attention chooses its keys (an indexer's cache
    leaf beside K and V, device counters in the round state) and a draft
    of the same kind."""
    tokens = {"tokens": jnp.zeros((1, 4), jnp.int32)}
    model = select_family.program(SELECT_ARCH, max_seq=MAX_SEQ)
    params = _seeded(model.init(jax.random.PRNGKey(0), tokens)["params"], 1)
    draft = select_family.program(
        select_family.draft(SELECT_ARCH, {"draft_layers": 1}),
        max_seq=MAX_SEQ)
    draft_params = _seeded(
        draft.init(jax.random.PRNGKey(0), tokens)["params"], 2)
    return model, draft, params, draft_params


def _batcher(models, kind):
    if kind in ("latent", "select"):
        return ContinuousBatcher(*models, total_len=30, n_draft=1)
    return ContinuousBatcher(*models, total_len=TOTAL, n_draft=NDRAFT,
                             eos_token=None)


def _prompts(kind, seed=13, rows=8):
    high = VOCAB if kind in ("latent", "select") else 64
    return np.random.default_rng(seed).integers(
        1, high, size=(rows, P)).astype(np.int32)


def _started(models, kind):
    """A batcher with ``B`` rows in service; the first two are free."""
    bat = _batcher(models, kind)
    bat.start(_prompts(kind)[:B])
    for row in (0, 1):
        bat.retire(row)
    return bat


def _payload(state):
    return [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(
        (state[3], state[4]))[0] if generate_mod._is_cache_payload(path, leaf)]


def _nbytes(tree):
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree))


# -- the compiled programs ----------------------------------------------------


def _entry(name, bat, kind):
    """``(jitted entry, its arguments as the batcher passes them, keywords,
    where ``state`` stands among the arguments that are not static)``."""
    modules = (bat._model, bat._draft_model, bat._params, bat._draft_params)
    row = jnp.int32(0)
    prompt_row = jnp.asarray(_prompts(kind)[5][None, :])
    if name == "_spec_round":
        return (generate_mod._spec_round,
                modules + (bat.state, bat._temperature),
                dict(n_draft=bat.n_draft, **bat._kw()), 2)
    if name == "_spec_admit":
        return (generate_mod._spec_admit,
                modules + (bat.state, row, prompt_row,
                           jax.random.PRNGKey(3), bat._temperature),
                bat._kw(), 2)
    if name == "_spec_import_row":
        # a selecting model's batcher refuses handoffs; the row of a fresh
        # batch-1 prefill is what ``prefill_handoff`` would have exported
        h = bat.prefill_handoff(prompt_row) if kind != "select" \
            else export_kv_row(generate_mod._spec_prefill(
                *modules, prompt_row, bat._rng, bat._temperature,
                max_new_tokens=bat.total_len - P, **bat._kw()), 0)
        return (generate_mod._spec_import_row,
                (bat.state, row, h.buf, h.n_tok, h.done, h.cache_t,
                 h.cache_d), {}, 0)
    if name == "_mtp_round":
        return (generate_mod._mtp_round, modules + (bat.state,),
                dict(eos_token=bat.eos_token), 2)
    assert name == "_mtp_admit"
    return (generate_mod._mtp_admit, modules + (bat.state, row, prompt_row),
            dict(eos_token=bat.eos_token), 2)


@pytest.mark.parametrize("name,kind", [
    ("_spec_round", "dense"), ("_spec_admit", "dense"),
    ("_spec_import_row", "dense"), ("_mtp_round", "latent"),
    ("_mtp_admit", "latent"),
    # ISSUE 31: the round whose attention is the decode kernel
    ("_spec_round", "kernel"),
    # ISSUE 33: the indexer's cache leaf and the round's device counters
    ("_spec_round", "select"), ("_spec_admit", "select"),
    ("_spec_import_row", "select"),
])
def test_the_entry_donates_its_state_and_aliases_every_cache_leaf(
        request, name, kind):
    from rocket_tpu.observe import trace

    bat = _started(request.getfixturevalue(kind), kind)
    fn, args, kw, at = _entry(name, bat, kind)
    tracer = trace.arm(1024)
    tracer.clear()
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            compiled = fn.lower(*args, **kw).compile()
        chose = {e[1] for e in tracer.events()
                 if e[1].startswith("attention/decode/")}
    finally:
        trace.disarm()
    if kind == "kernel":     # nothing traced this configuration before
        assert chose == {"attention/decode/kernel"}
    assert not [str(w.message) for w in caught
                if "donated" in str(w.message)]

    # ``state``, the whole of it, and nothing else: never the parameter
    # trees, the prompt row or a handoff's caches
    infos, kw_infos = compiled.args_info
    for i, info in enumerate(infos):
        donated = {leaf.donated for leaf in jax.tree_util.tree_leaves(info)}
        assert donated == ({True} if i == at else {False}), (name, i)
    assert not any(leaf.donated
                   for leaf in jax.tree_util.tree_leaves(kw_infos))

    # Every cache leaf is written where it was read.  What the state
    # holds beside its caches is less than its smallest cache leaf, so an
    # alias total of at least the caches' bytes leaves none of them out;
    # and it cannot pass the state's bytes, since nothing else is donated.
    state = bat.state
    payload = _payload(state)
    if kind == "select":      # a third leaf a layer, [rows, index_dim, slots]
        index = [p for p in payload
                 if p.shape[1:] == (SELECT_ARCH["index_dim"], MAX_SEQ)]
        assert len(index) == 3 and len(payload) == 9
    assert _nbytes(state) - _nbytes(payload) < min(p.nbytes for p in payload)
    aliased = compiled.memory_analysis().alias_size_in_bytes
    assert _nbytes(payload) <= aliased <= _nbytes(state), name


# -- through ContinuousBatcher -------------------------------------------------


def _live(tree):
    return not any(leaf.is_deleted()
                   for leaf in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("kind,call", [
    ("dense", "step"), ("dense", "admit"), ("dense", "admit_prefilled"),
    ("latent", "step"), ("latent", "admit"),
    ("select", "step"), ("select", "admit"),
])
def test_the_previous_state_is_gone_and_the_rest_lives(request, kind, call):
    bat = _started(request.getfixturevalue(kind), kind)
    prompts = _prompts(kind)
    handoff = bat.prefill_handoff(prompts[4]) \
        if call == "admit_prefilled" else None
    before = bat.state
    if call == "step":
        bat.step()
    elif call == "admit":
        bat.admit(0, prompts[3])
    else:
        bat.admit_prefilled(0, handoff)
    assert all(leaf.is_deleted() for leaf in _payload(before))
    assert _live((bat._params, bat._draft_params, bat._rng))
    if handoff is not None:       # an import reads the handoff, no more
        assert _live(handoff._tree())
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(_payload(before)[0])
    # and the batcher goes on
    bat.admit(1, prompts[6])
    n_tok, done = bat.step()
    assert n_tok[1] > P + 1 and not done[1]
    assert _live(bat.state)


def _decode_row(bat, row):
    while not bool(np.asarray(bat.state[2])[row]):
        bat.step()
    tokens, n = bat.row_tokens(row)
    return np.asarray(tokens)[:n]


@pytest.mark.parametrize("source", ["export_kv_row", "prefill_handoff"])
def test_a_handoff_outlives_the_rounds_after_it(dense, source):
    """The prefix store's oracle (``tests/test_kvstore.py``) with two
    donating rounds between export and import: the imported row decodes
    bit-equal to a local ``admit()`` of the same prompt, twice over."""
    prompts = _prompts("dense")
    bat = _started(dense, "dense")
    if source == "export_kv_row":
        bat.admit(0, prompts[5])
        handoff = export_kv_row(bat.state, 0)   # out of a live batch
    else:
        handoff = bat.prefill_handoff(prompts[5])
    for _ in range(2):
        bat.step()
    assert _live(handoff._tree())

    local = _started(dense, "dense")
    local.admit(0, prompts[5])
    want = _decode_row(local, 0)
    assert len(want) == TOTAL
    for _ in range(2):            # an import does not use the handoff up
        bat.admit_prefilled(1, handoff, preempt=True)
        np.testing.assert_array_equal(_decode_row(bat, 1), want)
    fresh = _started(dense, "dense")
    fresh.admit_prefilled(0, handoff)
    np.testing.assert_array_equal(_decode_row(fresh, 0), want)


def test_stepping_to_the_end_is_the_one_dispatch_output(dense):
    model, draft, params, draft_params = dense
    prompts = _prompts("dense")[:B]
    want = np.asarray(speculative_generate_batched(
        model, params, draft, draft_params, prompts,
        max_new_tokens=TOTAL - P, n_draft=NDRAFT))
    bat = _batcher(dense, "dense")
    bat.start(prompts)
    while not bat.all_done:
        bat.step()
    for row in range(B):
        np.testing.assert_array_equal(bat.row_tokens(row)[0], want[row])


def test_a_hidden_state_draft_still_serves_plain_greedy(latent):
    model, _, params, _ = latent
    prompts = _prompts("latent")
    total = 30
    want = [np.asarray(generate_mod.generate(
        model, params, jnp.asarray(p[None]), total - P,
        temperature=0.0))[0] for p in prompts[:4]]
    bat = _batcher(latent, "latent")
    bat.start(prompts[:2])
    waiting, in_row, got = [2, 3], {0: 0, 1: 1}, {}
    while in_row:
        _, done = bat.step()
        for row in [r for r in in_row if done[r]]:
            tokens, n = bat.row_tokens(row)
            got[in_row.pop(row)] = np.asarray(tokens)[:n]
            if waiting:            # admitted between rounds, mid-batch
                bat.admit(row, prompts[waiting[0]])
                in_row[row] = waiting.pop(0)
    for i, tokens in enumerate(want):
        np.testing.assert_array_equal(got[i], tokens)
