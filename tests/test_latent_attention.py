"""The latent decode kernel (``ops/latent_attention.py``) in interpret mode
against ``dot_attention(v=None, v_width=)``, at toy sizes; the rule that
chooses it; and ``idle`` on its way from a round to ``LatentAttention``.

The kernel reads, for each row, only the cache blocks that row has
written: so every case plants stale non-zero rows past each frontier (what
a retired request leaves behind) and compares with ``dot_attention``, whose
causal mask hides them.  Times are the chip's to give (``benchmark/run.py``);
a CPU run proves results and counts.
"""

import importlib
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rocket_tpu.models.generate import ContinuousBatcher, HostReads
from rocket_tpu.models.moe import ExpertsConfig
from rocket_tpu.models.transformer import (MLAConfig, MTPDraft,
                                           TransformerConfig, TransformerLM)
from rocket_tpu.observe import trace
from rocket_tpu.ops import latent_attention as la
from rocket_tpu.ops.attention import dot_attention

# the module, not the function of that name the package re-exports
generate_mod = importlib.import_module("rocket_tpu.models.generate")

T, BLOCK, C, DR = 36, 16, 128, 64   # 36 slots in blocks of 16: the last is ragged
W, SCALE = C + DR, 0.11
# frontiers (q_offset + S): a single token, exactly a block, a block + 1,
# the whole slab, and one row the caller marks idle
LENGTHS = (1, BLOCK, BLOCK + 1, T, 9)
IDLE = jnp.asarray([False, False, False, False, True])


def _operands(S, H, dtype, seed=0, rows=len(LENGTHS), n_slots=T):
    kq, kc = jax.random.split(jax.random.PRNGKey(seed))
    q = jax.random.normal(kq, (rows, S, H, W), dtype)
    # every slot holds something, live or stale; values away from zero
    cache = 1.5 + jax.random.normal(kc, (rows, n_slots, W), dtype)
    return q, cache


def _dot(q, cache, off):
    return dot_attention(q, cache[:, :, None, :], v_width=C, causal=True,
                         q_offset=off, scale=SCALE)


def _kernel(q, cache, off, **kw):
    kw.setdefault("block_k", BLOCK)
    return la.latent_decode_attention(q, cache, off, v_width=C, scale=SCALE,
                                      **kw)


def _offsets(lengths, S):
    # a chunk of S ends at the row's frontier; a 1-token row cannot hold 5
    return jnp.asarray([max(n - S, 0) for n in lengths], jnp.int32)


def _close(out, ref, dtype):
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("S", [1, 2, 5])
def test_kernel_matches_dot_attention_per_row(S, dtype):
    """Rows at frontiers of 1, a block, a block + 1 and the whole slab, one
    idle; stale rows past each frontier; a slab no block divides."""
    q, cache = _operands(S, 4, dtype)
    off = _offsets(LENGTHS, S)
    ref = _dot(q, cache, off)
    out = _kernel(q, cache, off, idle=IDLE)
    assert out.shape == ref.shape == (len(LENGTHS), S, 4, C)
    assert out.dtype == ref.dtype
    _close(out[:4], ref[:4], dtype)
    assert not np.asarray(out[4], np.float32).any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_a_slab_of_4098_slots_in_blocks_of_512(dtype):
    """The cell's slab and block: nine blocks a row, two slots in the
    last; rows that hold a token, exactly a block, a block + 1, all."""
    lengths = (1, 512, 513, 4098)
    q, cache = _operands(2, 2, dtype, seed=1, rows=4, n_slots=4098)
    off = _offsets(lengths, 2)
    assert la.block_k_for(q, cache, C) == 512
    _close(la.latent_decode_attention(q, cache, off, v_width=C, scale=SCALE),
           _dot(q, cache, off), dtype)


@pytest.mark.parametrize("S", [1, 2, 5])
def test_stale_slots_past_the_frontier_are_never_seen(S):
    """Whatever a retired request left past a row's frontier — here rows
    that would win every score, with values of 1e4 — changes no bit of the
    result: a dead block is not visited, the tail of a live one gets weight
    zero, not a small one."""
    q, cache = _operands(S, 4, jnp.float32, seed=2, rows=4)
    off = jnp.asarray([0, 3, BLOCK - S, BLOCK + 1], jnp.int32)
    slot = jnp.arange(T)[None, :, None]
    stale = slot >= (off + S)[:, None, None]
    loud = jnp.concatenate(
        [jnp.full((4, 1, C), 1e4), 50.0 * jnp.sign(q[:, 0, 0, C:])[:, None]],
        axis=-1)
    clean = _kernel(q, jnp.where(stale, 0.0, cache), off)
    dirty = _kernel(q, jnp.where(stale, loud, cache), off)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


@pytest.mark.parametrize("blocks", [1, 2])
def test_a_block_that_holds_exactly_a_rows_frontier(blocks):
    """One row ends on a block's last slot: that block is its last live
    one and the next is never read, though the neighbours read it."""
    S = 2
    q, cache = _operands(S, 4, jnp.float32, seed=3, rows=3)
    off = _offsets((blocks * BLOCK, T, blocks * BLOCK + 1), S)
    poisoned = cache.at[0, blocks * BLOCK:].set(jnp.nan)
    out = _kernel(q, poisoned, off)
    assert np.isfinite(np.asarray(out)).all()
    _close(out, _dot(q, cache, off), jnp.float32)


def test_an_idle_row_attends_nothing_and_disturbs_no_neighbour():
    """A row marked ``idle`` reads nothing: zeros out, finite, whatever its
    slab holds and wherever its offset points; the rows around it, before
    and after, read what they did."""
    S = 2
    q, cache = _operands(S, 4, jnp.float32, seed=4)
    off = jnp.asarray([3, 9, 0, T - S, T - S], jnp.int32)
    idle = jnp.asarray([True, False, True, True, False])
    ref = np.asarray(_dot(q, cache, off))
    out = np.asarray(_kernel(q, cache.at[0].set(jnp.nan), off, idle=idle))
    assert not out[[0, 2, 3]].any()
    np.testing.assert_allclose(out[[1, 4]], ref[[1, 4]], atol=2e-5, rtol=2e-5)
    # no row idle is no mask at all
    np.testing.assert_array_equal(
        np.asarray(_kernel(q, cache, off)),
        np.asarray(_kernel(q, cache, off, idle=jnp.zeros_like(idle))))


def test_a_shared_offset_and_the_default_block():
    """One scalar offset for every row; a slab shorter than ``BLOCK_K`` is
    one block of whole 128 lanes."""
    q, cache = _operands(2, 4, jnp.float32, seed=5)
    assert la.block_k_for(q, cache, C) == 128
    out = la.latent_decode_attention(q, cache, jnp.int32(7), v_width=C,
                                     scale=SCALE)
    _close(out, _dot(q, cache, 7), jnp.float32)


def _shapes(S, H, n_slots, dtype, B=32, width=576):
    return (jax.ShapeDtypeStruct((B, S, H, width), dtype),
            jax.ShapeDtypeStruct((B, n_slots, width), dtype))


@pytest.mark.parametrize("S,H,n_slots,dtype,block", [
    (2, 128, 4098, jnp.bfloat16, la.BLOCK_K),   # the cell's verify chunk
    (1, 128, 4098, jnp.float32, la.BLOCK_K),
    (8, 128, 4098, jnp.bfloat16, la.BLOCK_K),   # the longest chunk it takes
    (8, 256, 4098, jnp.float32, 384),           # the scores weigh in
    (2, 16, 300, jnp.bfloat16, 384),            # a short slab is one block
    (8, 1024, 4098, jnp.float32, None),         # nothing fits
], ids=["cell", "cell-f32-S1", "S8", "S8-H256-f32", "short", "too-wide"])
def test_the_block_is_the_latent_caches_own_and_fits_vmem(S, H, n_slots,
                                                          dtype, block):
    q, cache = _shapes(S, H, n_slots, dtype)
    assert la.block_k_for(q, cache, 512) == block
    if block is not None:
        assert la.vmem_bytes(block, S, H, 576, 512,
                             jnp.dtype(dtype).itemsize) <= la.VMEM_BUDGET
    # decode_attention's BLOCK_BYTES would be more slots than the slab
    from rocket_tpu.ops import decode_attention as da
    assert da.BLOCK_BYTES // (576 * 2) > 4098 > 8 * la.BLOCK_K


# -- the selection rule ---------------------------------------------------------


def _events(tracer, name):
    return [e[5] for e in tracer.events() if e[1] == name]


@pytest.fixture
def tracer():
    t = trace.arm(4096)
    t.clear()
    try:
        yield t
    finally:
        trace.disarm()


@pytest.fixture
def latent_kernel_here(monkeypatch):
    """``ops.latent_attention`` with its refusal of a backend that is no
    TPU taken out: a call the kernel would take on the chip takes it here,
    in interpret mode.  (Patching ``_on_tpu`` instead would ask for
    Mosaic.)"""
    real = la.why_not

    def why_not(q, cache, v_width):
        reason = real(q, cache, v_width)
        return None if reason == "backend" else reason

    monkeypatch.setattr(la, "why_not", why_not)


def _mesh_of_two():
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("data",))


@pytest.mark.parametrize("case,reason", [
    ("cpu", "backend"),
    ("f16", "float16"),
    ("narrow", "v_width=96"),
    ("long_chunk", f"S > {la.MAX_CHUNK}"),
    ("vmem", "vmem"),
    ("mesh", "mesh"),
])
def test_every_refusal_is_counted_by_reason(tracer, case, reason):
    """What keeps ``dot_attention`` bumps ``attention/decode/fallback`` with
    its reason.  The backend is asked last, so a CPU run names every other
    reason as the chip would."""
    from rocket_tpu.parallel.context import mesh_context

    S = la.MAX_CHUNK + 1 if case == "long_chunk" else 2
    H = 4096 if case == "vmem" else 2      # 8,192 query rows of float32
    dtype = jnp.float16 if case == "f16" else jnp.float32
    v_width = 96 if case == "narrow" else C
    q, cache = _shapes(S, H, T, dtype, B=2, width=W)
    if case == "mesh":
        with mesh_context(_mesh_of_two()):
            took = la.takes(q, cache, v_width)
    else:
        took = la.takes(q, cache, v_width)
    assert took is False
    (event,) = _events(tracer, "attention/decode/fallback")
    assert event["reason"] == reason and event["kind"] == "latent", event
    assert event["S"] == S and event["D"] == W and event["T"] == T
    assert not _events(tracer, "attention/decode/kernel")


def test_the_rule_asks_nothing_of_the_configurations_attention(monkeypatch):
    """On a TPU the cell's shapes take the kernel; the rule has no
    ``impl``: ``attention`` chooses among ``Attention``'s implementations
    and the one latent configuration states ``dot`` (PERF.md section 6, PR
    34)."""
    monkeypatch.setattr(la, "_on_tpu", lambda: True)
    assert la.why_not(*_shapes(2, 128, 4098, jnp.bfloat16), 512) is None
    assert la.why_not(*_shapes(2, 128, 4098, jnp.float32), 512) is None


def test_the_kernel_is_counted_when_it_is_chosen(latent_kernel_here, tracer):
    q, cache = _operands(2, 4, jnp.float32, seed=7)
    assert la.takes(q, cache, C) is True
    (event,) = _events(tracer, "attention/decode/kernel")
    assert event == {"kind": "latent", "S": 2, "H": 4, "T": T,
                     "block_k": 128, "kernel": 1.0}
    assert not _events(tracer, "attention/decode/fallback")


# -- through the model: the counter, and ``idle`` on its way down ---------------

VOCAB, MAX_SEQ = 61, 40


def _config(n_layers=2, first_k_dense=1):
    return TransformerConfig(
        vocab_size=VOCAB, hidden=32, n_layers=n_layers, n_heads=4,
        ffn_dim=40, max_seq=MAX_SEQ, norm="rmsnorm", mlp="swiglu",
        positions="rope", tie_embeddings=False, sandwich_norm=True,
        residual_float32=True, first_k_dense=first_k_dense,
        mla=MLAConfig(12, C, 8, 4, 6),
        experts=ExpertsConfig(n_routed=8, top_k=2, expert_dim=16, n_shared=1,
                              held_start=0, n_held=8))


def _seeded(tree, seed):
    leaves, treedef = jax.tree_util.tree_flatten(nn.meta.unbox(tree))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return treedef.unflatten([
        0.3 * jax.random.normal(k, leaf.shape, jnp.float32)
        + (1.0 if leaf.ndim == 1 else 0.0) for k, leaf in zip(keys, leaves)])


@pytest.fixture(scope="module")
def pair():
    """A toy latent target and its MTP module, float32, seeded."""
    target = TransformerLM(_config())
    draft = MTPDraft(_config(n_layers=1, first_k_dense=0))
    tokens = {"tokens": jnp.zeros((1, 4), jnp.int32)}
    return (target, draft,
            _seeded(target.init(jax.random.PRNGKey(0), tokens)["params"], 1),
            _seeded(draft.init(jax.random.PRNGKey(0), tokens)["params"], 2))


def _prefilled(model, params, rows=3, P=6):
    prompt = jax.random.randint(jax.random.PRNGKey(8), (rows, P), 0, VOCAB)
    pos = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (rows, P))
    _, mut = model.apply(
        {"params": params,
         "cache": generate_mod.zero_cache(model, params, prompt)},
        {"tokens": prompt, "positions": pos}, decode=True, mutable=["cache"],
        prefill=True)
    return mut["cache"], P


def _decode(model, params, cache, P, idle=None):
    batch = {"tokens": jnp.asarray([[3, 4], [5, 6], [7, 8]], jnp.int32),
             "positions": P + jnp.broadcast_to(jnp.arange(2), (3, 2))}
    if idle is not None:
        batch["idle"] = idle
    out, mut = model.apply({"params": params, "cache": cache}, batch,
                           decode=True, mutable=["cache", "routing"])
    return np.asarray(out["logits"]), mut["cache"]


def test_the_absorbed_path_on_a_cpu_counts_its_fallback(pair, tracer):
    """Every layer's absorbed call keeps ``dot_attention`` here and says
    why; the expanded prefill counts neither."""
    target, _, params, _ = pair
    cache, P = _prefilled(target, params)
    assert not _events(tracer, "attention/decode/fallback")
    _decode(target, params, cache, P)
    events = _events(tracer, "attention/decode/fallback")
    assert len(events) == target.config.n_layers
    assert all(e["reason"] == "backend" and e["kind"] == "latent"
               and e["S"] == 2 and e["D"] == C + 4 and e["T"] == MAX_SEQ
               for e in events)
    assert not _events(tracer, "attention/decode/kernel")


def test_block_hands_idle_to_latent_attention(pair, latent_kernel_here,
                                              tracer):
    """An idle row's attention reads nothing, so what the row emits is not
    what it would have (the caller drops it); the live rows emit what they
    did, and every row's chunk, the idle one's too, is written to the cache
    as ever."""
    target, _, params, _ = pair
    cache, P = _prefilled(target, params)
    idle = jnp.asarray([False, True, False])
    want, cache_want = _decode(target, params, cache, P)
    got, cache_got = _decode(target, params, cache, P, idle=idle)
    assert len(_events(tracer, "attention/decode/kernel")) \
        == 2 * target.config.n_layers
    np.testing.assert_allclose(got[[0, 2]], want[[0, 2]], atol=2e-5, rtol=2e-5)
    assert np.abs(got[1] - want[1]).max() > 1e-3
    # the first layer's write is what it was bit for bit; the second's
    # follows the first's (dropped) output on the idle row alone
    first, second = [
        (np.asarray(a), np.asarray(b)) for a, b in zip(
            jax.tree_util.tree_leaves(cache_want),
            jax.tree_util.tree_leaves(cache_got)) if a.ndim == 3]
    np.testing.assert_array_equal(*first)
    np.testing.assert_allclose(second[0][[0, 2]], second[1][[0, 2]],
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(second[0][1, :P], second[1][1, :P])
    assert np.abs(second[1][1, P:P + 2]).max() > 0


def _serve(pair, counters=None):
    """Three requests through two rows: row 0 finishes first and stands
    idle for a round before the third request is admitted into it."""
    target, draft, params, draft_params = pair
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, VOCAB, size=n).astype(np.int32)
               for n in (7, 7, 5)]
    bat = ContinuousBatcher(target, draft, params, draft_params,
                            total_len=24, n_draft=1)
    if counters is not None:
        bat.reads = HostReads(counters=counters)
    bat.start(np.stack(prompts[:2]))
    bat.retire(0)
    bat.step()                           # row 0 stands idle
    bat.admit(0, prompts[2])
    steps = 0
    while not bat.all_done:
        bat.step()
        steps += 1
        assert steps < 100
    return bat, [np.asarray(bat.row_tokens(r)[0]) for r in range(2)]


def _forget_compiled_rounds():
    """The same models take the other branch next time: their traced
    programs hold the one they were traced with."""
    for program in (generate_mod._mtp_round, generate_mod._mtp_admit,
                    generate_mod._mtp_prefill):
        program.clear_cache()


def test_kernel_round_serves_what_the_dot_round_serves(pair, request, tracer,
                                                       monkeypatch):
    """The toy ``_mtp_round`` through ``ContinuousBatcher``: the tokens of
    the ``dot_attention`` round; every absorbed call of the compiled round
    is the kernel (the target's layers and the module's), each handed the
    finished rows; and the host counts the blocks a round had to read."""
    from rocket_tpu.serve.metrics import ServeCounters

    bat, want = _serve(pair)
    assert bat._slab is None
    assert {e["reason"] for e in _events(
        tracer, "attention/decode/fallback")} == {"backend"}
    request.getfixturevalue("latent_kernel_here")
    handed, real = [], la.latent_decode_attention
    monkeypatch.setattr(
        la, "latent_decode_attention",
        lambda *a, idle=None, **kw: handed.append(idle is not None)
        or real(*a, idle=idle, **kw))
    _forget_compiled_rounds()
    tracer.clear()
    counters = ServeCounters()
    bat, got = _serve(pair, counters)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    names = [e[1] for e in tracer.events()]
    assert "attention/decode/fallback" not in names
    assert names.count("attention/decode/kernel") == handed.count(True) == 3
    assert bat._slab == (MAX_SEQ, 128)   # the toy slab is one block a row
    snap = counters.snapshot()
    assert 0 < snap["attended_blocks"] < snap["total_blocks"]
    assert snap["attended_block_share"] == pytest.approx(
        snap["attended_blocks"] / snap["total_blocks"])
    _forget_compiled_rounds()


# -- Mosaic's verdict at the cell's widths, with no chip attached ---------------


@pytest.fixture(scope="module")
def one_chip():
    """A described ``v5e`` chip (``docs/performance.md``): only inside a
    fixture, never at import; skipped where libtpu cannot be had."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a described device's executable cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    except Exception as exc:                       # no libtpu, lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_mosaic_takes_the_cells_shapes_and_xla_copies_no_cache(
        one_chip, monkeypatch, dtype):
    """32 rows x 4,098 slots x 576, 128 heads x 2 positions: the kernel
    lowers, and the cache reaches it as stored — the leaf is laid out
    slots-minor on the chip, so the view ``[B, W, T]`` is a bitcast and the
    program holds no temporary the size of the leaf."""
    monkeypatch.setattr(la, "_on_tpu", lambda: True)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    compiled = jax.jit(lambda q, cache, off, idle: la.latent_decode_attention(
        q, cache, off, v_width=512, scale=192 ** -0.5, idle=idle)).lower(
        sds((32, 2, 128, 576), dtype), sds((32, 4098, 576), dtype),
        sds((32,), jnp.int32), sds((32,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "latent_decode_attention" in text
    leaf = 32 * 4098 * 576 * jnp.dtype(dtype).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < leaf // 8
