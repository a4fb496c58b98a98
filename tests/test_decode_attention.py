"""The decode kernel (``ops/decode_attention.py``) in interpret mode against
``dot_attention``, at toy sizes; and the rule that chooses it.

The kernel reads, for each row, only the key blocks that row has written:
so every case plants stale non-zero keys and values past each row's
frontier (what a retired request leaves behind) and compares with
``dot_attention``, whose causal mask hides them.  Times are the chip's to
give (``benchmark/run.py``); a CPU run proves results and counts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rocket_tpu.observe import trace
from rocket_tpu.ops import decode_attention as da
from rocket_tpu.ops.attention import dot_attention

T, BLOCK, D = 36, 16, 128          # 36 slots in blocks of 16: the last is ragged
# frontiers (q_offset + S): 1 token, exactly a block, a block + 1, the slab
LENGTHS = (1, BLOCK, BLOCK + 1, T)


def _operands(S, H, KV, dtype, seed=0, rows=len(LENGTHS)):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (rows, S, H, D), dtype)
    # every slot holds something, live or stale
    k = jax.random.normal(kk, (rows, T, KV, D), dtype)
    v = 3.0 + jax.random.normal(kv, (rows, T, KV, D), dtype)
    return q, k, v


def _close(out, ref, dtype):
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("window", [None, 7], ids=["full", "window7"])
@pytest.mark.parametrize("G", [1, 4])
@pytest.mark.parametrize("S", [1, 5])
def test_kernel_matches_dot_attention_per_row(S, G, window, dtype):
    """Rows at frontiers of 1, a block, a block + 1 and the whole slab;
    stale keys past each frontier; a window shorter than the longest row."""
    KV = 2
    q, k, v = _operands(S, KV * G, KV, dtype)
    # a chunk of S ends at the row's frontier; a 1-token row cannot hold 5
    off = jnp.asarray([max(n - S, 0) for n in LENGTHS], jnp.int32)
    ref = dot_attention(q, k, v, causal=True, q_offset=off, window=window)
    out = da.decode_attention(q, k, v, off, window=window, block_k=BLOCK)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    _close(out, ref, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("off", [0, BLOCK - 1, BLOCK, T - 5])
def test_kernel_matches_dot_attention_shared_offset(off, dtype):
    """The shared ``cache_index``: one scalar offset for every row."""
    q, k, v = _operands(5, 4, 2, dtype, seed=1)
    ref = dot_attention(q, k, v, causal=True, q_offset=off, window=None)
    out = da.decode_attention(q, k, v, jnp.int32(off), block_k=BLOCK)
    _close(out, ref, dtype)


def test_stale_slots_past_the_frontier_are_never_seen():
    """Whatever a retired request left past a row's frontier — here keys
    that would win every score and values of 1e4 — changes no bit of the
    result: a dead block is not visited, the tail of a live one gets
    weight zero, not a small one."""
    S, KV, G = 5, 2, 4
    q, k, v = _operands(S, KV * G, KV, jnp.float32, seed=2)
    off = jnp.asarray([0, 3, BLOCK - 2, BLOCK + 1], jnp.int32)
    slot = jnp.arange(T)[None, :, None, None]
    stale = slot >= (off + S)[:, None, None, None]
    loud_k = 50.0 * jnp.sign(q[:, :1, :KV])          # aligned with a query
    clean = da.decode_attention(q, jnp.where(stale, 0.0, k),
                                jnp.where(stale, 0.0, v), off, block_k=BLOCK)
    dirty = da.decode_attention(q, jnp.where(stale, loud_k, k),
                                jnp.where(stale, 1e4, v), off, block_k=BLOCK)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


def test_an_idle_row_attends_nothing_and_disturbs_no_neighbour():
    """A row marked ``idle`` reads nothing: zeros out, finite, whatever its
    slab holds and wherever its offset points; the rows around it, before
    and after, read what they did."""
    S, KV, G = 5, 2, 2
    q, k, v = _operands(S, KV * G, KV, jnp.float32, seed=3, rows=5)
    off = jnp.asarray([3, 9, 0, T - S, T - S], jnp.int32)
    idle = jnp.asarray([True, False, True, True, False])
    ref = np.asarray(dot_attention(q, k, v, causal=True, q_offset=off))
    for window in (None, 7):
        out = np.asarray(da.decode_attention(
            q, k, v, off, idle=idle, window=window, block_k=BLOCK))
        assert not out[[0, 2, 3]].any()
        if window is None:
            np.testing.assert_allclose(out[[1, 4]], ref[[1, 4]],
                                       atol=2e-5, rtol=2e-5)
    # no row idle is no mask at all
    np.testing.assert_array_equal(
        np.asarray(da.decode_attention(q, k, v, off, block_k=BLOCK)),
        np.asarray(da.decode_attention(q, k, v, off,
                                       idle=jnp.zeros_like(idle),
                                       block_k=BLOCK)))


# -- a key mask: the selecting layer's round chunk -------------------------------


def _selection(S, off, seed):
    """``select_mask`` over random index scores (dead past each query's
    position), top 6: ties across the threshold in row 1, row 5's choice
    all in its second block, row 4 idle (chooses nothing)."""
    from rocket_tpu.ops.select_attention import select_mask

    q_pos = off[:, None] + jnp.arange(S)[None, :]
    slot = jnp.arange(T)[None, None, :]
    scores = jax.random.normal(jax.random.PRNGKey(seed), (len(off), S, T))
    scores = scores.at[1, :, 2:14].set(0.5)                     # ties
    scores = scores.at[5].set(jnp.where((slot[0] >= BLOCK)
                                        & (slot[0] < 2 * BLOCK), 9.0, -9.0))
    scores = jnp.where(slot <= q_pos[:, :, None], scores, -jnp.inf)
    scores = scores.at[4].set(-jnp.inf)
    return select_mask(scores, 6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("KV", [4, 8])
@pytest.mark.parametrize("S", [1, 2, 5])
def test_a_key_mask_is_dot_attention_under_that_mask(S, KV, dtype):
    """``key_mask=`` is ``dot_attention(causal=False, key_mask=)`` over a
    ragged slab (36 slots in blocks of 16) for rows whose chunk starts the
    cache, ends inside the first block, crosses into the second, ends the
    slab, is idle, and chose every key of one block only; stale keys past
    each frontier are loud.  A query that chose nothing (the idle row's)
    reads zeros."""
    q, k, v = _operands(S, 2 * KV, KV, dtype, seed=7, rows=6)
    off = jnp.asarray([0, 9 - S, BLOCK + 3 - S, T - S, 11, T - S], jnp.int32)
    stale = jnp.arange(T)[None, :, None, None] >= (off + S)[:, None, None,
                                                            None]
    k = jnp.where(stale, 40.0, k).astype(dtype)
    chosen = _selection(S, off, seed=S * KV)
    idle = jnp.arange(6) == 4
    assert chosen[5].any() and not chosen[5, :, :BLOCK].any()
    out = da.decode_attention(q, k, v, off, idle=idle, key_mask=chosen,
                              block_k=BLOCK)
    ref = dot_attention(q, k, v, causal=False, key_mask=chosen)
    live = np.asarray(~idle)
    _close(out[live], ref[live], dtype)
    assert not np.asarray(out[4], np.float32).any()


def test_the_unmasked_kernel_traces_what_it_traced_before():
    """With no key mask the kernel is, op for op, the one the Mistral cells
    compiled before the mask was added (its jaxpr at toy size, a ragged
    slab, an idle operand and a window; hashes of the tree before)."""
    import hashlib

    def traced(S, H, KV, dtype, window):
        q = jax.ShapeDtypeStruct((3, S, H, D), dtype)
        k = jax.ShapeDtypeStruct((3, T, KV, D), dtype)
        off = jax.ShapeDtypeStruct((3,), jnp.int32)
        idle = jax.ShapeDtypeStruct((3,), jnp.bool_)
        text = str(jax.make_jaxpr(
            lambda q, k, v, o, i: da.decode_attention(
                q, k, v, o, idle=i, window=window, block_k=BLOCK))(
            q, k, k, off, idle))
        return hashlib.sha256(text.encode()).hexdigest()[:16]

    assert traced(2, 32, 4, jnp.bfloat16, None) == "2b3ab8ff7adf84ab"
    assert traced(5, 8, 2, jnp.float32, 7) == "5a4409e107c7c46d"


def _shapes(S, H, KV, Dh, n_slots, dtype, B=8):
    return (jax.ShapeDtypeStruct((B, S, H, Dh), dtype),
            jax.ShapeDtypeStruct((B, n_slots, KV, Dh), dtype))


@pytest.mark.parametrize("S,H,KV,n_slots,dtype,block,masked", [
    # 8 bf16 KV heads of 128 (Mistral): BLOCK_BYTES of keys is 3,072 slots
    (5, 32, 8, 4100, jnp.bfloat16, 3072, False),
    (1, 32, 8, 4100, jnp.bfloat16, 3072, False),
    # 32 bf16 KV heads (the llama2_7b preset): a quarter of the slots
    (5, 32, 32, 4096, jnp.bfloat16, 768, False),
    # float32 caches: half
    (5, 32, 8, 4096, jnp.float32, 1536, False),
    (5, 32, 32, 4096, jnp.float32, 384, False),
    # the widest chunk the rule takes, 8 query heads a KV head: the
    # scores weigh in, the block gives way
    (8, 64, 8, 4096, jnp.bfloat16, 2688, False),
    # a slab shorter than a block is one block, on the 16-row tile
    (5, 8, 2, 36, jnp.float32, 48, False),
    (5, 8, 2, 64, jnp.bfloat16, 64, False),
    # with a key mask, MASKED_BLOCK_BYTES: Keye's verify chunk (4 bf16
    # heads) 1,024 slots, Mistral's widths 512
    (2, 32, 4, 20481, jnp.bfloat16, 1024, True),
    (5, 32, 8, 4100, jnp.bfloat16, 512, True),
    (8, 64, 32, 4096, jnp.float32, 128, True),
], ids=["mistral-verify", "mistral-draft", "kv32-bf16", "kv8-f32",
        "kv32-f32", "S8-G8", "toy-36", "toy-64", "keye-verify-masked",
        "mistral-masked", "kv32-f32-masked"])
def test_the_block_follows_the_bytes_of_the_cache(S, H, KV, n_slots, dtype,
                                                   block, masked):
    """K and V blocks, each double-buffered, and a group's working arrays
    fit the budget whatever the heads and the dtype (a fixed 3,072 slots
    of 32 bf16 heads is 100 MB and Mosaic refuses it)."""
    q, k = _shapes(S, H, KV, D, n_slots, dtype)
    assert da.block_k_for(q, k, masked) == block
    need = da.vmem_bytes(block, S, H // KV, KV, D, jnp.dtype(dtype).itemsize,
                         masked)
    assert need <= da.VMEM_BUDGET < da.VMEM_LIMIT
    size = da.MASKED_BLOCK_BYTES if masked else da.BLOCK_BYTES
    # a masked block is never cut below MIN_BLOCK_K for its size's sake
    assert block * KV * D * jnp.dtype(dtype).itemsize <= size \
        or (masked and block == da.MIN_BLOCK_K)


def test_the_default_block_covers_a_short_slab_in_one():
    q, k, v = _operands(1, 2, 2, jnp.float32, seed=4)
    assert da.block_k_for(q, k) == 48
    off = jnp.asarray([0, 5, 20, T - 1], jnp.int32)
    _close(da.decode_attention(q, k, v, off),
           dot_attention(q, k, v, causal=True, q_offset=off), jnp.float32)


# -- the selection rule ---------------------------------------------------------


def _events(tracer, name):
    return [e[5] for e in tracer.events() if e[1] == name]


@pytest.fixture
def tracer():
    t = trace.arm(512)
    t.clear()
    try:
        yield t
    finally:
        trace.disarm()


def _mesh_of_two():
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("data",))


@pytest.mark.parametrize("case,reason", [
    ("cpu", "backend"),
    ("dot", "attention=dot"),
    ("int8", "int8"),
    ("D64", "D=64"),
    ("D256", "D=256"),
    ("long_chunk", f"S > {da.MAX_CHUNK}"),
    ("vmem", "vmem"),
    ("mesh", "mesh"),
])
def test_every_refusal_is_counted_by_reason(tracer, case, reason):
    """What keeps ``dot_attention`` bumps ``attention/decode/fallback`` with
    its reason, and the result is ``dot_attention``'s.  The backend is asked
    last, so a CPU run names every other reason as the chip would."""
    from rocket_tpu.parallel.context import mesh_context

    S = da.MAX_CHUNK + 1 if case == "long_chunk" else 2
    Dh = {"D64": 64, "D256": 256}.get(case, D)
    KV = 128 if case == "vmem" else 2      # 128 float32 heads: 96 slots
    kq, kk = jax.random.split(jax.random.PRNGKey(5))
    q = jax.random.normal(kq, (2, S, KV, Dh), jnp.float32)
    k = jax.random.normal(kk, (2, T, KV, Dh), jnp.float32)
    off = jnp.asarray([3, 11], jnp.int32)
    kw = dict(window=None, impl="dot" if case == "dot" else "auto",
              quantized=case == "int8")
    if case == "mesh":
        with mesh_context(_mesh_of_two()):
            out = da.cached_attention(q, k, k, off, **kw)
    else:
        out = da.cached_attention(q, k, k, off, **kw)
    (event,) = _events(tracer, "attention/decode/fallback")
    assert event["reason"] == reason, event
    assert event["S"] == S and event["D"] == Dh and event["T"] == T
    assert not _events(tracer, "attention/decode/kernel")
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(dot_attention(q, k, k, causal=True, q_offset=off)))


@pytest.mark.parametrize("KV,n_slots,dtype,block", [
    (32, 4096, jnp.bfloat16, 768), (8, 4096, jnp.float32, 1536)],
    ids=["kv32-bf16", "kv8-f32"])
def test_wide_caches_take_the_kernel_at_a_block_that_fits(
        monkeypatch, tracer, KV, n_slots, dtype, block):
    """The llama2_7b preset's 32 bf16 KV heads and a float32 cache of 8,
    4,096 slots: chosen (on a TPU), at a quarter and a half of the slots
    that 8 bf16 heads get; counted with that block."""
    monkeypatch.setattr(da, "_on_tpu", lambda: True)
    q, k = _shapes(5, 32, KV, D, n_slots, dtype)
    assert da.why_not(q, k, impl="auto") is None
    seen = {}
    monkeypatch.setattr(
        da, "decode_attention",
        lambda q, k, v, off, **kw: seen.update(kw) or jnp.zeros(q.shape))
    da.cached_attention(q, k, k, jnp.zeros((8,), jnp.int32), window=None,
                        impl="auto")
    (event,) = _events(tracer, "attention/decode/kernel")
    assert (event["block_k"], event["T"], event["G"]) == (
        block, n_slots, 32 // KV)
    assert seen == {"idle": None, "window": None}


def test_the_kernel_is_counted_when_it_is_chosen(decode_kernel_here, tracer):
    q, k, v = _operands(5, 8, 2, jnp.float32, seed=6)
    off = jnp.asarray([0, 3, 17, T - 5], jnp.int32)
    out = da.cached_attention(q, k, v, off, window=None, impl="auto")
    (event,) = _events(tracer, "attention/decode/kernel")
    assert (event["S"], event["G"], event["T"]) == (5, 4, T)
    assert event["block_k"] == da.block_k_for(q, k) == 48
    assert not _events(tracer, "attention/decode/fallback")
    _close(out, dot_attention(q, k, v, causal=True, q_offset=off),
           jnp.float32)


def test_the_rolling_cache_keeps_dot_attention_and_says_so(tracer):
    """``_decode_attend``'s rolling branch (slots are not positions) counts
    its own refusal; the int8 pages count theirs through the model too."""
    import flax.linen as nn

    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM

    for extra, reason in (
            (dict(attention_window=8, decode_rolling_cache=True,
                  decode_rolling_slack=4), "rolling"),
            (dict(kv_cache_int8=True), "int8")):
        tracer.clear()
        cfg = TransformerConfig(vocab_size=32, hidden=256, n_layers=1,
                                n_heads=2, max_seq=32, **extra)
        model = TransformerLM(cfg)
        batch = {"tokens": jnp.zeros((2, 2), jnp.int32)}
        variables = nn.meta.unbox(model.init(
            jax.random.PRNGKey(0), batch, decode=True))
        model.apply(variables, batch, decode=True, mutable=["cache"])
        reasons = {e["reason"]
                   for e in _events(tracer, "attention/decode/fallback")}
        assert reasons == {reason}
        assert not _events(tracer, "attention/decode/kernel")
