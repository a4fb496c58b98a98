"""Absorbed latent attention over its cache — a Pallas TPU kernel that
reads, for each row, only the cache blocks that row has written.

A decode round of a latent model (``models.transformer.LatentAttention``,
absorbed path) scores a handful of new positions per row — ``H`` heads
each, their queries already folded into the latent space, ``[S, H, C +
dr]`` — against that row's cache slab ``[T, C + dr]``: one row ``[c_kv |
k_rope]`` a token serves every head (multi-query), and its first ``C``
numbers are the values.  ``dot_attention(v=None, v_width=C)`` streams all
``T`` slots of every row and masks; XLA cannot skip by row.  Here, as in
:mod:`rocket_tpu.ops.decode_attention` (whose skeleton this is), the rows'
lengths ride as scalar-prefetch operands, the slot axis is cut into blocks
of ``BLOCK_K``, and a block past a row's frontier is neither fetched (its
``index_map`` names a block already resident, so no DMA is issued) nor
computed (the body runs under ``pl.when``).  A row the caller marks
``idle`` has length zero.

The cache is read as stored.  On a TPU a ``[B, T, W]`` leaf whose rows are
no whole 128 lanes (``W`` = 576: 640 with padding) is laid out with the
slots minor, ``{1,2,0}``: the view that is free is ``[B, W, T]`` (XLA makes
the transpose a bitcast; ``[B, T, W]`` row-major would be a copy of the
whole leaf a call).  So a block is ``[W, block_k]``, slots in lanes, and a
live step is two MXU products: a row's ``S * H`` queries ``[S * H, W]``
times the block, and the probabilities ``[S * H, block_k]`` against the
block's first ``C`` sublanes, contracted over the lanes.  No head packing,
no strided load, nothing of the cache repeated, sliced in HBM or converted.
(A leaf the TPU stores otherwise — a slab of a few hundred slots — is
relaid by XLA before the call: correct, and small.)  Scores, running max,
normaliser and accumulator are float32; probabilities are cast to the
cache's dtype for p.V, as ``dot_attention`` does; the mask value is
finite.  Off-TPU the kernel runs in interpret mode, so the CPU tests cover
its logic.

``LatentAttention`` asks :func:`takes`: the kernel where what the call can
see says it applies (:func:`why_not`), its own ``dot_attention(v=None,
v_width=)`` otherwise — each choice counted at trace time under the names
``ops.decode_attention`` counts its own.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rocket_tpu.ops.decode_attention import (
    MASK_VALUE,
    MAX_CHUNK,
    MIN_BLOCK_K,
    VMEM_BUDGET,
    VMEM_LIMIT,
    _on_tpu,
    live_blocks,
)

# Cache slots a block: 512 slots of 576 bfloat16 numbers are 0.56 MiB.
# The latent cache's own constant, from a sweep of the kernel alone on the
# chip (PERF.md section 6, PR 34); ``decode_attention.BLOCK_BYTES`` would be
# 5,461 of these slots, more than a 4,097-slot slab, and skip nothing.
BLOCK_K = 512


def _query_rows(S: int, H: int) -> int:
    """Query rows of a cache row, padded to the bf16 sublane tile."""
    return -(-(S * H) // 16) * 16


def vmem_bytes(block_k: int, S: int, H: int, W: int, C: int,
               itemsize: int) -> int:
    """What a grid step holds in VMEM, reckoned from above: the cache
    block and the queries, each twice (the next is fetched while this one
    is read); the output block twice and the accumulator; the mask's index
    arrays, the scores and the probabilities, ``[rows, block_k]`` of 32
    bits each."""
    rows = _query_rows(S, H)
    buffers = 2 * (block_k + rows) * W * itemsize
    outputs = rows * C * (2 * itemsize + 4)
    scores = 8 * rows * block_k * 4
    return buffers + outputs + scores


def block_k_for(q, cache, v_width: int) -> Optional[int]:
    """Cache slots a block for this call (``q`` and ``cache`` are read for
    shape and dtype alone): ``BLOCK_K``, in whole 128 slots fewer while
    ``vmem_bytes`` is over ``VMEM_BUDGET``; the whole slab (rounded up to
    whole 128 lanes) when that is shorter; ``None`` when even
    ``MIN_BLOCK_K`` slots do not fit.  The last block may be ragged: slots
    past the slab are masked, never read as values."""
    S, H, W = q.shape[1:]
    itemsize = jnp.dtype(cache.dtype).itemsize
    block = BLOCK_K
    while block >= MIN_BLOCK_K and vmem_bytes(
            block, S, H, W, v_width, itemsize) > VMEM_BUDGET:
        block -= 128
    if block < MIN_BLOCK_K:
        return None
    return min(block, -(-cache.shape[1] // 128) * 128)


def _kernel(len_ref, src_ref, q_ref, c_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, S: int, H: int, block_k: int, n_slots: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    rows, C = acc_ref.shape
    length = len_ref[b]
    _, count = live_blocks(length, 0, block_k)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j < count)
    def _compute():
        # Query row s * H + h is head h at position (length - S) + s; lane
        # t of the block is slot j * block_k + t.
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 1)
        s_idx = jnp.zeros_like(r)
        for i in range(1, S):        # r // H without a vector division
            s_idx = s_idx + (r >= i * H).astype(jnp.int32)
        mask = j * block_k + c <= (length - S) + s_idx
        v = c_ref[0, :C, :]                              # [C, block_k]
        if n_slots % block_k:
            # the ragged last block: what lies past the slab is not data
            t = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
            v = jnp.where(j * block_k + t < n_slots, v, jnp.zeros_like(v))
        s = jax.lax.dot_general(
            q_ref[0], c_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = jnp.where(mask, s, MASK_VALUE)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        correction = jnp.exp(m_prev - m_new)
        l_new = correction * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        l_final = l_ref[:, :1]
        safe_l = jnp.where(l_final == 0.0, 1.0, l_final)
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("v_width", "scale", "block_k"))
def latent_decode_attention(q: jax.Array, cache: jax.Array, q_offset, *,
                            v_width: int, scale: float, idle=None,
                            block_k: Optional[int] = None) -> jax.Array:
    """``dot_attention(q, cache[:, :, None, :], v_width=v_width,
    causal=True, q_offset=..., scale=scale)`` for a short chunk of absorbed
    queries against a latent cache, reading only each row's live blocks.

    ``q`` is ``[B, S, H, W]`` (the chunk's queries, at positions
    ``q_offset .. q_offset + S - 1``), the cache ``[B, T, W]``, float32 or
    bfloat16, a slot's first ``v_width`` numbers its values; ``q_offset``
    is ``[B]`` (a frontier a row) or a scalar.  Slots at or past a row's
    ``q_offset + S`` are never seen, whatever they hold.  A row that
    ``idle`` (``[B]`` bool, optional) marks attends nothing and its output
    is zeros: for rows whose result the caller drops.  ``block_k`` (cache
    slots a block) is :func:`block_k_for`'s unless a test cuts finer.
    Returns ``[B, S, H, v_width]``.

    Jitted, so that the layers of a round share one trace and one lowered
    kernel (five layers and the MTP module: six calls)."""
    B, S, H, W = q.shape
    T = cache.shape[1]
    if block_k is None:
        block_k = block_k_for(q, cache, v_width)
        if block_k is None:
            raise ValueError(
                f"{S * H} queries of {W} x {cache.dtype}: a block of "
                f"{MIN_BLOCK_K} slots does not fit the kernel's VMEM")
    nj = -(-T // block_k)
    lengths = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (B,)) + S
    if idle is not None:
        lengths = jnp.where(idle, 0, lengths)
    # a dead step names a block that is already resident, so no DMA is
    # issued for it: a live row's own last block, an idle row's
    # predecessor's last
    src = jax.lax.cummax(
        jnp.where(lengths > 0, jnp.arange(B, dtype=jnp.int32), 0))

    def cache_map(b, j, len_ref, src_ref):
        r = src_ref[b]
        last = jnp.maximum(live_blocks(len_ref[r], 0, block_k)[1] - 1, 0)
        return r, 0, jnp.where(r == b, jnp.minimum(j, last), last)

    rows = _query_rows(S, H)
    qs = jnp.pad(q.reshape(B, S * H, W).astype(cache.dtype),
                 ((0, 0), (0, rows - S * H), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, S=S, H=H, block_k=block_k,
                          n_slots=T),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nj),
            in_specs=[
                pl.BlockSpec((1, rows, W), lambda b, j, *_: (b, 0, 0)),
                pl.BlockSpec((1, W, block_k), cache_map),
            ],
            out_specs=pl.BlockSpec((1, rows, v_width),
                                   lambda b, j, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows, v_width), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, rows, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=not _on_tpu(),
        name="latent_decode_attention",
    )(lengths, src, qs, jnp.swapaxes(cache, 1, 2))
    return out[:, :S * H].reshape(B, S, H, v_width)


def why_not(q, cache, v_width: int) -> Optional[str]:
    """The reason the kernel does not apply to this call, or ``None``.
    Read from what the call can see — backend, dtype, shapes, the active
    mesh — and nothing else (``q`` and ``cache`` for shape and dtype
    alone).  Not from the configuration's ``attention``: that chooses
    among the implementations of ``Attention`` (heads of q, k and v), and
    ``LatentAttention`` has never read it."""
    from rocket_tpu.parallel.context import current_mesh

    if cache.dtype not in (jnp.float32, jnp.bfloat16):
        return str(cache.dtype)
    if v_width % 128:
        # the accumulator's lanes, and the values a tile-aligned slice
        return f"v_width={v_width}"
    if q.shape[1] > MAX_CHUNK:
        return f"S > {MAX_CHUNK}"
    if block_k_for(q, cache, v_width) is None:
        return "vmem"
    mesh = current_mesh()
    if mesh is not None and mesh.devices.size > 1:
        # a Mosaic call cannot be partitioned
        return "mesh"
    # last, so that a CPU run names what would keep the kernel off a TPU too
    return None if _on_tpu() else "backend"


def takes(q, cache, v_width: int) -> bool:
    """Whether the kernel takes this absorbed call — :func:`why_not` finds
    no reason against it — and the choice counted, at TRACE time (it is
    static): ``attention/decode/kernel`` or ``attention/decode/fallback``
    with the reason, ``kind="latent"``, the names ``ops.decode_attention``
    counts its own under.  The caller keeps ``dot_attention(v=None,
    v_width=)`` where this says no."""
    from rocket_tpu.observe.trace import counter

    S, H, W = q.shape[1:]
    reason = why_not(q, cache, v_width)
    if reason is not None:
        counter("attention/decode/fallback", 1, reason=reason,
                kind="latent", S=S, D=W, T=cache.shape[1])
        return False
    counter("attention/decode/kernel", 1, kind="latent", S=S, H=H,
            T=cache.shape[1], block_k=block_k_for(q, cache, v_width))
    return True
