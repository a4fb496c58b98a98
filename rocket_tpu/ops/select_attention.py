"""Attention that chooses its keys: a learned indexer scores every key a
query may see, the ``top_k`` best are kept, and the softmax runs over
those alone (DeepSeek-V3.2's sparse attention, here over grouped-query
K and V).

For a query ``t`` and a key ``s <= t``::

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (float32)
    S_t     = the top_k keys of largest I[t, s]; all of them while
              t + 1 <= top_k; a tie goes to the lower slot
    o[t, a] = sum_{s in S_t} softmax_s(q[t, a] . k[s, g(a)] * scale) v[s, g(a)]

``qI`` are the indexer's ``J`` query heads, ``kI`` its one key a token
(cached beside K and V), ``w`` its ``J`` head weights.  A slot past a
query's position never counts, also while fewer than ``top_k`` are live:
the unchosen and the dead weigh nought.

Two ways to attend over a selection, one result, chosen by
:func:`selected_attention` from the call's shapes (:func:`gathers`):

- **gather** (few queries a row against a slab more than ``GATHER_COST``
  times the keys they keep): ``lax.top_k`` gives each query's slots, their
  K and V rows are gathered and ``dot_attention`` runs over ``top_k`` keys
  a query — the cost no longer grows with the slab;
- **mask** (an admission's chunk of hundreds of queries; a round's chunk
  against a slab streamed faster than it is gathered from): the
  ``top_k``-th largest score of each query is found by a search over the
  bits of its float32 pattern (32 counting passes, no sort), and the slab
  is attended under the mask ``score >= threshold``.

Both keep exactly the same set, ties included.  The scores, the selection
and the gather are XLA's.  Under the mask a chunk attends through a Pallas
kernel where one applies, ``dot_attention(key_mask=)`` otherwise: an
admission's chunk through :func:`masked_attention` (online softmax over
key blocks, the mask read a block at a time, the blocks past the chunk's
last position neither fetched nor computed; XLA's masked pass writes and
reads ``f32[heads, chunk, slots]`` scores five times over, a gigabyte each
for a chunk of 512 queries against 16,384 slots), a round's chunk through
``ops.decode_attention``'s kernel with its ``key_mask`` (each row's
written blocks alone, the mask beside K and V; XLA's pass streams every
slot of every row).  A kernel that reads the selected rows in place, for
the gather's half, is ``ROADMAP.md``'s.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rocket_tpu.ops import decode_attention
from rocket_tpu.ops.attention import _group_size, dot_attention
from rocket_tpu.ops.decode_attention import VMEM_LIMIT
from rocket_tpu.ops.flash import MASK_VALUE

Array = jax.Array


def index_scores(q_idx: Array, w_idx: Array, k_idx: Array, q_pos: Array,
                 idle: Optional[Array] = None) -> Array:
    """``[B, S, T]`` float32 index scores of ``S`` queries a row against
    ``T`` slots, ``-inf`` where a slot lies past the query's position (or
    the row is ``idle``: its output is dropped, so it scores nothing).

    ``q_idx`` ``[B, S, J, d]``, ``w_idx`` ``[B, S, J]`` (already scaled),
    ``k_idx`` ``[B, d, T]`` (slots last, as the cache stores them; slot ==
    position), ``q_pos`` ``[B, S]``."""
    dots = jnp.einsum("bsjd,bdt->bsjt", q_idx, k_idx,
                      preferred_element_type=jnp.float32)
    # weighed and summed on the vector unit in float32: a matrix product
    # would round the weights to bfloat16 on their way into the MXU.  The
    # added nought turns a negative zero (a negative weight times a dead
    # head) into the positive one, so that the two selections below, which
    # compare numbers and bit patterns, order the same.
    scores = jnp.sum(w_idx.astype(jnp.float32)[..., None]
                     * jax.nn.relu(dots), axis=2) + 0.0
    seen = jnp.arange(k_idx.shape[-1])[None, None, :] <= q_pos[:, :, None]
    if idle is not None:
        seen &= ~idle[:, None, None]
    return jnp.where(seen, scores, -jnp.inf)


def _ordered_bits(scores: Array) -> Array:
    """float32 -> uint32 whose unsigned order is the numbers' order."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    sign = jnp.uint32(0x80000000)
    return jnp.where(bits >= sign, ~bits, bits | sign)


def select_mask(scores: Array, top_k: int) -> Array:
    """``[B, S, T]`` bool: each query's ``top_k`` largest scores, a tie at
    the threshold going to the lower slots, less what is ``-inf``.

    The threshold (the ``top_k``-th largest) is found bit by bit from the
    top: a bit stays set if at least ``top_k`` scores lie at or above the
    pattern so far.  Thirty-two passes that compare and count; no sort."""
    T = scores.shape[-1]
    seen = scores > -jnp.inf
    if top_k >= T:
        return seen
    keys = _ordered_bits(scores)

    def step(i, prefix):
        trial = prefix | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
        enough = jnp.sum(keys >= trial[..., None], axis=-1) >= top_k
        return jnp.where(enough, trial, prefix)

    thr = jax.lax.fori_loop(0, 32, step,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
    at_or_above = keys >= thr[..., None]

    def with_ties(_):
        above = keys > thr[..., None]
        room = top_k - jnp.sum(above, axis=-1, keepdims=True)
        tied = keys == thr[..., None]
        return above | (tied & (jnp.cumsum(tied, axis=-1) <= room))

    exact = jnp.all(jnp.sum(at_or_above, axis=-1) == top_k)
    chosen = jax.lax.cond(exact, lambda _: at_or_above, with_ties, None)
    return chosen & seen


def select_slots(scores: Array, top_k: int) -> Tuple[Array, Array]:
    """``(slots [B, S, k], valid [B, S, k])``: each query's ``k =
    min(top_k, T)`` best slots (``lax.top_k``: of equal scores the lower
    slot first) and which of them are live (a query with fewer than ``k``
    live keys is handed dead slots too, marked not valid)."""
    B, S, T = scores.shape
    # two axes: XLA sorts [B, 1, T] at a quarter of the rate of [B, T]
    # (one query a sublane tile; PERF.md section 6, PR 33)
    vals, slots = jax.lax.top_k(scores.reshape(B * S, T), min(top_k, T))
    return slots.reshape(B, S, -1), vals.reshape(B, S, -1) > -jnp.inf


# Slab slots a masked pass streams in the time the gather path takes over
# one kept key (its sort, its gathered K and V rows, its attention).  On a
# v5e, 16 rows of 20,481 slots of 4 bf16 heads of 128 and 2,048 keys a
# query, timed alone: one query a row 1.09 ms gathered, 1.04 ms masked; two
# 2.14 against 1.05 — a ratio of ten.  Inside a round the draft's single
# queries did better gathered (a round of 19.2 ms against 21.3 with both
# models masked: the masked pass's float32 scores crowd the rest of the
# program), so the gather pays a little earlier than alone (PERF.md
# section 6, PR 33).  A kernel that reads the selected rows in place is what
# would change the ratio.
GATHER_COST = 8


def gathers(S: int, T: int, top_k: int) -> bool:
    """Whether a chunk of ``S`` queries a row against ``T`` slots gathers
    its keys (the gathered rows, at what a gathered row costs, come to less
    than the slab's) or masks."""
    return S * top_k * GATHER_COST < T


# -- the admission's masked attention: a Pallas kernel -----------------------

# Key slots a block: a multiple of the 128 lanes that divides the slab.
MASK_BLOCK_K = 1024
# The shortest chunk worth the kernel (an admission's; a round's few
# queries stay with XLA, whose masked pass they do not strain).
MASK_MIN_CHUNK = 128


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def mask_block_k(T: int) -> Optional[int]:
    """Key slots a block for a slab of ``T``: ``MASK_BLOCK_K`` or, for a
    shorter slab, the slab; ``None`` where whole blocks of 128 lanes do not
    tile it (the kernel reads no ragged block)."""
    block = min(MASK_BLOCK_K, T)
    return block if T % block == 0 and block % 128 == 0 else None


def why_not_masked(q, k) -> Optional[str]:
    """The reason :func:`masked_attention` does not apply to this call, or
    ``None``; from what the call can see (shapes, dtype, mesh, backend)."""
    from rocket_tpu.parallel.context import current_mesh

    S, D = q.shape[1], q.shape[3]
    if S < MASK_MIN_CHUNK or S % 32:
        return f"S={S}"
    if D % 128:
        return f"D={D}"
    if k.dtype not in (jnp.bfloat16, jnp.float32):
        return str(k.dtype)
    if mask_block_k(k.shape[1]) is None:
        return f"T={k.shape[1]}"
    mesh = current_mesh()
    if mesh is not None and mesh.devices.size > 1:
        return "mesh"
    # last, so that a CPU run names what would keep the kernel off a TPU too
    return None if _on_tpu() else "backend"


def _masked_kernel(live_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale: float, G: int):
    b, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j < live_ref[b])
    def _compute():
        keep = mask_ref[0].astype(jnp.float32) > 0.5          # [S, block]
        k, v = k_ref[0, 0], v_ref[0, 0]
        for g in range(G):          # the query heads of this KV head
            s = jax.lax.dot_general(
                q_ref[0, g], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(keep, s, MASK_VALUE)
            m_prev, l_prev = m_ref[g][:, :1], l_ref[g][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            correction = jnp.exp(m_prev - m_new)
            l_new = correction * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[g] = acc_ref[g] * correction + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[g] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        for g in range(G):
            l_final = l_ref[g][:, :1]
            safe_l = jnp.where(l_final == 0.0, 1.0, l_final)
            o_ref[0, g] = (acc_ref[g] / safe_l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale",))
def masked_attention(q: Array, k: Array, v: Array, mask: Array,
                     live_slots: Array, scale: Optional[float] = None
                     ) -> Array:
    """``dot_attention(q, k, v, causal=False, key_mask=mask)`` for a chunk
    of an admission: ``q`` ``[B, S, H, D]``, ``k``/``v`` ``[B, T, KV, D]``,
    ``mask`` ``[B, S, T]`` bool (it holds causality), ``live_slots``
    ``[B]``: slots at or past it are masked for every query of the row (the
    chunk's last position + 1), and their key blocks are neither fetched
    nor computed.

    A grid of rows x KV heads x key blocks; a step holds the ``G`` query
    heads of one KV head (``[G, S, D]``), one block of that head's keys and
    values and the mask's ``[S, block]`` (int8), and runs the online
    softmax a query head at a time; scores, maximum, normaliser and
    accumulator are float32, probabilities are cast to V's type for p.V as
    ``dot_attention`` does.  K and V are transposed to ``[B, KV, T, D]``
    on the way in (a copy of the chunk's slab: an eighth of a millisecond
    against the milliseconds the masked scores cost).  The kernel's name
    carries ``S`` and ``T``, which its cost is counted from.  Off-TPU it
    runs in interpret mode."""
    B, S, H, D = q.shape
    T, KV = k.shape[1:3]
    G = _group_size(H, KV)
    block = mask_block_k(T)
    scale = D ** -0.5 if scale is None else scale
    nk = T // block
    live = jnp.clip((live_slots.astype(jnp.int32) + block - 1) // block,
                    1, nk)

    def kv_map(b, h, j, live_ref):
        return b, h, jnp.minimum(j, live_ref[b] - 1), 0

    def mask_map(b, h, j, live_ref):
        return b, 0, jnp.minimum(j, live_ref[b] - 1)

    out = pl.pallas_call(
        functools.partial(_masked_kernel, scale=scale, G=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, KV, nk),
            in_specs=[
                pl.BlockSpec((1, G, S, D), lambda b, h, j, _: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block, D), kv_map),
                pl.BlockSpec((1, 1, block, D), kv_map),
                pl.BlockSpec((1, S, block), mask_map),
            ],
            out_specs=pl.BlockSpec((1, G, S, D),
                                   lambda b, h, j, _: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, S, D), jnp.float32),
                pltpu.VMEM((G, S, 128), jnp.float32),
                pltpu.VMEM((G, S, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=not _on_tpu(),
        name=f"select_attention_s{S}_t{T}",
    )(live, q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
      v.transpose(0, 2, 1, 3), mask.astype(jnp.int8))
    return out.transpose(0, 2, 1, 3)


def selected_attention(q: Array, k: Array, v: Array, scores: Array,
                       q_pos: Array, top_k: int,
                       idle: Optional[Array] = None) -> Tuple[Array, Array]:
    """Attention of ``q`` ``[B, S, H, D]`` (at positions ``q_pos`` ``[B,
    S]``, consecutive a row; slot == position) over the ``top_k`` keys of
    ``k``/``v`` ``[B, T, KV, D]`` that ``scores`` ``[B, S, T]`` (from
    :func:`index_scores`, dead slots ``-inf``) ranks first for each query.
    Returns ``(out [B, S, H, D], kept [B, S])``, ``kept`` the keys each
    query attended.  ``idle`` (``[B]`` bool, optional) marks rows whose
    output the caller drops (their scores are all ``-inf``).

    Gathers where :func:`gathers` says the gathered rows cost less than
    the slab, masks otherwise (an admission's chunk; a round's chunk
    against a slab under ``GATHER_COST`` times its kept keys; a ``top_k``
    that covers the slab, where the mask is the causal one and the result
    bit for bit plain attention's).  Under the mask a chunk long enough
    goes through :func:`masked_attention` where :func:`why_not_masked`
    finds no reason against it, and a round's chunk through the decode
    kernel where its gate (``ops.decode_attention.why_not``, the
    configuration's ``attention`` not asked) finds none: there a query
    that chose nothing (an idle row's) reads zeros, where
    ``dot_attention`` averages the slab."""
    B, S, H, D = q.shape
    if gathers(S, k.shape[1], top_k):
        slots, valid = select_slots(scores, top_k)             # [B, S, k]
        n = slots.shape[-1]
        rows = jax.vmap(lambda cache, at: cache[at])
        flat = slots.reshape(B, S * n)
        k_sel = rows(k, flat).reshape((B * S, n) + k.shape[2:])
        v_sel = rows(v, flat).reshape((B * S, n) + v.shape[2:])
        out = dot_attention(
            q.reshape(B * S, 1, H, D), k_sel, v_sel, causal=False,
            kv_mask=valid.reshape(B * S, n))
        return out.reshape(B, S, H, v.shape[-1]), jnp.sum(valid, axis=-1)
    chosen = select_mask(scores, top_k)
    if why_not_masked(q, k) is None:
        # no query of the chunk sees a slot past its last query's position
        out = masked_attention(q, k, v, chosen, q_pos[:, -1] + 1)
    elif decode_attention.why_not(q, k, masked=True) is None:
        out = decode_attention.decode_attention(
            q, k, v, q_pos[:, 0], idle=idle, key_mask=chosen)
    else:
        out = dot_attention(q, k, v, causal=False, key_mask=chosen)
    return out, jnp.sum(chosen, axis=-1)
