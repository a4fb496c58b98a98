"""Decode attention over a KV cache — a Pallas TPU kernel that reads, for
each row, only the key blocks that row has written.

A decode round attends a handful of new queries per row (one draft token,
or the ``n_draft + 1`` tokens of a verify chunk) against that row's whole
cache slab ``[T, KV, D]``, of which only the first ``q_offset + S`` slots
hold anything a query may see.  ``dot_attention`` streams all ``T`` slots
of every row and masks; XLA cannot skip by row.  Here the rows' lengths
ride as scalar-prefetch operands, the key axis is cut into blocks, and a
block outside a row's live range is neither fetched (its ``index_map``
names a block already resident, so no DMA is issued) nor computed (the
body runs under ``pl.when``).  A row the caller marks ``idle`` (a finished
row of a serving batch) has length zero: a grid step a block and nothing
else.  The grain is coarse: a block is ``BLOCK_BYTES`` of keys, HALF a
4,100-slot slab of 8 bfloat16 heads of 128, so a row streams at least half
its slab however little it holds; what the kernel saves today is the dead
half of the rows that fit in one block, the idle rows, and XLA's masked
float32 scores.  The chip prefers a sixth of that block (``BLOCK_BYTES``).

A ``key_mask`` (``[B, S, T]``, True = attend: a selection of keys, as
``ops.select_attention`` makes) narrows what each query sees further.  It
is read a block at a time beside K and V, by the same index map, so a dead
block fetches no mask either; a masked call's block is
``MASKED_BLOCK_BYTES`` of keys, the size the kernel is fastest at alone.

The caches are read as stored.  On a TPU a ``[B, T, KV, D]`` leaf is tiled
over its last two axes, so ``[B, T, KV*D]`` would be a copy of the leaf; the
view that is free is ``[B, T*KV, D]`` (row ``t * KV + h``) where a slot's
heads fill whole tiles (Mistral's 8 bfloat16 ones).  A leaf of fewer heads
is laid out a ``(KV, 128)`` tile a slot (Keye's 4), and there that view is
a copy too: a call with a key mask reads the leaf itself.  Either way a
32-bit word holds one number of float32 or two of bfloat16 — the same ``d``
of two neighbouring heads — so the kernel loads, with a stride of ``KV /
pack`` words, the rows of ``pack`` heads at a time (``pack`` = 1 or 2), as
a ``[pack * block, D]`` matrix whose row ``t * pack + e`` is head ``e`` of
the group at slot ``t``.  The group's queries (``G`` heads x ``S`` positions of
each of its heads) score against all of it; a mask keeps each query to its
own head's rows.  K and V are never repeated, transposed or converted.
Scores, running max, normaliser and accumulator are float32; probabilities
are cast to V's dtype for p.V, as ``dot_attention`` does; the mask value is
finite.  Off-TPU the kernel runs in interpret mode, so the CPU tests cover
its logic.

:func:`cached_attention` is what ``models.transformer.Attention`` calls:
the kernel where what it can see says the kernel applies (backend, dtype,
shapes, mesh), ``dot_attention`` otherwise — each choice counted at trace
time, ``attention/decode/kernel`` or ``attention/decode/fallback`` with
the reason, so a compiled round that lacks the kernel is counted.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rocket_tpu.ops.attention import _group_size
from rocket_tpu.ops.flash import MASK_VALUE

# The longest chunk the kernel takes: a round's (one draft token, or a
# verify chunk of n_draft + 1).  A prefill chunk is flash's or dot's.
MAX_CHUNK = 8
# Bytes of keys a block (and as many of values): 3,072 slots of 8 bfloat16
# heads of 128, 768 of 32, 1,536 of 8 float32 ones.  Coarse on purpose, and
# the one constant to change: alone, the kernel is fastest at 1 MiB (512 of
# those slots; it streams at the same rate from 0.5 MiB up and a finer
# block wastes fewer slots past a frontier) — PERF.md section 6, PR 31, and
# section 7 for what has to land before a finer block can.
BLOCK_BYTES = 6 * 1024 * 1024
# A call with a key mask is no Mistral cell's: its block is the size the
# kernel streams fastest at alone (PERF.md section 6: the kernel-alone sweeps).
MASKED_BLOCK_BYTES = 1024 * 1024
# A block is never cut finer than this many slots for the sake of VMEM:
# a cache whose heads are that wide keeps ``dot_attention`` (``vmem``).
MIN_BLOCK_K = 128
# What Mosaic may use (a v5e core has 128 MiB), and what ``vmem_bytes`` may
# reckon of it: the rest is the compiler's own temporaries.
VMEM_LIMIT = 64 * 1024 * 1024
VMEM_BUDGET = 48 * 1024 * 1024


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pack(dtype, kv_heads: int) -> Optional[int]:
    """Heads a 32-bit word of the cache holds, if the kernel can read
    them: 1 (float32) or 2 (bfloat16, an even number of KV heads)."""
    pack = 4 // jnp.dtype(dtype).itemsize
    return pack if pack in (1, 2) and kv_heads % pack == 0 else None


def _query_rows(S: int, G: int) -> int:
    """Query rows of a KV head, padded to the bf16 sublane tile."""
    return -(-(S * G) // 16) * 16


def vmem_bytes(block_k: int, S: int, G: int, KV: int, D: int,
               itemsize: int, masked: bool = False) -> int:
    """What a grid step holds in VMEM, reckoned from above: the K and V
    blocks, each twice (the next is fetched while this one is read); one
    group's keys and values as words and as numbers; the mask's index
    arrays, the scores and the probabilities, ``[pack * rows, pack *
    block_k]`` of 32 bits each; with a key mask, its ``[S, block_k]``
    block twice as int8 (32 rows a tile), once as float32 with each
    column repeated for the heads of a word and once spread over the
    query rows."""
    pack = 4 // itemsize
    buffers = 4 * block_k * KV * D * itemsize
    group = 4 * pack * block_k * D * itemsize
    scores = 8 * (pack * _query_rows(S, G)) * (pack * block_k) * 4
    if masked:
        scores += (2 * 32 * block_k + 4 * max(S, 8) * pack * block_k
                   + 4 * pack * _query_rows(S, G) * pack * block_k)
    return buffers + group + scores


def block_k_for(q, k_cache, masked: bool = False) -> Optional[int]:
    """Key slots a block for this call (``q`` and ``k_cache`` are read for
    shape and dtype alone; ``masked``: the call has a key mask):
    ``BLOCK_BYTES`` of keys (``MASKED_BLOCK_BYTES`` with a mask), in whole
    128 slots, fewer while ``vmem_bytes`` is over ``VMEM_BUDGET``; the
    whole slab (rounded up to the 16-row bf16 tile) when that is shorter;
    ``None`` when even ``MIN_BLOCK_K`` slots do not fit.  The last block
    may be ragged: slots past the slab are masked, never read as values."""
    S, H = q.shape[1:3]
    T, KV, D = k_cache.shape[1:]
    itemsize = jnp.dtype(k_cache.dtype).itemsize
    size = MASKED_BLOCK_BYTES if masked else BLOCK_BYTES
    block = size // (KV * D * itemsize) // 128 * 128
    if masked:       # the finer size is a preference: VMEM is the limit
        block = max(block, MIN_BLOCK_K)
    while block >= MIN_BLOCK_K and vmem_bytes(
            block, S, H // KV, KV, D, itemsize, masked) > VMEM_BUDGET:
        block -= 128
    if block < MIN_BLOCK_K:
        return None
    # a shorter slab is one block, of whole bf16 tiles (with a mask, of
    # whole 128 lanes: the mask's block is a row of slots)
    return min(block, -(-T // (128 if masked else 16)) * (128 if masked else 16))


def live_blocks(lengths, starts, block_k: int):
    """``(first, count)`` of the key blocks a row's queries can see:
    blocks ``first .. first + count - 1`` hold slots ``starts ..
    lengths - 1``; ``count`` is 0 for a row of length 0 (whose ``starts``
    is 0).  Shared by the wrapper, the index maps and the kernel body."""
    first = starts // block_k
    count = (lengths + block_k - 1) // block_k - first
    return first, jnp.maximum(count, 0)


def _repeat_lanes(x, n: int):
    """``[R, W]`` -> ``[R, n * W]``, each column ``n`` times over, by lane
    gathers a vreg (128 lanes) at a time; ``W`` is at most 64 or whole
    128s."""
    R, W = x.shape
    width, out = min(128, W), min(128, n * W)   # a source, an output vreg
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, out), 1)
    cols = []
    for v in range(n * W // out):
        first = v * out // n                    # its first source column
        base = first // width * width
        cols.append(jnp.take_along_axis(
            x[:, base:base + width], first - base + lane // n, axis=1,
            mode="promise_in_bounds"))
    return jnp.concatenate(cols, axis=1) if len(cols) > 1 else cols[0]


def _kernel(len_ref, lo_ref, src_ref, q_ref, k_ref, v_ref, *refs,
            scale: float, window, S: int, G: int, pack: int, rows: int,
            block_k: int, n_slots: int, masked: bool):
    if masked:
        mask_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    groups, D = acc_ref.shape[0], acc_ref.shape[2]
    length = len_ref[b]
    first, count = live_blocks(length, lo_ref[b], block_k)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(jnp.logical_and(j >= first, j < first + count))
    def _compute():
        # One mask for every group.  Query row e * rows + s * G + g is
        # head e of the group at position (length - S) + s; key row
        # t * pack + e' is head e' at slot j * block_k + t.
        shape = (pack * rows, pack * block_k)
        r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        c = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        head = (r >= rows).astype(jnp.int32)       # pack is 1 or 2
        r = r - head * rows
        s_idx = jnp.zeros_like(r)
        for i in range(1, S):        # r // G without a vector division
            s_idx = s_idx + (r >= i * G).astype(jnp.int32)
        q_pos = (length - S) + s_idx
        k_pos = j * block_k + (c >> (pack - 1))
        mask = k_pos <= q_pos
        if window is not None:
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
        if pack > 1:
            mask = jnp.logical_and(mask, (c & 1) == head)
        if masked:
            # the block's [S, block_k] selection, a slot's column once a
            # head of the word (as the keys' rows), spread over each
            # position's rows
            chosen = mask_ref[0].astype(jnp.float32)
            if pack > 1:
                chosen = _repeat_lanes(chosen, pack)
            chosen = chosen > 0.5
            keep = jnp.logical_and(s_idx == 0, chosen[:1])
            for i in range(1, S):
                keep = jnp.logical_or(
                    keep, jnp.logical_and(s_idx == i, chosen[i:i + 1]))
            mask = jnp.logical_and(mask, keep)
        tail = n_slots % block_k     # slots of the ragged last block
        if tail and masked:
            # what lies past the slab is not data: zeroed once, in place
            # (the leaf's rows are whole slots; a chosen slot is never
            # past the slab, so the keys there score nothing)
            @pl.when(j == nj - 1)
            def _clear():
                v_ref[0, tail:] = jnp.zeros_like(v_ref[0, tail:])
        elif tail:
            # the ragged last block: what lies past the slab is not data
            t = jax.lax.broadcasted_iota(jnp.int32, (pack * block_k, D), 0)
            in_slab = j * block_k + (t >> (pack - 1)) < n_slots
        k_words = k_ref if pack == 1 else k_ref.bitcast(jnp.uint32)
        v_words = v_ref if pack == 1 else v_ref.bitcast(jnp.uint32)
        for i in range(groups):
            if masked:               # the leaf's [1, block_k, groups, D]
                k, v = k_words[0, :, i, :], v_words[0, :, i, :]
            else:
                take = pl.ds(i, block_k, stride=groups)
                k, v = k_words[0, take, :], v_words[0, take, :]
            if pack > 1:
                k = pltpu.bitcast(k, k_ref.dtype)  # [pack * block_k, D]
                v = pltpu.bitcast(v, v_ref.dtype)
            if tail and not masked:
                v = jnp.where(in_slab, v, jnp.zeros_like(v))
            s = jax.lax.dot_general(
                q_ref[0, i], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            s = jnp.where(mask, s, MASK_VALUE)
            m_prev = m_ref[i][:, :1]
            l_prev = l_ref[i][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            correction = jnp.exp(m_prev - m_new)
            l_new = correction * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[i] = acc_ref[i] * correction + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[i] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[i] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(j == nj - 1)
    def _finish():
        for i in range(groups):
            l_final = l_ref[i][:, :1]
            safe_l = jnp.where(l_final == 0.0, 1.0, l_final)
            o_ref[0, i] = (acc_ref[i] / safe_l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "block_k"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     q_offset, *, idle=None, key_mask=None,
                     window: Optional[int] = None,
                     block_k: Optional[int] = None) -> jax.Array:
    """``dot_attention(q, k_cache, v_cache, causal=True, q_offset=...,
    window=...)`` for a short chunk of queries against a KV cache, reading
    only each row's live key blocks.

    ``q`` is ``[B, S, H, D]`` (the chunk's queries, at positions
    ``q_offset .. q_offset + S - 1``), the caches ``[B, T, KV, D]`` with
    ``H = G * KV``, float32 or (``KV`` even) bfloat16; ``q_offset`` is
    ``[B]`` (a frontier a row) or a scalar.  Slots at or past a row's
    ``q_offset + S`` are never seen, whatever they hold.  A row that
    ``idle`` (``[B]`` bool, optional) marks attends nothing and its output
    is zeros: for rows whose result the caller drops.  ``key_mask``
    (``[B, S, T]`` bool, optional) keeps each query to the keys it marks,
    besides the causal mask; a query that sees none attends nothing and
    its output is zeros (``dot_attention(causal=False, key_mask=)`` where
    the mask holds causality).  ``block_k`` (key slots a block) is
    :func:`block_k_for`'s unless a test cuts finer.

    Jitted, so that the layers of a round share one trace and one lowered
    kernel (8 + 2 calls a Mistral round: 2.4 s of every start-up else)."""
    B, S, H, D = q.shape
    _, T, KV, _ = k_cache.shape
    G = _group_size(H, KV)
    if window is not None and window < 1:
        raise ValueError(f"window={window} must be >= 1")
    pack = _pack(k_cache.dtype, KV)
    if pack is None or v_cache.dtype != k_cache.dtype:
        raise ValueError(
            f"caches of {k_cache.dtype}/{v_cache.dtype} with {KV} KV heads: "
            f"the kernel reads float32, or bfloat16 with KV even")
    groups = KV // pack
    masked = key_mask is not None
    if block_k is None:
        block_k = block_k_for(q, k_cache, masked)
        if block_k is None:
            raise ValueError(
                f"{KV} KV heads of {D} x {k_cache.dtype}: a block of "
                f"{MIN_BLOCK_K} slots does not fit the kernel's VMEM")
    nj = -(-T // block_k)
    off = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (B,))
    lengths = off + S
    # the oldest slot any query of the chunk may see
    starts = jnp.zeros_like(off) if window is None \
        else jnp.maximum(off - window + 1, 0)
    if idle is not None:
        lengths = jnp.where(idle, 0, lengths)
        starts = jnp.where(idle, 0, starts)
    # a dead step names a block that is already resident, so no DMA is
    # issued for it: a live row's own nearest live block, an idle row's
    # predecessor's last
    _, count = live_blocks(lengths, starts, block_k)
    src = jax.lax.cummax(
        jnp.where(count > 0, jnp.arange(B, dtype=jnp.int32), 0))

    def kv_map(b, j, len_ref, lo_ref, src_ref):
        r = src_ref[b]
        first, cnt = live_blocks(len_ref[r], lo_ref[r], block_k)
        last = jnp.maximum(first + cnt - 1, first)
        return r, jnp.where(r == b, jnp.clip(j, first, last), last), 0

    rows = _query_rows(S, G)
    # [B, S, KV, G, D] -> [B, KV, S*G, D]: a KV head's query rows together,
    # then the heads of a group one after the other
    qg = q.reshape(B, S, KV, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KV, S * G, D).astype(k_cache.dtype)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows - S * G), (0, 0))) \
        .reshape(B, groups, pack * rows, D)
    q_spec = pl.BlockSpec((1, groups, pack * rows, D),
                          lambda b, j, *_: (b, 0, 0, 0))
    if masked:
        # the caches as stored; the mask as int8 (what a ragged block
        # holds past the slab is past every query: causally masked)
        def mask_map(*args):
            r, block, _ = kv_map(*args)
            return r, 0, block

        kv_spec = pl.BlockSpec((1, block_k, KV, D),
                               lambda *a: kv_map(*a) + (0,))
        in_specs = [q_spec, kv_spec, kv_spec,
                    pl.BlockSpec((1, S, block_k), mask_map)]
        operands = [qg, k_cache, v_cache, key_mask.astype(jnp.int8)]
    else:
        kv_spec = pl.BlockSpec((1, block_k * KV, D), kv_map)
        in_specs = [q_spec, kv_spec, kv_spec]
        operands = [qg, k_cache.reshape(B, T * KV, D),
                    v_cache.reshape(B, T * KV, D)]
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=D ** -0.5, window=window, S=S, G=G, pack=pack,
            rows=rows, block_k=block_k, n_slots=T, masked=masked),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, nj),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((groups, pack * rows, D), jnp.float32),
                pltpu.VMEM((groups, pack * rows, 128), jnp.float32),
                pltpu.VMEM((groups, pack * rows, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=not _on_tpu(),
        name="selected_decode_attention" if masked else "decode_attention",
    )(lengths, starts, src, *operands)
    out = out.reshape(B, KV, rows, D)[:, :, :S * G].reshape(B, KV, S, G, D)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, S, H, D)


def why_not(q, k_cache, *, impl: Optional[str] = None,
            masked: bool = False) -> Optional[str]:
    """The reason the kernel does not apply to this call, or ``None``.
    Read from what the call can see — backend, the configuration's
    ``attention`` (``impl``; a call that passes none is not asked, as a
    selecting layer's), dtype, shapes, whether it has a key mask
    (``masked``), the active mesh — and nothing else (``q`` and ``k_cache``
    for shape and dtype alone)."""
    from rocket_tpu.parallel.context import current_mesh

    S, D = q.shape[1], q.shape[3]
    if impl not in (None, "auto", "flash"):
        return f"attention={impl}"
    if _pack(k_cache.dtype, k_cache.shape[2]) is None:
        return f"{k_cache.dtype} x {k_cache.shape[2]} KV heads"
    if D != 128:
        # the strided load of a group's words takes rows of 128 lanes
        return f"D={D}"
    if S > MAX_CHUNK:
        return f"S > {MAX_CHUNK}"
    if block_k_for(q, k_cache, masked) is None:
        return "vmem"
    mesh = current_mesh()
    if mesh is not None and mesh.devices.size > 1:
        # a Mosaic call cannot be partitioned (ops.flash._over_mesh is
        # the later answer)
        return "mesh"
    # last, so that a CPU run names what would keep the kernel off a TPU too
    return None if _on_tpu() else "backend"


def note_fallback(reason: str, q, n_slots: int) -> None:
    """Count a cached attention that keeps ``dot_attention``.  Runs at
    TRACE time (the choice is static), like ``attention/flash/fallback``."""
    from rocket_tpu.observe.trace import counter

    counter("attention/decode/fallback", 1, reason=reason, S=q.shape[1],
            D=q.shape[3], T=n_slots)


def cached_attention(q, k_cache, v_cache, q_offset, *, window, impl: str,
                     idle=None, quantized: bool = False):
    """Causal attention of a chunk against the cache it was just written
    to: :func:`decode_attention` where :func:`why_not` finds no reason
    against it, ``dot_attention`` otherwise; the choice is counted.
    ``idle`` marks rows whose result the caller drops: the kernel skips
    them, ``dot_attention`` attends them like any other.  ``quantized``
    says the caches handed in were dequantized from int8 pages (the
    payload the kernel would have to read is not what it is given)."""
    from rocket_tpu.observe.trace import counter
    from rocket_tpu.ops.attention import dot_attention

    reason = "int8" if quantized else why_not(q, k_cache, impl=impl)
    if reason is not None:
        note_fallback(reason, q, k_cache.shape[1])
        return dot_attention(q, k_cache, v_cache, causal=True,
                             q_offset=q_offset, window=window)
    T, KV = k_cache.shape[1:3]
    counter("attention/decode/kernel", 1, S=q.shape[1], G=q.shape[2] // KV,
            T=T, block_k=block_k_for(q, k_cache))
    return decode_attention(q, k_cache, v_cache, q_offset, idle=idle,
                            window=window)
