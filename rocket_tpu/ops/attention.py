"""Attention implementations — the framework's hot op.

The reference contains no attention at all (models are user-supplied,
SURVEY §5.7); the TPU build makes long-context attention a first-class op
with three interchangeable implementations behind one signature, and a
fourth for the decode round:

- ``dot``   — plain einsum softmax attention (XLA-fused; baseline and the
  correctness oracle for the others).
- ``flash`` — blocked online-softmax Pallas TPU kernel
  (:mod:`rocket_tpu.ops.flash`): O(S) memory, MXU-tiled.
- ``ring``  — blockwise ring attention over the mesh's ``seq`` axis
  (:mod:`rocket_tpu.ops.ring`): sequence/context parallelism for sequences
  too long for one chip, K/V blocks rotating over ICI via ``ppermute``.
- ``decode`` — a chunk of a round's few queries against a KV cache
  (:mod:`rocket_tpu.ops.decode_attention`): a Pallas TPU kernel that reads,
  for each row, only the key blocks that row has written, the cache as
  stored.  Not an ``impl`` of :func:`attend`: ``Attention._decode_attend``
  takes it where backend, dtype, shapes and mesh allow, ``dot`` elsewhere,
  and counts the choice.

The first three take ``(q, k, v)`` shaped ``[batch, seq, heads, head_dim]``. K/V may
have fewer heads (grouped-query attention): ``dot`` contracts each KV head
against its group of query heads, so K and V are read once as they are;
``flash`` and ``ring`` still repeat K/V up to the query heads first.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array


def _group_size(num_q_heads: int, kv_heads: int) -> int:
    """Query heads per K/V head (1 = plain multi-head attention)."""
    if num_q_heads % kv_heads != 0:
        raise ValueError(f"q heads {num_q_heads} not a multiple of kv heads {kv_heads}")
    return num_q_heads // kv_heads


def _repeat_kv(k: Array, v: Array, num_q_heads: int):
    """Expand grouped K/V heads to match Q heads (GQA/MQA) — for the flash
    and ring impls; ``dot_attention`` contracts grouped instead."""
    reps = _group_size(num_q_heads, k.shape[2])
    if reps == 1:
        return k, v
    return jnp.repeat(k, reps, axis=2), jnp.repeat(v, reps, axis=2)


def dot_attention(
    q: Array,
    k: Array,
    v: Optional[Array] = None,
    *,
    v_width: Optional[int] = None,
    causal: bool = True,
    segment_ids: Optional[Array] = None,
    scale: Optional[float] = None,
    q_offset: Optional[Array] = None,
    kv_mask: Optional[Array] = None,
    window: Optional[int] = None,
    k_positions: Optional[Array] = None,
    key_mask: Optional[Array] = None,
) -> Array:
    """Reference einsum attention. Computes logits in f32 for stability
    regardless of the compute dtype (bf16 inputs stay bf16 on the matmuls —
    MXU native — with an f32 softmax accumulator, XLA's preferred pattern).

    K/V with ``KV`` heads serve ``H = G * KV`` query heads (query head ``h``
    reads KV head ``h // G``): q is viewed as ``[B, S, KV, G, D]`` and each
    KV head is contracted against its ``G`` query heads, so K and V are
    never expanded — a decode step reads the cache once, not ``G`` times.
    ``G = 1`` is plain multi-head attention through the same code.

    V's head width may differ from K's (latent attention expands keys of
    ``nope + rope`` numbers and values of fewer).  ``v=None`` with
    ``v_width`` reads the values out of the key rows themselves: each
    value is the first ``v_width`` numbers of its key (absorbed latent
    attention, where one cached row a token is both).  The probabilities
    are then contracted against the whole key row and the tail dropped,
    so the cache is read as stored and no slice of it is written.

    ``q_offset`` positions the queries at ``q_offset .. q_offset+S-1``
    within the key axis — the KV-cache decode case, where K/V span the
    whole cache (``[B, T, KV, D]``, zeros past the write frontier masked
    out causally) while q holds only the newest token(s).  A ``[B]``
    array gives each row its OWN offset (batched speculative decode:
    rows sit at different frontiers); a scalar applies to all rows.

    ``kv_mask`` (``[B, S_k]``, 1 = attend) is a key-only padding mask —
    the cross-attention case (q and k come from different sequences, so
    ``segment_ids`` cannot express it).  K and Q lengths may differ when
    it is used with ``causal=False``.  The fill value is a large finite
    negative, not ``-inf``: a fully-masked row (an all-padding dummy
    input in a wrap-around batch) then degrades to uniform weights
    instead of a batch-poisoning softmax NaN.

    ``key_mask`` (``[B, S, S_k]`` bool, True = attend) gives every query
    a mask of its own over the keys — attention that chooses its keys
    (:mod:`rocket_tpu.ops.select_attention`), whose mask holds causality
    already, so it goes with ``causal=False``.

    ``k_positions`` (``[B, S_k]`` int) gives each key slot an EXPLICIT
    sequence position instead of its array index — the rolling-KV-cache
    case, where slot ``s`` holds whatever position last wrote it (and
    ``-1``-ish negatives mean never written).  Causal/window masking
    then compares ``q_pos`` against these values; requires ``causal``.
    """
    B, S, H, D = q.shape
    if window is not None and (not causal or window < 1):
        # validate at the op itself: every entry point (direct call,
        # attend dispatch, flash fallback) must reject a window that
        # would otherwise be silently ignored or fully mask rows
        raise ValueError(
            f"window={window} requires causal=True and window >= 1"
        )
    KV = k.shape[2]
    G = _group_size(H, KV)
    scale = scale if scale is not None else D ** -0.5
    # logits are [B, KV, G, S, K]; every mask below is built without head
    # axes and broadcasts over (KV, G)
    logits = jnp.einsum(
        "bqkgd,btkd->bkgqt", q.reshape(B, S, KV, G, D), k,
        preferred_element_type=jnp.float32,
    )
    logits = logits * scale
    neg = jnp.asarray(-0.7 * jnp.finfo(jnp.float32).max, logits.dtype)
    if k_positions is not None:
        if not causal:
            raise ValueError("k_positions requires causal=True")
        # q positions: arange(S) offset per row (or shared scalar)
        q_pos = jnp.arange(S)[None, :]
        if q_offset is not None:
            off = jnp.asarray(q_offset)
            q_pos = q_pos + (off[:, None] if off.ndim == 1 else off)
        kp = k_positions[:, None, :]          # [B, 1, K]
        qp = q_pos[:, :, None]                # [B, S, 1]
        mask = (kp >= 0) & (kp <= qp)
        if window is not None:
            mask &= (qp - kp) < window
        logits = jnp.where(mask[:, None, None], logits, neg)
    elif causal:
        k_pos = jnp.arange(k.shape[1])
        if q_offset is not None and jnp.ndim(q_offset) == 1:
            # per-row offsets: mask is [B, S, K], broadcast over heads
            q_pos = jnp.arange(S)[None, :] + q_offset[:, None]
            mask = q_pos[:, :, None] >= k_pos[None, None, :]
            if window is not None:
                mask &= (q_pos[:, :, None] - k_pos[None, None, :]) < window
            logits = jnp.where(mask[:, None, None], logits, neg)
        else:
            q_pos = jnp.arange(S)[:, None]
            if q_offset is not None:
                q_pos = q_pos + q_offset
            mask = q_pos >= k_pos[None, :]
            if window is not None:
                mask &= (q_pos - k_pos[None, :]) < window
            logits = jnp.where(mask, logits, neg)
    if segment_ids is not None:
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]
        logits = jnp.where(seg_mask[:, None, None], logits, neg)
    if kv_mask is not None:
        logits = jnp.where(
            kv_mask[:, None, None, None, :].astype(bool), logits, neg
        )
    if key_mask is not None:
        logits = jnp.where(key_mask[:, None, None], logits, neg)
    if v is None:
        if v_width is None:
            raise ValueError("v=None needs v_width (values read from k)")
        weights = jax.nn.softmax(logits, axis=-1).astype(k.dtype)
        out = jnp.einsum("bkgqt,btkd->bqkgd", weights, k)[..., :v_width]
        return out.reshape(B, S, H, v_width)
    weights = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqt,btkd->bqkgd", weights, v)
    return out.reshape(B, S, H, v.shape[-1])


def attend(
    q: Array,
    k: Array,
    v: Array,
    *,
    impl: str = "auto",
    causal: bool = True,
    segment_ids: Optional[Array] = None,
    scale: Optional[float] = None,
    seq_axis: Optional[str] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    window: Optional[int] = None,
) -> Array:
    """Dispatch to an attention implementation.

    ``impl='auto'``: flash on TPU (falls back to dot where the kernel's
    tiling constraints aren't met), dot elsewhere. ``impl='ring'`` requires
    an active mesh context with a non-trivial ``seq`` axis.
    ``block_q``/``block_k`` = None uses the flash kernel's shape-aware
    measured defaults (``ops.flash.auto_blocks``).  ``window`` is
    sliding-window attention (causal only; flash and dot — the ring
    rotation schedule has no early-exit for windowed keys, so it is
    rejected rather than silently doing full-causal work).
    """
    if impl == "auto":
        impl = "flash" if q.shape[1] >= 128 and _on_tpu() else "dot"
    if impl == "dot":
        return dot_attention(
            q, k, v, causal=causal, segment_ids=segment_ids, scale=scale,
            window=window,
        )
    if impl == "flash":
        from rocket_tpu.ops.flash import flash_attention

        return flash_attention(
            q, k, v, causal=causal, segment_ids=segment_ids, scale=scale,
            block_q=block_q, block_k=block_k, window=window,
        )
    if impl == "ring":
        from rocket_tpu.ops.ring import ring_attention

        if window is not None:
            raise ValueError(
                "sliding-window attention is not supported under "
                "impl='ring' (sequence parallelism); use flash/dot"
            )
        return ring_attention(
            q, k, v, causal=causal, segment_ids=segment_ids, scale=scale,
            seq_axis=seq_axis or "seq"
        )
    raise ValueError(f"unknown attention impl {impl!r}")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"
