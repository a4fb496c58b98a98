"""Flash attention — blocked online-softmax Pallas TPU kernel, fwd + bwd.

The attention matrix never materializes in HBM: the kernel streams K/V
blocks through VMEM, keeping a running row-max ``m``, normalizer ``l`` and
f32 output accumulator in VMEM scratch that persists across the innermost
(sequential) grid dimension — O(S) memory instead of O(S²), MXU-tiled
matmuls with f32 accumulation.  The backward pass is the standard two-kernel
split (dq; dk+dv) over the saved logsumexp, wired through ``jax.custom_vjp``
(pallas_call has no autodiff of its own).

Layout: kernels run on ``[B, H, S, D]``; the public wrapper takes the
model-side ``[B, S, H, D]`` and transposes (XLA folds the transpose into
neighboring ops).  Causal skipping: fully-masked K blocks are skipped with
``pl.when`` (half the work for causal attention); the diagonal block masks
with a large negative constant (never ``-inf`` — ``exp(-inf - -inf)`` is
NaN).

Packed sequences: ``segment_ids`` adds a block mask (query and key must
share a segment).  The q-side ids ride in the same lane-broadcast layout as
the logsumexp (``[B, S, 128]``; the kernel reads lane 0) and the k-side ids
in a sublane layout (``[B, 8, S]``; the kernel reads sublane 0), so both
respect TPU tiling without reshapes inside the kernel.

Shapes that do not meet the tiling constraints reroute to dot attention
and are COUNTED (``attention/flash/fallback``, see :func:`flash_attention`);
off-TPU the same kernels run in interpret mode, so the CPU tests cover the
kernel logic.  Under a multi-device mesh the kernel call is wrapped in
``shard_map`` over the batch and heads axes: GSPMD cannot partition a
Mosaic custom call, and JAX refuses to lower one it would have to
("Mosaic kernels cannot be automatically partitioned").
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def auto_blocks(S: int) -> tuple:
    """Shape-aware default tiling: the largest of 512/256 query rows
    and 1024/512/256 key rows that tile ``S`` exactly.  512/1024 at
    S=1024 is what ``gpt2m-train-1chip`` runs, and its
    ``train_flash_fwd_roofline``, ``train_flash_dq_roofline`` and
    ``train_flash_dkv_roofline`` metrics measure the three kernels.
    (Keep this file's line count: ``tools/hlo_hashes.py`` hashes line numbers.)
    When none divide, falls back to ``min(256, S)`` / ``min(512, S)``, so
    flash-eligible irregular shapes keep the kernel instead of
    rerouting to dot attention: ViT-B/16's S=197 runs it as one 197-row
    block, which Mosaic compiles although 197 is not a multiple of 8 and
    which matches ``dot_attention`` on a v5e (``chip_smoke.py``)."""
    bq = next((b for b in (512, 256) if S % b == 0), min(256, S))
    bk = next((b for b in (1024, 512, 256) if S % b == 0), min(512, S))
    return bq, bk


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _note_fallback(reason: str, S: int, D: int, block_q: int,
                   block_k: int) -> None:
    """Count a reroute to dot attention the way ``ops.quant`` counts its
    own.  Runs at TRACE time (the branch is static on shapes), so the
    counter counts compiled programs that lack the kernel."""
    from rocket_tpu.observe.trace import counter

    counter("attention/flash/fallback", 1, reason=reason, S=S, D=D,
            block_q=block_q, block_k=block_k)


def _block_mask(causal: bool, has_seg: bool, qi, ki, sq_ref, sk_ref,
                block_q: int, block_k: int, window=None):
    """[bq, bk] boolean mask (True = attend) or None when unmasked.

    ``window`` (requires ``causal``) keeps only the newest ``window``
    positions per query — Mistral-style sliding-window attention."""
    mask = None
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = q_pos >= k_pos
        if window is not None:
            mask = mask & (q_pos - k_pos < window)
    if has_seg:
        sq = sq_ref[0][:, :1]  # [bq, 1] (lane-broadcast layout, lane 0)
        sk = sk_ref[0][:1, :]  # [1, bk] (sublane layout, sublane 0)
        seg = sq == sk
        mask = seg if mask is None else mask & seg
    return mask


def _block_live(causal: bool, window, qi, ki, block_q: int, block_k: int):
    """Whether a (qi, ki) tile can contain any attended pair: causal
    skips tiles entirely above the diagonal; a sliding window also
    skips tiles entirely OLDER than every query's window."""
    run = True
    if causal:
        run = ki * block_k <= qi * block_q + block_q - 1
        if window is not None:
            run = jnp.logical_and(
                run,
                ki * block_k + block_k - 1 >= qi * block_q - window + 1,
            )
    return run


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, scale: float, causal: bool, has_seg: bool,
                block_q: int, block_k: int, window=None):
    if has_seg:
        q_ref, k_ref, v_ref, sq_ref, sk_ref = refs[:5]
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs[5:]
    else:
        q_ref, k_ref, v_ref = refs[:3]
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs[3:]
        sq_ref = sk_ref = None
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[:] = jnp.zeros_like(l_ref)

    # Causal: K blocks entirely above the diagonal contribute nothing;
    # a sliding window also skips blocks entirely older than the window.
    run = _block_live(causal, window, qi, ki, block_q, block_k)

    @pl.when(run)
    def _compute():
        # Matmul operands stay in the input dtype (bf16 in mixed-precision
        # runs) — the MXU's native bf16xbf16->f32 path runs ~4x the f32
        # rate on v5e; only the softmax math is f32.
        q = q_ref[0, 0]  # [bq, D]
        k = k_ref[0, 0]  # [bk, D]
        v = v_ref[0, 0]  # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk] f32
        mask = _block_mask(causal, has_seg, qi, ki, sq_ref, sk_ref,
                           block_q, block_k, window)
        if mask is not None:
            s = jnp.where(mask, s, MASK_VALUE)
        m_prev = m_ref[:, :1]  # [bq, 1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)  # [bq, bk]
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        correction = jnp.exp(m_prev - m_new)  # [bq, 1]
        l_new = correction * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * correction + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l_final = l_ref[:, :1]
        safe_l = jnp.where(l_final == 0.0, 1.0, l_final)
        o_ref[0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        # lse broadcast across the 128-lane dim (TPU tiling needs the last
        # two block dims (bq, 128) — same layout as jax's reference kernel).
        lse_ref[0, 0] = jnp.broadcast_to(
            m_ref[:, :1] + jnp.log(safe_l), lse_ref.shape[2:]
        )


def _seg_specs(block_q: int, block_k: int, kv_order: bool = False):
    """BlockSpecs for (q-side [B,S,128], k-side [B,8,S]) segment layouts."""
    if kv_order:  # grid (B, H, ki, qi)
        sq = pl.BlockSpec((1, block_q, 128), lambda b, h, ki, qi: (b, qi, 0))
        sk = pl.BlockSpec((1, 8, block_k), lambda b, h, ki, qi: (b, 0, ki))
    else:  # grid (B, H, qi, ki)
        sq = pl.BlockSpec((1, block_q, 128), lambda b, h, qi, ki: (b, qi, 0))
        sk = pl.BlockSpec((1, 8, block_k), lambda b, h, qi, ki: (b, 0, ki))
    return [sq, sk]


def _seg_layouts(seg):
    """Expand compact ``[B, S]`` f32 segment ids into the kernel layouts:
    q-side lane-broadcast ``[B, S, 128]`` and k-side sublane ``[B, 8, S]``.
    Built just before each pallas_call so only the compact form is ever a
    custom_vjp residual."""
    if seg is None:
        return None, None
    B, S = seg.shape
    sq = jnp.broadcast_to(seg[:, :, None], (B, S, 128))
    sk = jnp.broadcast_to(seg[:, None, :], (B, 8, S))
    return sq, sk


def _flash_fwd(q, k, v, seg, causal: bool, scale: float,
               block_q: int, block_k: int, window=None):
    B, H, S, D = q.shape
    has_seg = seg is not None
    sq, sk = _seg_layouts(seg)
    nq, nk = S // block_q, S // block_k
    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, has_seg=has_seg,
        block_q=block_q, block_k=block_k, window=window,
    )
    in_specs = [
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h, ki, 0)),
        pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h, ki, 0)),
    ]
    operands = [q, k, v]
    if has_seg:
        in_specs += _seg_specs(block_q, block_k)
        operands += [sq, sk]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec(
                (1, 1, block_q, 128), lambda b, h, qi, ki: (b, h, qi, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(*operands)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(*refs, scale: float, causal: bool, has_seg: bool,
               block_q: int, block_k: int, window=None):
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         sq_ref, sk_ref, dq_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, acc_ref) = refs
        sq_ref = sk_ref = None
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run = _block_live(causal, window, qi, ki, block_q, block_k)

    @pl.when(run)
    def _compute():
        # bf16 matmul operands, f32 softmax math (see _fwd_kernel note).
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]  # [bq, 1] (lane-broadcast layout)
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        p = jnp.exp(s - lse)
        mask = _block_mask(causal, has_seg, qi, ki, sq_ref, sk_ref,
                           block_q, block_k, window)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk] f32
        ds = p * (dp - delta)
        acc_ref[:] += scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale: float, causal: bool, has_seg: bool,
                block_q: int, block_k: int, window=None):
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         sq_ref, sk_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        sq_ref = sk_ref = None
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = _block_live(causal, window, qi, ki, block_q, block_k)

    @pl.when(run)
    def _compute():
        # bf16 matmul operands, f32 softmax math (see _fwd_kernel note).
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk] f32
        p = jnp.exp(s - lse)
        mask = _block_mask(causal, has_seg, qi, ki, sq_ref, sk_ref,
                           block_q, block_k, window)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        # dV += Pᵀ dO
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)
        # dK += dSᵀ Q * scale
        dk_acc[:] += scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, seg, o, lse, do, causal: bool, scale: float,
               block_q: int, block_k: int, window=None):
    B, H, S, D = q.shape
    has_seg = seg is not None
    sq, sk = _seg_layouts(seg)
    nq, nk = S // block_q, S // block_k
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, 128))

    common_in = [
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h, ki, 0)),
        pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h, ki, 0)),
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_q, 128), lambda b, h, qi, ki: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_q, 128), lambda b, h, qi, ki: (b, h, qi, 0)),
    ]
    operands = [q, k, v, do, lse, delta]
    if has_seg:
        common_in = common_in + _seg_specs(block_q, block_k)
        operands = operands + [sq, sk]
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, has_seg=has_seg,
            block_q=block_q, block_k=block_k, window=window,
        ),
        grid=(B, H, nq, nk),
        in_specs=common_in,
        out_specs=pl.BlockSpec(
            (1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=_interpret(),
        name="flash_dq",
    )(*operands)

    kv_in = [
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, ki, qi: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, 0)),
        pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, 0)),
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, ki, qi: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_q, 128), lambda b, h, ki, qi: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_q, 128), lambda b, h, ki, qi: (b, h, qi, 0)),
    ]
    kv_operands = [q, k, v, do, lse, delta]
    if has_seg:
        kv_in = kv_in + _seg_specs(block_q, block_k, kv_order=True)
        kv_operands = kv_operands + [sq, sk]
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, has_seg=has_seg,
            block_q=block_q, block_k=block_k, window=window,
        ),
        grid=(B, H, nk, nq),
        in_specs=kv_in,
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, S, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_dkv",
    )(*kv_operands)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing
# ---------------------------------------------------------------------------
# The compact [B, S] f32 segment ids are a primal arg (custom_vjp wants
# array args differentiable-typed; the cotangent is a structural zero); the
# 128x lane/sublane kernel layouts are built inside each rule so they are
# never held as fwd->bwd residuals.


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, seg, causal, scale, block_q, block_k, window):
    o, _ = _flash_fwd(q, k, v, seg, causal, scale, block_q, block_k,
                      window)
    return o


def _flash_fwd_rule(q, k, v, seg, causal, scale, block_q, block_k,
                    window):
    o, lse = _flash_fwd(q, k, v, seg, causal, scale, block_q, block_k,
                        window)
    return o, (q, k, v, seg, o, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, window, res, g):
    q, k, v, seg, o, lse = res
    dq, dk, dv = _flash_bwd(
        q, k, v, seg, o, lse, g.astype(q.dtype), causal, scale,
        block_q, block_k, window
    )
    dseg = None if seg is None else jnp.zeros_like(seg)
    return dq, dk, dv, dseg


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Flash attention on ``[B, S, H, D]`` (K/V may be GQA-grouped).

    ``segment_ids`` (``[B, S]`` int) restricts attention to same-segment
    pairs — packed multi-document batches keep the O(S) blocked kernel.
    ``window`` (requires ``causal``) is Mistral-style sliding-window
    attention: each query sees only the newest ``window`` positions, and
    K blocks entirely older than the window are SKIPPED — at long S the
    kernel's work becomes O(S·window) instead of O(S²/2).
    ``block_q``/``block_k`` default to the shape-aware measured-best
    tiling (:func:`auto_blocks`); pass explicit sizes to override.
    Reroutes to :func:`rocket_tpu.ops.attention.dot_attention` when the
    kernel's tiling constraints don't hold (S not a multiple of the block
    sizes, head_dim not a multiple of 8) and counts it under
    ``attention/flash/fallback`` with the reason.
    """
    from rocket_tpu.ops.attention import _repeat_kv, dot_attention

    B, S, H, D = q.shape
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window} requires causal=True and window >= 1"
        )
    scale = scale if scale is not None else D ** -0.5
    auto_q, auto_k = auto_blocks(S)
    block_q = min(block_q if block_q is not None else auto_q, S)
    block_k = min(block_k if block_k is not None else auto_k, S)
    if S % block_q != 0 or S % block_k != 0 or D % 8 != 0:
        _note_fallback(
            f"D % 8 == {D % 8}" if D % 8 != 0
            else f"S % blocks != 0 (S={S}, blocks {block_q}/{block_k})",
            S, D, block_q, block_k,
        )
        return dot_attention(
            q, k, v, causal=causal, segment_ids=segment_ids, scale=scale,
            window=window,
        )
    k, v = _repeat_kv(k, v, H)
    # The kernels run their matmuls in the input dtype (no internal f32
    # casts), and dot_general needs matching operand dtypes — normalize
    # mixed-precision callers to q's dtype here.
    k = k.astype(q.dtype)
    v = v.astype(q.dtype)
    seg = None if segment_ids is None else segment_ids.astype(jnp.float32)

    def kernel(q, k, v, *seg):
        # [B, S, H, D] -> [B, H, S, D] for the kernel
        qt, kt, vt = (x.swapaxes(1, 2) for x in (q, k, v))
        o = _flash(qt, kt, vt, seg[0] if seg else None, causal, scale,
                   block_q, block_k, window)
        return o.swapaxes(1, 2)

    operands = (q, k, v) if seg is None else (q, k, v, seg)
    return _over_mesh(kernel, seg is not None)(*operands)


def _over_mesh(kernel, has_seg: bool):
    """``kernel`` run per shard of the active mesh's batch and heads axes
    (the layout ``models.transformer`` constrains q/k/v to), or as-is when
    no mesh is active, the mesh is one device, or an enclosing
    ``shard_map`` already holds every axis.  Mosaic wants EVERY mesh axis
    manual, so the map takes all that are still automatic; the sequence
    stays whole per shard (sequence parallelism is ``ops.ring``)."""
    from rocket_tpu.parallel.collectives import shard_map
    from rocket_tpu.parallel.context import (
        _manual_axes,
        current_mesh,
        current_rules,
    )

    mesh = current_mesh()
    if mesh is None or mesh.devices.size == 1:
        return kernel
    auto = frozenset(mesh.axis_names) - _manual_axes()
    if not auto:
        return kernel

    def free(entry):
        axes = (entry,) if isinstance(entry, str) else tuple(entry or ())
        return tuple(a for a in axes if a in auto) or None

    batch, heads = (free(e) for e in current_rules().spec("batch", "heads"))
    spec = P(batch, None, heads, None)
    return shard_map(
        kernel,
        mesh=mesh,
        in_specs=(spec,) * 3 + ((P(batch, None),) if has_seg else ()),
        out_specs=spec,
        axis_names=auto,
        check_vma=False,
    )
