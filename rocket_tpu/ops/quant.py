"""Int8 weight-only quantization for bandwidth-bound decode.

KV-cache decode re-reads every weight matrix once per emitted token, so
single-chip decode throughput is HBM-bandwidth-bound (no benchmark
cell runs the int8 path yet).  Storing weights as int8 with a
per-output-channel scale halves the bytes the matmuls pull per token —
the serving-world W8A16 recipe, done the TPU way:

- :func:`quantize_int8` — symmetric per-channel quantization over the
  contraction axis;
- :func:`int8_matmul` — a pallas kernel whose HBM reads ARE int8 (the
  dequant happens in VMEM, after the bandwidth was paid).  A plain
  ``x @ (q * s)`` dequant in XLA would be hoisted out of the decode loop
  (loop-invariant code motion) and materialize full bf16 weights — the
  kernel is what makes the bandwidth win real;
- :func:`quantize_params` — rewrites a trained f32/bf16 params tree into
  the layout the ``weights_int8=True`` model expects (``kernel`` →
  ``kernel_q`` + ``kernel_scale``, ``embedding`` → ``embedding_q`` +
  ``embedding_scale``).

The reference has no quantization (or generation) path at all; this is a
TPU-native addition in the spirit of its extensibility goals.
"""

from __future__ import annotations

import functools
import warnings
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# One warning per process, not per trace: the fallback is a *performance*
# surprise (full-width dequant defeats the int8 bandwidth win), not an
# error, and decode loops retrace on shape buckets.
_warned_fallback = False


def _note_fallback(reason: str, M: int, K: int, N: int,
                   remediable: bool = True) -> None:
    """Record an ``int8_matmul`` dequant-einsum fallback.

    Runs at TRACE time (the routing branch is static on shapes), so the
    tracing counter counts compiled programs that contain the fallback —
    exactly the unit that matters, since within one program the cost
    recurs every execution.  The ``warnings.warn`` is one-shot per
    process and only fires for the *remediable* case (misaligned K, fixed
    by padding); large-M routing is by design and only counted.
    """
    from rocket_tpu.observe.trace import counter

    counter("quant/int8_matmul/fallback", 1, reason=reason, M=M, K=K, N=N)
    global _warned_fallback
    if _warned_fallback or not remediable:
        return
    _warned_fallback = True
    warnings.warn(
        f"int8_matmul fell back to dequant-einsum ({reason}; M={M}, "
        f"K={K}, N={N}): the full weight matrix is dequantized to "
        f"activation width, so the int8 HBM bandwidth saving is lost "
        f"for this matmul. Remedy: pad the "
        f"contraction dim to a multiple of 128 (e.g. vocab 50257 -> "
        f"50304, as TransformerConfig.gpt2_124m does) so the pallas "
        f"kernel can load full-K tiles.",
        stacklevel=3,
    )


def quantize_int8(w: jax.Array, axis: int = 0) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-channel int8 quantization.

    ``axis`` is the CONTRACTION axis (reduced in the matmul): the scale is
    one f32 per output channel, so dequantization commutes with the dot.
    Returns ``(q int8, scale f32)`` with ``scale.shape = w.shape`` minus
    ``axis``.
    """
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = amax / 127.0
    safe = jnp.where(scale == 0.0, 1.0, scale)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / safe), -127, 127)
    return q.astype(jnp.int8), jnp.squeeze(scale, axis)


def dequantize_int8(q: jax.Array, scale: jax.Array, axis: int = 0,
                    dtype: Any = jnp.bfloat16) -> jax.Array:
    """Inverse of :func:`quantize_int8` (used on the non-kernel paths)."""
    return (q.astype(jnp.float32)
            * jnp.expand_dims(scale, axis)).astype(dtype)


def _matvec_kernel(x_ref, q_ref, s_ref, o_ref, *, nk_layout: bool):
    w = q_ref[...].astype(jnp.bfloat16)  # int8 -> bf16 in VMEM (free);
    # the HBM transfer already happened at int8 width
    contract = ((1,), (1,)) if nk_layout else ((1,), (0,))
    acc = jax.lax.dot_general(
        x_ref[...], w, (contract, ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = (acc * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    rem = x.shape[axis] % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=("nk_layout", "block_n"))
def _int8_matmul_kernel_call(x, q, scale, nk_layout: bool, block_n: int):
    M, K = x.shape
    N = scale.shape[0]
    Mp = max(16, M + (-M) % 16)  # bf16 sublane tile
    x = _pad_to(x, Mp, 0)
    q = _pad_to(q, block_n, 0 if nk_layout else 1)
    # scale rides as a [1, N] row: Mosaic tiles a rank-1 f32 operand by
    # its block (512) while XLA lays it out in 1024-element tiles, and the
    # TPU compiler rejects the mismatch for every N.
    scale = _pad_to(scale, block_n, 0)[None, :]
    Np = scale.shape[1]
    grid = (Np // block_n,)
    if nk_layout:  # q is [N, K]
        q_spec = pl.BlockSpec((block_n, K), lambda n: (n, 0))
    else:  # q is [K, N]
        q_spec = pl.BlockSpec((K, block_n), lambda n: (0, n))
    out = pl.pallas_call(
        functools.partial(_matvec_kernel, nk_layout=nk_layout),
        grid=grid,
        in_specs=[
            pl.BlockSpec((Mp, K), lambda n: (0, 0)),
            q_spec,
            pl.BlockSpec((1, block_n), lambda n: (0, n)),
        ],
        out_specs=pl.BlockSpec((Mp, block_n), lambda n: (0, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        interpret=_interpret(),
        name="int8_matmul",
    )(x, q, scale)
    return out[:M, :N]


# Above this many rows the matmul is compute-shaped, not decode-shaped:
# the MXU-scheduled dequant-einsum path serves it better than the
# bandwidth-oriented kernel.
KERNEL_MAX_ROWS = 64


def int8_matmul(x: jax.Array, q: jax.Array, scale: jax.Array, *,
                nk_layout: bool = False, block_n: int = 512) -> jax.Array:
    """``x @ dequant(q)`` with int8 HBM reads for decode-shaped ``x``.

    ``x`` is ``[..., K]`` (leading dims flattened internally); ``q`` is
    ``[K, N]`` (or ``[N, K]`` with ``nk_layout=True`` — the natural layout
    of a tied embedding table); ``scale`` is ``[N]`` f32.  Two conditions
    route to a dequant-einsum fallback instead of the kernel: rows beyond
    :data:`KERNEL_MAX_ROWS` (prefill/training shapes are compute-bound;
    the kernel exists for the bandwidth-bound one-token-per-step decode
    loop), and ``K % 128 != 0`` (the kernel loads full-K tiles on
    128-lane boundaries) — the fallback dequantizes the FULL weight
    matrix, so a contraction dim that isn't a multiple of 128 gets no
    bandwidth saving; pad the model dims if the int8 read path matters.
    """
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    small = M <= KERNEL_MAX_ROWS
    aligned = K % 128 == 0
    if small and aligned:
        out = _int8_matmul_kernel_call(x2, q, scale, nk_layout, block_n)
    else:
        N = scale.shape[0]
        if small and not aligned:
            # Rows were decode-shaped — only the misaligned K forced the
            # fallback, which is the fixable (padding) case worth flagging.
            _note_fallback(f"K % 128 == {K % 128}", M, K, N)
        else:
            _note_fallback(f"M > KERNEL_MAX_ROWS ({M} > {KERNEL_MAX_ROWS})",
                           M, K, N, remediable=False)
        w = dequantize_int8(
            q, scale, axis=1 if nk_layout else 0, dtype=x.dtype
        )
        if nk_layout:
            w = w.T
        out = x2 @ w
    return out.reshape(*lead, out.shape[-1])


def quantize_kv_page(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-page int8 quantization for KV-cache writes.

    A "page" is one head's feature vector at one cache slot: the amax is
    taken over the LAST axis (head dim) with ``keepdims=True``, so for a
    ``[..., KV, D]`` key/value tensor the scale is ``[..., KV, 1]`` f32 —
    rank-preserving, which lets the scale ride the cache through every
    slot-indexed scatter/gather exactly like the int8 payload (the decode
    batcher's rank-4 cache-leaf discrimination sees both identically).
    Returns ``(q int8, scale f32 [..., 1])``.  All-zero pages quantize to
    zeros with scale 0 (the zero-scale guard keeps the divide finite and
    the dequant exact).
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = amax / 127.0
    safe = jnp.where(scale == 0.0, 1.0, scale)
    q = jnp.clip(jnp.round(xf / safe), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_kv_page(q: jax.Array, scale: jax.Array,
                       dtype: Any = jnp.bfloat16) -> jax.Array:
    """Inverse of :func:`quantize_kv_page` (scale broadcasts over D)."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def quantize_params(params: Any) -> Any:
    """Rewrite a trained params tree into the ``weights_int8=True`` layout.

    Every 2-D ``kernel`` leaf (PDense) becomes ``kernel_q`` (int8, per-
    output-channel over the contraction/input axis) + ``kernel_scale``;
    every ``embedding`` leaf (Embed) becomes ``embedding_q`` (per-ROW
    scale — rows are the output channels of the tied ``attend`` head and
    the units of the gather) + ``embedding_scale``.  Everything else
    (norms, biases, LoRA adapters, position tables) is left untouched —
    they are a rounding error of decode bandwidth and precision-critical.
    """
    from collections.abc import Mapping

    import flax.linen as nn

    params = nn.meta.unbox(params)  # boxed Partitioned leaves would
    # otherwise pass through silently unquantized
    if isinstance(params, Mapping) and not isinstance(params, dict):
        params = dict(params)  # FrozenDict and friends
    if not isinstance(params, dict):
        return params
    out = {}
    for name, sub in params.items():
        if name == "kernel" and hasattr(sub, "ndim") and sub.ndim == 2:
            q, s = quantize_int8(sub, axis=0)
            out["kernel_q"] = q
            out["kernel_scale"] = s
        elif name == "kernel" and hasattr(sub, "ndim") and sub.ndim > 2:
            raise ValueError(
                f"stacked kernel of rank {sub.ndim} (scan_layers layout?) "
                f"— weights_int8 supports the unrolled layout only; "
                f"re-export the checkpoint with scan_layers=False"
            )
        elif name == "embedding" and hasattr(sub, "ndim") and sub.ndim == 2:
            q, s = quantize_int8(sub, axis=1)  # per-vocab-row
            out["embedding_q"] = q
            out["embedding_scale"] = s
        else:
            out[name] = quantize_params(sub)
    return out
