"""Building-block layers with logical-axis partitioning and optional LoRA.

The reference has no model zoo (models are user torch modules,
``rocket/core/module.py:50-60``); these layers exist so the TPU build's
model families (LeNet/ResNet/ViT/transformer LMs) ship with GSPMD sharding
annotations built in.  Parameters carry *logical* axis names via
``nn.with_partitioning``; :class:`~rocket_tpu.parallel.sharding.ShardingRules`
maps them onto mesh axes at materialization (so the same model runs on one
chip or a tensor/fsdp-sharded pod — only the rules change).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

Axes = Tuple[Optional[str], ...]


def _init(fn, *logical: Optional[str]):
    return nn.with_partitioning(fn, logical)


def image_input(x: jax.Array, dtype: Any = None) -> jax.Array:
    """Cast an image batch leaf to the model's compute dtype.

    ``dtype=None`` (no policy threaded): raw integer images become f32,
    floats keep their dtype.  With a policy compute dtype (the Module clones
    vision models with ``dtype=policy.compute_dtype``), both integer and
    float images land in it — so uint8 loaders get honest bf16 too."""
    if dtype is None:
        dtype = jnp.float32 if jnp.issubdtype(x.dtype, jnp.integer) else x.dtype
    return x.astype(dtype)


class RMSNorm(nn.Module):
    """Root-mean-square layer norm (Llama-family norm)."""

    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", _init(nn.initializers.ones_init(), "norm"), (x.shape[-1],)
        )
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(var + self.eps).astype(x.dtype)
        return y * scale.astype(x.dtype)


class PDense(nn.Module):
    """Partitioned dense layer with optional fused LoRA adapter.

    ``logical_axes`` names the kernel dims, e.g. ``('embed', 'mlp')``.
    When ``lora_rank > 0`` a frozen-base + trainable-adapter decomposition
    is added: ``y = x W + (alpha/r) (x A) B`` with A, B under the
    ``'lora'`` param prefix so an optax mask can train adapters only
    (see :func:`rocket_tpu.models.lora.lora_mask`).
    """

    features: int
    logical_axes: Axes = (None, None)
    use_bias: bool = False
    dtype: Any = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()
    lora_rank: int = 0
    lora_alpha: float = 16.0
    # Inference-only W8A16: the kernel lives as int8 + per-output-channel
    # scale (ops.quant.quantize_params produces the layout from trained
    # weights) and decode-shaped matmuls read int8 HBM via the pallas
    # kernel — the bandwidth that bounds KV-cache decode is halved.
    weights_int8: bool = False

    @nn.compact
    def __call__(self, x):
        in_dim = x.shape[-1]
        if self.weights_int8:
            from rocket_tpu.ops.quant import int8_matmul

            kernel_q = self.param(
                "kernel_q",
                _init(nn.initializers.zeros_init(), *self.logical_axes),
                (in_dim, self.features),
                jnp.int8,
            )
            kernel_scale = self.param(
                "kernel_scale",
                _init(nn.initializers.ones_init(), self.logical_axes[-1]),
                (self.features,),
                jnp.float32,
            )
            y = int8_matmul(x, kernel_q, kernel_scale)
        else:
            kernel = self.param(
                "kernel",
                _init(self.kernel_init, *self.logical_axes),
                (in_dim, self.features),
            )
            y = jnp.einsum("...d,df->...f", x, kernel.astype(x.dtype))
        if self.lora_rank > 0:
            a = self.param(
                "lora_a",
                _init(nn.initializers.normal(0.02), self.logical_axes[0], None),
                (in_dim, self.lora_rank),
            )
            b = self.param(
                "lora_b",
                _init(nn.initializers.zeros_init(), None, self.logical_axes[1]),
                (self.lora_rank, self.features),
            )
            scaling = self.lora_alpha / self.lora_rank
            y = y + scaling * jnp.einsum(
                "...d,dr,rf->...f", x, a.astype(x.dtype), b.astype(x.dtype)
            )
        if self.use_bias:
            bias = self.param(
                "bias",
                _init(nn.initializers.zeros_init(), self.logical_axes[-1]),
                (self.features,),
            )
            y = y + bias.astype(x.dtype)
        return y


class Embed(nn.Module):
    """Token embedding, shardable over ``('vocab', 'embed')``; ``attend``
    reuses the table as a tied LM head."""

    vocab_size: int
    features: int
    dtype: Any = None  # None = the table's own dtype (the policy casts it)
    # Inference-only: int8 table + per-vocab-row scale. The row scale
    # serves both directions of tying — rows are the output channels of
    # ``attend`` (the LM head) and the units of the token gather.
    # dtype=None resolves to bf16 on this path (there is no float table
    # whose dtype could serve as "its own" — int8 weights exist FOR the
    # bf16 decode pipeline); pass dtype=f32 explicitly to keep an
    # f32-compute residual stream.
    weights_int8: bool = False

    def setup(self):
        if self.weights_int8:
            self.embedding_q = self.param(
                "embedding_q",
                _init(nn.initializers.zeros_init(), "vocab", "embed"),
                (self.vocab_size, self.features),
                jnp.int8,
            )
            self.embedding_scale = self.param(
                "embedding_scale",
                _init(nn.initializers.ones_init(), "vocab"),
                (self.vocab_size,),
                jnp.float32,
            )
            return
        self.embedding = self.param(
            "embedding",
            _init(nn.initializers.normal(0.02), "vocab", "embed"),
            (self.vocab_size, self.features),
        )

    def __call__(self, tokens):
        if self.weights_int8:
            dt = self.dtype if self.dtype is not None else jnp.bfloat16
            if self._vocab_sharded():
                # same reasoning as the f32 branch below: a gather from a
                # vocab-sharded table forces a full rematerialization, so
                # route through the one-hot matmul (dequant feeds the dot;
                # the sharded case trades the int8 bandwidth win for a
                # correct distributed layout)
                from rocket_tpu.ops.quant import dequantize_int8

                table = dequantize_int8(
                    self.embedding_q, self.embedding_scale, axis=1, dtype=dt
                )
                one_hot = jax.nn.one_hot(tokens, self.vocab_size, dtype=dt)
                return one_hot @ table
            # Gathering B*S int8 rows + scales is negligible traffic; the
            # dequant happens on the gathered slice, never the full table.
            rows = jnp.asarray(self.embedding_q)[tokens].astype(dt)
            s = jnp.asarray(self.embedding_scale)[tokens].astype(dt)
            return rows * s[..., None]
        # The precision policy casts params to the compute dtype before
        # apply, so the table's dtype IS the compute dtype — pinning f32
        # here would silently upcast the whole residual stream (every
        # downstream PDense follows activation dtype).
        table = self.embedding
        if self.dtype is not None:
            table = jnp.asarray(table, self.dtype)
        if self._vocab_sharded():
            # One-hot matmul instead of gather: a gather from a
            # vocab-sharded table forces XLA into a full rematerialization
            # (replicate-then-reshard); the matmul shards cleanly and rides
            # the MXU — the standard TPU embedding trick.
            one_hot = jax.nn.one_hot(tokens, self.vocab_size, dtype=table.dtype)
            return one_hot @ table
        # asarray: host-restored (numpy) params + traced token indices
        # would otherwise route through numpy's __array__ on the tracer.
        return jnp.asarray(table)[tokens]

    def _vocab_sharded(self) -> bool:
        from rocket_tpu.parallel.context import current_mesh, current_rules

        mesh = current_mesh()
        if mesh is None:
            return False
        axes = current_rules().table().get("vocab")
        if axes is None:
            return False
        if isinstance(axes, str):
            axes = (axes,)
        size = 1
        for axis in axes:
            size *= mesh.shape.get(axis, 1)
        return size > 1

    def attend(self, x):
        if self.weights_int8:
            from rocket_tpu.ops.quant import dequantize_int8, int8_matmul

            if self._vocab_sharded():
                # mirror __call__: a vocab-sharded table cannot feed the
                # pallas kernel (pallas_call won't partition over the
                # sharded vocab rows) — dequant + einsum lets GSPMD
                # shard the LM-head matmul instead (ADVICE r4)
                table = dequantize_int8(
                    self.embedding_q, self.embedding_scale, axis=1,
                    dtype=x.dtype,
                )
                return jnp.einsum("...d,vd->...v", x, table)
            # nk_layout: the table's natural [vocab, embed] IS [N, K]
            return int8_matmul(
                x, self.embedding_q, self.embedding_scale, nk_layout=True
            )
        return jnp.einsum(
            "...d,vd->...v", x, jnp.asarray(self.embedding, x.dtype)
        )


def rotary_embedding(
    positions: jax.Array, head_dim: int, theta: float = 10000.0,
    dtype=jnp.float32, mrope_section: Optional[Sequence[int]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for RoPE; positions ``[B, S]`` -> ``[B, S, 1, D/2]``.

    ``mrope_section`` (multimodal RoPE) cuts the ``D/2`` rotary frequencies
    into three contiguous runs, each turned by a position stream of its
    own: ``positions`` is then ``[3, B, S]`` (temporal, height, width) and
    frequency ``i`` of run ``r`` turns by ``positions[r]``.  Positions of
    rank 2 stand for three equal streams (text), which is plain RoPE: the
    same products in the same order, so the tables are bit for bit those
    of the call without ``mrope_section``."""
    freqs = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if mrope_section is not None and positions.ndim == 3:
        if positions.shape[0] != 3 or sum(mrope_section) != head_dim // 2 \
                or len(mrope_section) != 3:
            raise ValueError(
                f"mrope_section {tuple(mrope_section)} needs three position "
                f"streams and runs that sum to {head_dim // 2} frequencies; "
                f"got positions {tuple(positions.shape)}")
        by_stream = positions.astype(jnp.float32)[..., None] * freqs
        edges = [sum(mrope_section[:r]) for r in range(4)]
        angles = jnp.concatenate(                        # [B, S, D/2]
            [by_stream[r, ..., edges[r]:edges[r + 1]] for r in range(3)],
            axis=-1)
    else:
        angles = positions.astype(jnp.float32)[..., None] * freqs
    return (
        jnp.cos(angles)[:, :, None, :].astype(dtype),
        jnp.sin(angles)[:, :, None, :].astype(dtype),
    )


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate pairs (split-halves convention) of ``[B, S, H, D]``."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)
