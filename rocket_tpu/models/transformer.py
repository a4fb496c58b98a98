"""Decoder-only transformer LM — the framework's flagship model family.

One configurable implementation covers the BASELINE.json ladder:

- **GPT-2 style** (``TransformerConfig.gpt2_124m()``): learned positions,
  LayerNorm, GELU MLP, tied embeddings — the "GPT-2 124M on OpenWebText"
  config.
- **Llama style** (``TransformerConfig.llama2_7b()``): RoPE, RMSNorm,
  SwiGLU, GQA, untied head — the "Llama-2 7B LoRA fine-tune" config
  (``lora_rank > 0`` adds adapters; see :mod:`rocket_tpu.models.lora`).

TPU-first design notes:

- every parameter carries logical-axis names (scaling-book recipe: embed on
  ``fsdp``, heads/mlp/vocab on ``tensor``) so the mesh rules decide between
  pure DP, ZeRO-style fsdp, tensor parallel, or combinations;
- activations are sharding-constrained at the residual stream and attention
  reshapes (``('batch', 'sequence', 'embed')``) — with a non-trivial ``seq``
  axis this IS sequence parallelism for the norms/MLPs, and attention
  switches to the ring implementation over the same axis;
- blocks can be ``remat``-ed (trade FLOPs for HBM) and ``scan``-stacked
  (one compiled block body instead of ``n_layers`` copies — compile time
  O(1) in depth, the standard big-model pattern);
- attention logits accumulate in f32 on the MXU regardless of bf16 compute
  (``ops.attention``).

- **DeepSeek-V3 style** (``mla=MLAConfig(...)``, ``experts=
  ExpertsConfig(...)``, ``first_k_dense``, ``sandwich_norm``): latent
  attention over one cached row a token, leading dense layers and then
  gated experts with a shared one, and :class:`MTPDraft`, the
  multi-token-prediction module that drafts from the target's hidden state.

Batch contract (blackboard style, reference ``module.py:139``): reads
``batch['tokens']`` (int32 ``[B, S]``; optional ``positions``,
``segment_ids``), writes ``batch['logits']`` and, when decoding,
``batch['hidden']`` (the last block's output before the final norm).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from rocket_tpu.core.attributes import Attributes
from rocket_tpu.models.layers import (
    Embed,
    PDense,
    RMSNorm,
    _init,
    apply_rope,
    rotary_embedding,
)
from rocket_tpu.models.mamba import MambaConfig, MambaMixer
from rocket_tpu.models.moe import ExpertsConfig, RoutedExperts
from rocket_tpu.ops.attention import attend, dot_attention
from rocket_tpu.parallel.context import constrain


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention's published sizes (:class:`LatentAttention`)."""

    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def cache_width(self) -> int:
        """Numbers cached a token and a layer: the latent and one rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class SelectConfig:
    """Attention that chooses its keys (:mod:`rocket_tpu.ops.
    select_attention`): an indexer of ``index_heads`` query heads of
    ``index_dim`` numbers over one cached key of ``index_dim`` a token
    scores every key a query may see, and the query attends the ``top_k``
    best alone.  ``chunk`` is how many queries of a prompt are admitted at
    a time (a tile size: it changes no result, it bounds the scores that
    are alive at once).  The cached keys are one leaf a layer,
    ``cached_index_k`` ``[rows, index_dim, slots]``: slots last, as a TPU
    lays it out for the score product."""

    index_heads: int
    index_dim: int
    top_k: int
    chunk: int = 512

    def __post_init__(self) -> None:
        if min(self.index_heads, self.index_dim, self.top_k, self.chunk) < 1 \
                or self.index_dim % 2:
            raise ValueError(
                f"SelectConfig needs positive sizes and an even index_dim "
                f"(it is rotated), got {self}")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None  # None -> n_heads (MHA)
    # Numbers a head, where the heads are not ``hidden // n_heads`` wide
    # (32 heads of 128 over a hidden size of 2048).  None -> that quotient.
    head_width: Optional[int] = None
    ffn_dim: Optional[int] = None  # None -> 4*hidden (gelu) / 8/3*hidden (swiglu)
    max_seq: int = 2048
    norm: str = "rmsnorm"  # 'rmsnorm' | 'layernorm'
    mlp: str = "swiglu"  # 'swiglu' | 'gelu'
    positions: str = "rope"  # 'rope' | 'learned'
    rope_theta: float = 10000.0
    dropout: float = 0.0
    tie_embeddings: bool = False
    use_bias: bool = False
    norm_eps: float = 1e-5
    attention: str = "auto"  # 'auto' | 'dot' | 'flash' | 'ring'
    # Sliding-window attention (Mistral-style): each position attends to
    # the newest `attention_window` positions only. None = full causal.
    # Requires causal=True; the flash kernel skips out-of-window K blocks
    # (O(S*window) work at long S) and the decode cache masks by
    # position, so generation beyond the window works unchanged.
    attention_window: Optional[int] = None
    # None = shape-aware flash tiling (ops.flash.auto_blocks: 512/1024
    # at S>=1024, shrinking with S).
    attention_block_q: Optional[int] = None
    attention_block_k: Optional[int] = None
    # One [hidden, (H+2*KV)*D] projection instead of three separate q/k/v
    # matmuls — at GPT-2 width the MXU prefers the single wider matmul.
    # Changes the param tree (attn/qkv vs attn/{q,k,v}), so it is opt-in.
    fused_qkv: bool = False
    # Inference-only W8A16 (ops.quant): kernels + tied embedding live as
    # int8 with per-channel scales; decode-shaped matmuls read int8 HBM
    # via the pallas kernel.  Load weights with quantize_params; training
    # a weights_int8 model is rejected by the Module (int8 leaves are not
    # trainable).
    weights_int8: bool = False
    # Int8 KV cache for decode (ops.quant.quantize_kv_page): cache pages
    # are stored int8 with a per-(row, slot, kv-head) f32 scale, halving
    # the bytes the bandwidth-bound decode loop re-reads per token.  Keys
    # and values are quantized on cache WRITE and dequantized to the
    # query dtype on read, so attention math is unchanged bf16; the
    # scale rides the cache as a rank-4 ``[B, slots, KV, 1]`` leaf, so
    # every cache-shuffling caller (beam gather, speculative admit,
    # batched retire/admit) handles it exactly like the K/V payload.
    # Orthogonal to weights_int8; composes with rolling + per-row caches.
    kv_cache_int8: bool = False
    # Logits-free LM loss: emit per-token NLL (``batch['token_nll']``,
    # consumed by objectives.lm_cross_entropy) straight from the tied
    # embedding table via ops.fused_ce — the [B*S, vocab] logits tensor
    # never exists in HBM. Requires tie_embeddings; no 'logits' key is
    # produced in this mode (decode/generation is unaffected).
    fused_ce: bool = False
    # Tokens per fused-CE chunk; peak transient memory is chunk * vocab f32.
    fused_ce_chunk: int = 1024
    # Rolling KV cache for windowed decode (opt-in): the decode cache
    # holds attention_window + decode_rolling_slack slots instead of
    # max_seq — O(window) serving memory however long the generation.
    # Slots are addressed position-mod-slots; the slack region
    # guarantees a chunk's writes never clobber a key still inside any
    # live query's window, so every decode chunk (a prefill piece, a
    # speculative verify chunk) must be <= decode_rolling_slack tokens
    # — generate()/the batched decoder chunk their prefill accordingly.
    # Requires attention_window; positions (RoPE/learned) stay absolute.
    decode_rolling_cache: bool = False
    decode_rolling_slack: int = 128
    # Per-row KV-cache frontiers for decode: cache writes and the causal
    # mask derive from the caller's ``positions`` (first column = each
    # row's write offset) instead of the shared scalar ``cache_index``.
    # Batched speculative decoding needs this — rows accept different
    # draft counts, so their frontiers diverge.  Off by default: the
    # uniform-frontier path lowers to ONE dynamic_update_slice where
    # per-row writes become a vmapped scatter.  The param tree and cache shapes are identical either
    # way, so the same params/cache work under both settings.
    decode_per_row: bool = False
    causal: bool = True  # False -> bidirectional encoder (ViT)
    remat: bool = False
    # Rematerialization policy (remat=True): what the checkpointed block
    # may KEEP instead of recomputing in the backward pass.
    #   'nothing'  — recompute everything (max memory savings, max FLOPs)
    #   'dots'     — keep matmul outputs (jax checkpoint_dots; recompute
    #                only the cheap elementwise ops — the usual TPU sweet
    #                spot: matmuls are the expensive part of the fwd)
    #   'dots_no_batch' — keep only batch-free matmuls (weights-stationary)
    remat_policy: str = "nothing"
    scan_layers: bool = False
    lora_rank: int = 0
    lora_alpha: float = 16.0
    # Mixture-of-Experts (0 = dense MLP). Experts shard over the mesh's
    # 'expert' axis; see rocket_tpu.models.moe.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # Pipeline parallelism (0 = off): split the batch into this many
    # microbatches and GPipe the blocks over the mesh's 'pipe' axis
    # (rocket_tpu.parallel.pipeline). Requires dropout == 0 and divides
    # n_layers by the pipe-axis size; layer params shard over 'pipe' via
    # the 'stage' logical axis.
    pipeline_microbatches: int = 0
    # Alternative spelling: fixed ROWS per pipeline microbatch, so the
    # microbatch COUNT scales with the incoming batch — what
    # Module(fuse_accumulation=True) needs: the fused window widens the
    # batch k-fold and the pipe runs k x more microbatches of the same
    # size, amortizing the fill/drain bubble. Mutually exclusive with
    # pipeline_microbatches.
    pipeline_microbatch_size: int = 0
    # Pipeline schedule (rocket_tpu.parallel.pipeline.SCHEDULES):
    #   'gpipe'       — all forwards then the transposed backward;
    #   '1f1b'        — same ticks, schedule-aware remat bounds the live
    #                   activation stash to <=P microbatches;
    #   'interleaved' — each stage holds pipeline_chunks non-contiguous
    #                   layer chunks, bubble fraction ~1/chunks.
    # All three are bit-equal in loss/grads; see docs/performance.md.
    pipeline_schedule: str = "gpipe"
    # Interleaved chunk count v (layer chunks per stage); must be 1 for
    # the other schedules. Needs n_layers % (pipe * v) == 0.
    pipeline_chunks: int = 1
    # Latent attention (None = the q/k/v heads above): queries and keys go
    # through low-rank latents and the decode cache holds one
    # ``[rows, slots, kv_lora_rank + qk_rope_head_dim]`` leaf a layer.
    mla: Optional[MLAConfig] = None
    # Gated experts with shared ones (rocket_tpu.models.moe.RoutedExperts)
    # in every layer from ``first_k_dense`` on; the layers before keep the
    # dense MLP of ``ffn_dim``.  None = the ``n_experts`` switch above.
    experts: Optional[ExpertsConfig] = None
    first_k_dense: int = 0
    # Four norms a block: ``x + N(attn(N(x)))``, ``x + N(mlp(N(x)))``.
    sandwich_norm: bool = False
    # Keep the residual stream in float32 while the sublayers compute in the
    # embedding's type: every norm reads float32 and its output is cast for
    # the matrix products, a sublayer's output is added (and, with
    # sandwich_norm, normalised) in float32.  A few elementwise passes over
    # [tokens, hidden] more; the sums that decide a router's near ties stop
    # being rounded to bfloat16 layer after layer.
    residual_float32: bool = False
    # Attention that chooses its keys (None = every key at or before the
    # query): a learned indexer beside the q/k/v heads and one more cache
    # leaf a layer, ``cached_index_k`` ``[rows, index_dim, slots]``.
    select: Optional[SelectConfig] = None
    # RMSNorm with a learned scale over each query and key head, before
    # the rotation (Qwen3-style).
    qk_norm: bool = False
    # Multimodal RoPE: the head's rotary frequencies in three contiguous
    # runs of these sizes, each turned by a position stream of its own
    # (``batch['mrope_positions']`` ``[3, B, S]``); text, which has one
    # position, gives the same to all three and gets plain RoPE.
    mrope_section: Optional[tuple] = None
    # The kind of each layer's mixer, one a layer: "attention" (the q/k/v
    # heads above) or "mamba" (a state-space layer of ``mamba``'s sizes,
    # :mod:`rocket_tpu.models.mamba`).  None: every layer attends.
    layer_types: Optional[tuple] = None
    mamba: Optional[MambaConfig] = None
    # Granite's scalars; None emits no operation.  The embeddings times
    # ``embedding_multiplier``; each sublayer's output times
    # ``residual_multiplier`` before it joins the stream; the logits over
    # ``logits_scaling``; the attention's softmax scale
    # ``attention_multiplier`` in place of 1/sqrt(head_dim).
    embedding_multiplier: Optional[float] = None
    residual_multiplier: Optional[float] = None
    logits_scaling: Optional[float] = None
    attention_multiplier: Optional[float] = None

    def __post_init__(self) -> None:
        if self.positions not in ("rope", "learned", "none"):
            raise ValueError(f"positions={self.positions!r}: 'rope', "
                             f"'learned' or 'none'")
        if self.layer_types is not None or self.mamba is not None:
            self._check_pattern()
        if self.fused_ce and self.logits_scaling is not None:
            raise ValueError("fused_ce reads the tied table's logits as they "
                             "are: it cannot run with logits_scaling yet")
        if self.select is not None:
            refused = [name for name, on in (
                ("mla", self.mla is not None),
                ("kv_cache_int8", self.kv_cache_int8),
                ("decode_rolling_cache", self.decode_rolling_cache),
                ("attention_window", self.attention_window is not None),
                ("fused_qkv", self.fused_qkv),
                ("scan_layers", self.scan_layers),
                ("pipeline_microbatches", self.pipeline_microbatches > 0),
                ("pipeline_microbatch_size",
                 self.pipeline_microbatch_size > 0),
                ("causal=False", not self.causal),
                ("positions='learned'", self.positions != "rope"),
            ) if on]
            if refused:
                raise ValueError(
                    f"attention that chooses its keys (select) cannot run "
                    f"with {', '.join(refused)} yet")
        if self.mrope_section is not None and (
                self.positions != "rope" or self.mla is not None
                or len(self.mrope_section) != 3
                or 2 * sum(self.mrope_section) != self.head_dim):
            raise ValueError(
                f"mrope_section {self.mrope_section} needs positions='rope', "
                f"the q/k/v heads, and three runs that sum to half a head "
                f"({self.head_dim // 2})")
        if self.qk_norm and self.mla is not None:
            raise ValueError("qk_norm is the q/k/v heads'; latent attention "
                             "(mla) norms its latents")
        if self.mla is not None or self.experts is not None:
            what = "latent attention (mla)" if self.mla is not None \
                else "routed experts (experts)"
            refused = [name for name, on in (
                ("kv_cache_int8", self.kv_cache_int8 and self.mla is not None),
                ("decode_rolling_cache",
                 self.decode_rolling_cache and self.mla is not None),
                ("fused_qkv", self.fused_qkv and self.mla is not None),
                ("scan_layers", self.scan_layers),
                ("pipeline_microbatches", self.pipeline_microbatches > 0),
                ("pipeline_microbatch_size",
                 self.pipeline_microbatch_size > 0),
                ("n_experts", self.n_experts > 0 and self.experts is not None),
            ) if on]
            if refused:
                raise ValueError(
                    f"{what} cannot run with {', '.join(refused)} yet")
        if self.residual_float32 and (self.scan_layers or self.pipelined):
            raise ValueError("residual_float32 cannot run with scan_layers "
                             "or a pipeline yet")
        if self.first_k_dense and self.experts is None:
            raise ValueError("first_k_dense needs experts for the layers "
                             "that follow")
        if self.pipeline_microbatches and self.pipeline_microbatch_size:
            raise ValueError(
                "pipeline_microbatches and pipeline_microbatch_size are "
                "mutually exclusive"
            )
        from rocket_tpu.parallel.pipeline import SCHEDULES

        if self.pipeline_schedule not in SCHEDULES:
            raise ValueError(
                f"pipeline_schedule {self.pipeline_schedule!r} unknown; "
                f"choose from {SCHEDULES}"
            )
        if self.pipeline_chunks < 1:
            raise ValueError(
                f"pipeline_chunks must be >= 1, got {self.pipeline_chunks}"
            )
        if self.pipeline_chunks > 1 and self.pipeline_schedule != "interleaved":
            raise ValueError(
                f"pipeline_chunks={self.pipeline_chunks} requires "
                f"pipeline_schedule='interleaved' "
                f"(got {self.pipeline_schedule!r})"
            )
        if not self.pipelined and (
            self.pipeline_schedule != "gpipe" or self.pipeline_chunks != 1
        ):
            raise ValueError(
                "pipeline_schedule/pipeline_chunks need pipelining on — "
                "set pipeline_microbatches or pipeline_microbatch_size"
            )
        if self.weights_int8 and self.fused_ce:
            raise ValueError(
                "weights_int8 is an inference-only layout; fused_ce is a "
                "training loss path reading the raw embedding table — "
                "they cannot combine"
            )
        if self.attention_window is not None and (
            not self.causal or self.attention_window < 1
        ):
            raise ValueError(
                f"attention_window={self.attention_window} requires "
                f"causal=True and a window >= 1"
            )
        if self.decode_rolling_cache:
            if self.attention_window is None:
                raise ValueError(
                    "decode_rolling_cache requires attention_window (an "
                    "unbounded-context cache cannot roll)"
                )
            if self.decode_rolling_slack < 1:
                raise ValueError(
                    f"decode_rolling_slack must be >= 1, got "
                    f"{self.decode_rolling_slack}"
                )
        if self.weights_int8 and self.scan_layers:
            raise ValueError(
                "weights_int8 requires the unrolled layer layout "
                "(scan_layers=False): scan stacks kernels to rank 3, "
                "which quantize_params rejects"
            )

    def _check_pattern(self) -> None:
        """``layer_types`` names a kind for every layer; state-space layers
        need ``mamba`` (and ``mamba`` a layer to size), and what they cannot
        run with yet is refused by name."""
        kinds = tuple(self.layer_types or ())
        if len(kinds) != self.n_layers \
                or set(kinds) - {"attention", "mamba"}:
            raise ValueError(
                f"layer_types needs 'attention' or 'mamba' for each of the "
                f"{self.n_layers} layers, got {self.layer_types}")
        if ("mamba" in kinds) != (self.mamba is not None):
            raise ValueError("state-space layers need mamba= (their sizes), "
                             "and mamba= a 'mamba' layer in layer_types")
        if self.mamba is None:
            return
        if self.mamba.inner != self.mamba.expand * self.hidden:
            raise ValueError(
                f"mamba: n_heads x head_dim ({self.mamba.inner}) must be "
                f"expand x hidden ({self.mamba.expand * self.hidden})")
        refused = [name for name, on in (
            ("mla", self.mla is not None),
            ("select", self.select is not None),
            ("experts", self.experts is not None),
            ("n_experts", self.n_experts > 0),
            ("kv_cache_int8", self.kv_cache_int8),
            ("decode_rolling_cache", self.decode_rolling_cache),
            ("attention_window", self.attention_window is not None),
            ("scan_layers", self.scan_layers),
            ("pipeline_microbatches", self.pipeline_microbatches > 0),
            ("pipeline_microbatch_size", self.pipeline_microbatch_size > 0),
            ("causal=False", not self.causal),
            ("fused_qkv", self.fused_qkv),
        ) if on]
        if refused:
            raise ValueError(f"state-space layers (mamba) cannot run with "
                             f"{', '.join(refused)} yet")

    @property
    def keeps_state(self) -> bool:
        """Whether a layer keeps a recurrent state (a ``mamba`` layer)."""
        return self.mamba is not None

    @property
    def pipelined(self) -> bool:
        return (
            self.pipeline_microbatches > 0
            or self.pipeline_microbatch_size > 0
        )

    def pipeline_n_micro(self, batch: int) -> int:
        """Microbatch count for an incoming batch of ``batch`` rows."""
        if self.pipeline_microbatch_size:
            if batch % self.pipeline_microbatch_size != 0:
                raise ValueError(
                    f"batch {batch} not divisible by "
                    f"pipeline_microbatch_size {self.pipeline_microbatch_size}"
                )
            return batch // self.pipeline_microbatch_size
        return self.pipeline_microbatches

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.head_width or self.hidden // self.n_heads

    @property
    def mlp_dim(self) -> int:
        if self.ffn_dim:
            return self.ffn_dim
        return 4 * self.hidden if self.mlp == "gelu" else int(8 * self.hidden / 3)

    # -- the BASELINE.json ladder -------------------------------------------

    @classmethod
    def tiny(cls, **kw) -> "TransformerConfig":
        return cls(
            vocab_size=256, hidden=64, n_layers=2, n_heads=4, max_seq=128, **kw
        )

    @classmethod
    def gpt2_124m(cls, **kw) -> "TransformerConfig":
        defaults = dict(
            vocab_size=50257,
            hidden=768,
            n_layers=12,
            n_heads=12,
            max_seq=1024,
            norm="layernorm",
            mlp="gelu",
            positions="learned",
            tie_embeddings=True,
            use_bias=True,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def llama2_7b(cls, **kw) -> "TransformerConfig":
        return cls(
            vocab_size=32000,
            hidden=4096,
            n_layers=32,
            n_heads=32,
            n_kv_heads=32,
            ffn_dim=11008,
            max_seq=4096,
            norm="rmsnorm",
            mlp="swiglu",
            positions="rope",
            norm_eps=1e-5,
            **kw,
        )

    @classmethod
    def mistral_7b(cls, **kw) -> "TransformerConfig":
        """Mistral-7B v0.1: Llama-2 architecture + GQA(8) +
        sliding-window attention (4096)."""
        return cls(
            vocab_size=32000,
            hidden=4096,
            n_layers=32,
            n_heads=32,
            n_kv_heads=8,
            ffn_dim=14336,
            max_seq=8192,
            attention_window=4096,
            norm="rmsnorm",
            mlp="swiglu",
            positions="rope",
            norm_eps=1e-5,
            **kw,
        )

    @classmethod
    def llama3_8b(cls, **kw) -> "TransformerConfig":
        return cls(
            vocab_size=128256,
            hidden=4096,
            n_layers=32,
            n_heads=32,
            n_kv_heads=8,
            ffn_dim=14336,
            max_seq=8192,
            rope_theta=500000.0,
            **kw,
        )


class _Norm(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        if cfg.norm == "rmsnorm":
            return RMSNorm(eps=cfg.norm_eps)(x)
        return nn.LayerNorm(
            epsilon=cfg.norm_eps,
            use_bias=cfg.use_bias,
            scale_init=nn.with_partitioning(nn.initializers.ones_init(), ("norm",)),
        )(x)


class Attention(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids, train: bool,
                 decode: bool = False, idle=None, mrope_positions=None):
        """With ``config.select`` the layer also holds the indexer
        (``index_q``, ``index_k`` with its LayerNorm, ``index_w``) and
        attends each query's ``top_k`` keys alone, through the cache and
        without it.  ``mrope_positions`` (``[3, B, S]``) turn the heads
        where ``config.mrope_section`` is set; ``positions`` stay each
        token's place in its row (cache slot, causal order, the indexer's
        rotation)."""
        cfg = self.config
        B, S, _ = x.shape
        H, KV, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        dense = lambda feat, name: PDense(  # noqa: E731
            feat,
            logical_axes=("embed", "heads"),
            use_bias=cfg.use_bias,
            lora_rank=cfg.lora_rank,
            lora_alpha=cfg.lora_alpha,
            weights_int8=cfg.weights_int8,
            name=name,
        )
        if cfg.fused_qkv:
            qkv = dense((H + 2 * KV) * D, "qkv")(x)
            q, k, v = jnp.split(qkv, [H * D, (H + KV) * D], axis=-1)
            q = q.reshape(B, S, H, D)
            k = k.reshape(B, S, KV, D)
            v = v.reshape(B, S, KV, D)
        else:
            q = dense(H * D, "q")(x).reshape(B, S, H, D)
            k = dense(KV * D, "k")(x).reshape(B, S, KV, D)
            v = dense(KV * D, "v")(x).reshape(B, S, KV, D)
        q = constrain(q, "batch", "sequence", "heads", None)
        k = constrain(k, "batch", "sequence", "heads", None)
        v = constrain(v, "batch", "sequence", "heads", None)
        if cfg.qk_norm:
            q = RMSNorm(eps=cfg.norm_eps, name="q_norm")(q)
            k = RMSNorm(eps=cfg.norm_eps, name="k_norm")(k)
        if cfg.positions == "rope":
            cos, sin = rotary_embedding(
                positions if mrope_positions is None else mrope_positions,
                D, cfg.rope_theta, x.dtype, mrope_section=cfg.mrope_section)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        if cfg.attention_multiplier is not None:
            # every path below scales the scores by 1/sqrt(D)
            q = q * jnp.asarray(cfg.attention_multiplier * D ** 0.5, q.dtype)
        if cfg.select is not None:
            if segment_ids is not None:
                raise ValueError("attention that chooses its keys (select) "
                                 "cannot run over packed sequences yet")
            out = self._select_attend(x, q, k, v, positions, decode, idle)
        elif decode:
            out = self._decode_attend(q, k, v, positions, idle)
        else:
            out = attend(
                q,
                k,
                v,
                impl=cfg.attention,
                causal=cfg.causal,
                segment_ids=segment_ids,
                block_q=cfg.attention_block_q,
                block_k=cfg.attention_block_k,
                window=cfg.attention_window,
            )
        out = out.reshape(B, S, H * D)
        out = PDense(
            cfg.hidden,
            logical_axes=("heads", "embed"),
            use_bias=cfg.use_bias,
            lora_rank=cfg.lora_rank,
            lora_alpha=cfg.lora_alpha,
            weights_int8=cfg.weights_int8,
            name="o",
        )(out)
        if cfg.dropout and train:
            out = nn.Dropout(cfg.dropout, deterministic=False)(out)
        return out

    def _select_attend(self, x, q, k, v, positions, decode, idle):
        """The indexer and the attention over what it chooses
        (:mod:`rocket_tpu.ops.select_attention`).  ``qI = RoPE(W_qI x)``
        (``index_heads`` of ``index_dim``), ``kI = RoPE(LN(W_kI x))`` (one
        a token, cached as ``cached_index_k`` where K and V are),
        ``w = W_w x / sqrt(index_heads * index_dim)``; both rotate over
        all their numbers by the token's own position.  The kept and the
        live keys of every query are sown (``selection/keys``
        ``[B, S, 2]``) for the serving round's counters.  The indexer's
        keys are laid out ``[B, index_dim, S]``, slots last: the layout a
        TPU keeps for the score product, so the cache is written and
        scored as stored, with no relayout between."""
        from rocket_tpu.ops.select_attention import (
            index_scores,
            selected_attention,
        )

        cfg, sel = self.config, self.config.select
        B, S, _ = x.shape
        J, d = sel.index_heads, sel.index_dim
        dense = lambda feat, name: PDense(  # noqa: E731
            feat, logical_axes=("embed", None), name=name)
        q_idx = dense(J * d, "index_q")(x).reshape(B, S, J, d)
        k_idx = nn.LayerNorm(epsilon=cfg.norm_eps, name="index_k_norm")(
            dense(d, "index_k")(x)).astype(x.dtype).reshape(B, S, 1, d)
        w_idx = dense(J, "index_w")(x).astype(jnp.float32) * (J * d) ** -0.5
        cos, sin = rotary_embedding(positions, d, cfg.rope_theta, x.dtype)
        q_idx, k_idx = apply_rope(q_idx, cos, sin), apply_rope(k_idx, cos, sin)
        k_idx = k_idx[:, :, 0].swapaxes(1, 2)                 # [B, d, S]

        def attend_selected(k_all, v_all, k_idx_all, q_pos):
            scores = index_scores(q_idx, w_idx, k_idx_all, q_pos, idle)
            out, kept = selected_attention(q, k_all, v_all, scores, q_pos,
                                           sel.top_k, idle)
            live = jnp.sum(scores > -jnp.inf, axis=-1)
            self.sow("selection", "keys",
                     jnp.stack([kept, live], axis=-1).astype(jnp.int32))
            return out

        if not decode:
            return attend_selected(k, v, k_idx, positions)
        return self._decode_attend(q, k, v, positions, idle,
                                   index=(k_idx, attend_selected))

    def _decode_attend(self, q, k, v, positions, idle=None, index=None):
        """KV-cache attention for autoregressive decode (the standard flax
        ``cache`` collection pattern): new K/V are written at the cache
        frontier, q attends against everything written so far.

        With ``config.decode_per_row`` the write offset and causal mask
        come from ``positions[:, 0]`` per row (positions must be
        contiguous per row — every caller in ``models.generate`` builds
        them as ``start + arange(S)``).  Stale cache slots past a row's
        frontier need no rewind: their key positions exceed every live
        query position, so the causal mask hides them until a later
        chunk overwrites them in place.

        The two caches that keep position == slot (per-row and shared
        ``cache_index``) attend through
        :func:`rocket_tpu.ops.decode_attention.cached_attention`: on a
        TPU a round's chunk reads only the key blocks its row has
        written, elsewhere ``dot_attention`` over the slab, and the
        choice is counted.  ``idle`` (``[B]`` bool, the batch's optional
        ``"idle"`` entry) marks rows whose output the caller drops (the
        round loop's finished rows): the kernel reads nothing for them.
        It changes no write: an idle row's chunk lands at its positions
        like any other's.

        ``index`` (a selecting layer's ``(k_idx [B, index_dim, S],
        attend_selected)``) adds the indexer's cache leaf
        ``cached_index_k`` ``[B, index_dim, slots]``, written at the
        slots K and V are, and attends through
        ``attend_selected`` over the written caches instead; a round's
        chunk the selection masks takes the decode kernel with the mask
        where its gate allows (``attention/select/decode`` ``path``
        kernel), and every other call's refusal of the decode kernel is
        counted as a fallback with reason ``selected``."""
        from rocket_tpu.ops.attention import dot_attention
        from rocket_tpu.ops.decode_attention import (
            MAX_CHUNK,
            cached_attention,
            note_fallback,
            why_not,
        )

        cfg = self.config
        B, S, KV, D = k.shape
        index_k, attend_selected = index if index is not None else (None,) * 2
        is_filled = self.has_variable("cache", "cached_k")
        n_slots = (
            cfg.attention_window + cfg.decode_rolling_slack
            if cfg.decode_rolling_cache else cfg.max_seq
        )
        quant = cfg.kv_cache_int8
        cached_k = self.variable(
            "cache", "cached_k", jnp.zeros, (B, n_slots, KV, D),
            jnp.int8 if quant else k.dtype,
        )
        cached_v = self.variable(
            "cache", "cached_v", jnp.zeros, (B, n_slots, KV, D),
            jnp.int8 if quant else v.dtype,
        )
        if quant:
            # Scales are RANK-4 on purpose: the decode callers that
            # shuffle cache rows (beam gather/tile, speculative admit)
            # discriminate K/V payload leaves from the scalar
            # cache_index by ndim == 4 — scale leaves ride the same
            # code paths with zero changes there.
            k_scale = self.variable(
                "cache", "cached_k_scale", jnp.zeros,
                (B, n_slots, KV, 1), jnp.float32,
            )
            v_scale = self.variable(
                "cache", "cached_v_scale", jnp.zeros,
                (B, n_slots, KV, 1), jnp.float32,
            )
        cache_index = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        if index is not None:
            # slots last: the callers that find a cache leaf's slots on
            # axis 1 (row scatters, pages) tell this one by its name
            cached_index_k = self.variable(
                "cache", "cached_index_k", jnp.zeros,
                (B, index_k.shape[1], n_slots), index_k.dtype,
            )
        if not is_filled:
            # init pass: create the cache shapes, attend normally (the
            # window still applies — a user init_with_output(decode=True)
            # must see the same masking as every other path)
            if index is not None:
                return attend_selected(k, v, index_k, positions)
            return attend(q, k, v, impl="dot", causal=cfg.causal,
                          window=cfg.attention_window)
        if quant:
            from rocket_tpu.ops.quant import (
                dequantize_kv_page,
                quantize_kv_page,
            )

            k_q, k_s = quantize_kv_page(k)
            v_q, v_s = quantize_kv_page(v)
            writes = [(cached_k, k_q), (cached_v, v_q),
                      (k_scale, k_s), (v_scale, v_s)]
        else:
            writes = [(cached_k, k), (cached_v, v)]

        def write_all(write_fn):
            # Apply one write op uniformly to every cache leaf (payload
            # AND scales — identical leading dims, so slot indexing is
            # shared), then return the full dequantized K/V to attend
            # against.  Dequant of the WRITTEN cache (not the inputs)
            # keeps the attended values bit-identical to what a later
            # step will read back — the quantization error is paid once,
            # at write time, consistently.
            new = [write_fn(var.value, upd) for var, upd in writes]
            for (var, _), nv in zip(writes, new):
                var.value = nv
            if quant:
                return (dequantize_kv_page(new[0], new[2], q.dtype),
                        dequantize_kv_page(new[1], new[3], q.dtype))
            return new[0], new[1]

        idx = cache_index.value
        if cfg.decode_rolling_cache:
            if S > cfg.decode_rolling_slack:
                raise ValueError(
                    f"decode chunk of {S} tokens exceeds "
                    f"decode_rolling_slack ({cfg.decode_rolling_slack}) — "
                    f"chunk the prefill (generate() does this when the "
                    f"config rolls)"
                )
            starts = positions[:, 0].astype(jnp.int32)     # [B]
            slots = (
                starts[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
            ) % n_slots                                    # [B, S], unique
            row_scatter = jax.vmap(lambda c, u, sl: c.at[sl].set(u))
            k_all, v_all = write_all(
                lambda c, u: row_scatter(c, u, slots)
            )
            cache_index.value = jnp.max(starts) + S
            # Implied position per slot: the largest position <= this
            # chunk's end congruent to the slot index.  A slot whose
            # STORED position is newer (stale speculative writes) maps
            # at least n_slots lower — below every live window — so the
            # mask hides it; negatives mean never-written slots.
            chunk_end = starts + S - 1                     # [B]
            s_idx = jnp.arange(n_slots, dtype=jnp.int32)[None, :]
            k_pos = chunk_end[:, None] - (
                (chunk_end[:, None] - s_idx) % n_slots
            )
            note_fallback("rolling", q, n_slots)
            return dot_attention(
                q, k_all, v_all, causal=True, q_offset=starts,
                window=cfg.attention_window, k_positions=k_pos,
            )
        if cfg.decode_per_row:
            starts = positions[:, 0].astype(jnp.int32)
            row_write = jax.vmap(
                lambda c, u, s: jax.lax.dynamic_update_slice(c, u, (s, 0, 0))
            )
            k_all, v_all = write_all(
                lambda c, u: row_write(c, u, starts)
            )
            q_off = starts
            # scalar cache_index is bookkeeping only in this mode (rows
            # advance independently); track the furthest write frontier
            cache_index.value = jnp.max(starts) + S
        else:
            k_all, v_all = write_all(
                lambda c, u: jax.lax.dynamic_update_slice(
                    c, u, (0, idx, 0, 0)
                )
            )
            q_off = idx
            cache_index.value = idx + S
        if index is not None:
            from rocket_tpu.observe.trace import counter
            from rocket_tpu.ops.select_attention import (
                gathers,
                why_not_masked,
            )

            if cfg.decode_per_row:
                cached_index_k.value = jax.vmap(
                    lambda c, u, s: jax.lax.dynamic_update_slice(c, u, (0, s))
                )(cached_index_k.value, index_k, starts)
            else:
                cached_index_k.value = jax.lax.dynamic_update_slice(
                    cached_index_k.value, index_k, (0, 0, idx))
            sel = cfg.select
            if S <= MAX_CHUNK:          # a round's chunk, as the kernel's
                # selected_attention's choice, asked the same way
                reason = None
                if gathers(S, n_slots, sel.top_k):
                    path = "gather"
                else:
                    reason = why_not(q, k_all, masked=True)
                    path = "mask" if reason else "kernel"
                if path != "kernel":
                    note_fallback("selected", q, n_slots)
                counter("attention/select/decode", 1, S=S, T=n_slots,
                        top_k=sel.top_k, index_heads=sel.index_heads,
                        path=path, **({"reason": reason} if reason else {}))
            else:
                note_fallback("selected", q, n_slots)
                reason = why_not_masked(q, k_all)
                counter("attention/select/prefill", 1, chunk=S, T=n_slots,
                        path="mask" if reason else "kernel",
                        **({"reason": reason} if reason else {}))
            q_pos = jnp.asarray(q_off)[..., None] \
                + jnp.arange(S, dtype=jnp.int32)
            return attend_selected(k_all, v_all, cached_index_k.value,
                                   jnp.broadcast_to(q_pos, (B, S)))
        return cached_attention(
            q, k_all, v_all, q_off, window=cfg.attention_window,
            impl=cfg.attention, idle=idle, quantized=quant,
        )


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2's MLA) with its two paths.

    ``c_q = norm(x W_qa)``, ``q = c_q W_qb`` -> per head ``[nope | rope]``;
    ``[c_kv | k_rope] = x W_kva``, ``c_kv = norm(c_kv)``; RoPE on ``q_rope``
    and on the one ``k_rope`` all heads share; scale ``(nope + rope)**-0.5``.

    *Expanded* (training, and a prefill into an empty cache): keys and
    values of every head are expanded from the latent,
    ``[k_nope_h | v_h] = c_kv W_kvb``, and attended as heads of
    ``nope + rope`` against values of ``v_head_dim``.  *Absorbed* (decode
    against the cache): ``W_kvb``'s key half is folded into the query,
    ``q~_h = q_nope_h W_kvb,h^K``, which then scores the cached rows
    themselves, multi-query: one row ``[c_kv | k_rope]`` a token serves all
    heads, its first ``kv_lora_rank`` numbers are the values, and
    ``W_kvb``'s value half is applied to the context.  Both give the same
    result; nothing per head is ever cached.

    The cache is one ``[rows, slots, kv_lora_rank + rope]`` leaf, always
    written at each row's own ``positions[:, 0]`` (contiguous positions a
    row, as every caller in ``models.generate`` builds them); stale slots
    past a row's frontier are hidden causally, as in
    :meth:`Attention._decode_attend`.  The absorbed path attends as
    :func:`rocket_tpu.ops.latent_attention.takes` says: on a TPU through a
    kernel that reads only the cache blocks each row has written,
    elsewhere through ``dot_attention`` over the slab, the choice counted.
    ``idle`` (``[B]`` bool, the batch's optional ``"idle"`` entry) marks
    rows whose output the caller drops: the kernel reads nothing of them.
    It changes no write."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids, train: bool,
                 decode: bool = False, prefill: bool = False, idle=None):
        cfg, m = self.config, self.config.mla
        B, S, _ = x.shape
        H, C = cfg.n_heads, m.kv_lora_rank
        dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
        scale = (dn + dr) ** -0.5
        dense = lambda feat, axes, name: PDense(  # noqa: E731
            feat, logical_axes=axes, name=name)
        norm = lambda name: RMSNorm(eps=cfg.norm_eps, name=name)  # noqa: E731

        c_q = norm("q_a_norm")(dense(m.q_lora_rank, ("embed", None), "q_a")(x))
        q = dense(H * (dn + dr), (None, "heads"), "q_b")(c_q)
        q = constrain(q.reshape(B, S, H, dn + dr),
                      "batch", "sequence", "heads", None)
        kv = dense(C + dr, ("embed", None), "kv_a")(x)
        c_kv = norm("kv_a_norm")(kv[..., :C])
        cos, sin = rotary_embedding(positions, dr, cfg.rope_theta, x.dtype)
        q_nope, q_rope = q[..., :dn], apply_rope(q[..., dn:], cos, sin)
        k_rope = apply_rope(kv[..., None, C:], cos, sin)        # [B,S,1,dr]
        w_kvb = self.param(
            "kv_b", _init(nn.initializers.lecun_normal(), None, "heads"),
            (C, H * (dn + dv)),
        ).astype(x.dtype).reshape(C, H, dn + dv)

        def expanded():
            heads = jnp.einsum("bsc,chd->bshd", c_kv, w_kvb)
            k = jnp.concatenate(
                [heads[..., :dn], jnp.broadcast_to(k_rope, (B, S, H, dr))],
                axis=-1)
            return _causal_in_blocks(
                jnp.concatenate([q_nope, q_rope], axis=-1), k,
                heads[..., dn:], scale, cfg.causal, segment_ids)

        if not decode:
            out = expanded()
        else:
            filled = self.has_variable("cache", "cached_latent")
            cached = self.variable(
                "cache", "cached_latent", jnp.zeros,
                (B, cfg.max_seq, C + dr), x.dtype)
            cache_index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
            if filled:
                starts = positions[:, 0].astype(jnp.int32)
                cached.value = jax.vmap(
                    lambda c, u, s: jax.lax.dynamic_update_slice(c, u, (s, 0))
                )(cached.value,
                  jnp.concatenate([c_kv, k_rope[:, :, 0]], axis=-1), starts)
                cache_index.value = jnp.max(starts) + S
            if not filled or prefill:
                # the cache held nothing before this chunk: the chunk
                # attends to itself, and expanding costs a third of
                # scoring the prompt against every slot
                out = expanded()
            else:
                q_lat = jnp.concatenate(
                    [jnp.einsum("bshd,chd->bshc", q_nope, w_kvb[..., :dn]),
                     q_rope], axis=-1)
                from rocket_tpu.ops import latent_attention

                if latent_attention.takes(q_lat, cached.value, C):
                    ctx = latent_attention.latent_decode_attention(
                        q_lat, cached.value, starts, v_width=C, scale=scale,
                        idle=idle)
                else:
                    ctx = dot_attention(
                        q_lat, cached.value[:, :, None, :], v_width=C,
                        causal=True, q_offset=starts, scale=scale)
                out = jnp.einsum("bshc,chd->bshd", ctx, w_kvb[..., dn:])
        out = dense(cfg.hidden, ("heads", "embed"), "o")(
            out.reshape(B, S, H * dv))
        if cfg.dropout and train:
            out = nn.Dropout(cfg.dropout, deterministic=False)(out)
        return out


def _causal_in_blocks(q, k, v, scale, causal, segment_ids, block: int = 512):
    """``dot_attention`` of a whole sequence against itself, a block of
    queries at a time against the keys at or before it, so that the scores
    of a 2048-token prompt over 128 heads are never all alive (2 GiB in
    float32).  Packed sequences (``segment_ids``) and bidirectional
    attention go in one piece."""
    S = q.shape[1]
    if not causal or segment_ids is not None or S <= block:
        return dot_attention(q, k, v, causal=causal, segment_ids=segment_ids,
                             scale=scale)
    return jnp.concatenate([
        dot_attention(q[:, lo:lo + block], k[:, :lo + block],
                      v[:, :lo + block], causal=True, q_offset=lo,
                      scale=scale)
        for lo in range(0, S, block)], axis=1)


class MLP(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x, train: bool):
        cfg = self.config
        up_axes = ("embed", "mlp")
        down_axes = ("mlp", "embed")
        if cfg.mlp == "swiglu":
            gate = PDense(cfg.mlp_dim, logical_axes=up_axes,
                          weights_int8=cfg.weights_int8, name="gate")(x)
            up = PDense(cfg.mlp_dim, logical_axes=up_axes,
                        weights_int8=cfg.weights_int8, name="up")(x)
            h = nn.silu(gate) * up
        else:
            h = nn.gelu(
                PDense(
                    cfg.mlp_dim,
                    logical_axes=up_axes,
                    use_bias=cfg.use_bias,
                    weights_int8=cfg.weights_int8,
                    name="up",
                )(x)
            )
        h = constrain(h, "batch", "sequence", "mlp")
        out = PDense(
            cfg.hidden,
            logical_axes=down_axes,
            use_bias=cfg.use_bias,
            weights_int8=cfg.weights_int8,
            name="down",
        )(h)
        if cfg.dropout and train:
            out = nn.Dropout(cfg.dropout, deterministic=False)(out)
        return out


class Block(nn.Module):
    """Returns ``(x, aux)`` — aux is the MoE load-balancing loss
    contribution (0.0 for dense blocks).  ``routed`` makes this layer one
    of ``config.experts`` (shared experts plus the routed ones held here)
    where the stack's leading layers keep the dense MLP.  ``mixer`` is the
    layer's kind in ``config.layer_types``: ``"mamba"`` puts a state-space
    mixer where attention is (``commit`` is its decode pass's)."""

    config: TransformerConfig
    routed: bool = False
    # with config.residual_float32: the type the sublayers compute in
    act_dtype: Any = None
    mixer: str = "attention"

    @nn.compact
    def __call__(self, x, positions, segment_ids, train: bool,
                 decode: bool = False, prefill: bool = False, idle=None,
                 mrope_positions=None, commit=None):
        cfg = self.config
        x = constrain(x, "batch", "sequence", "act_embed")
        if cfg.residual_multiplier is not None:
            res = lambda t: t * jnp.asarray(  # noqa: E731
                cfg.residual_multiplier, t.dtype)
        else:
            res = lambda t: t  # noqa: E731

        def pre(name, t):
            """The norm a sublayer reads through: cast for its matrix
            products, and as it was (what a router scores)."""
            wide = _Norm(cfg, name=name)(t)
            return (wide if self.act_dtype is None
                    else wide.astype(self.act_dtype)), wide

        def post(name, t):          # a sublayer's output on its way back
            if self.act_dtype is not None:
                t = t.astype(x.dtype)
            return _Norm(cfg, name=name)(t) if cfg.sandwich_norm else t

        if self.mixer == "mamba":
            if segment_ids is not None:
                raise ValueError("state-space layers (mamba) cannot run over "
                                 "packed sequences yet")
            y = MambaMixer(cfg, name="mamba")(
                pre("ln1", x)[0], positions, train, decode=decode, idle=idle,
                commit=commit,
            )
        elif cfg.mla is not None:
            y = LatentAttention(cfg, name="attn")(
                pre("ln1", x)[0], positions, segment_ids, train,
                decode=decode, prefill=prefill, idle=idle,
            )
        else:
            y = Attention(cfg, name="attn")(
                pre("ln1", x)[0], positions, segment_ids, train,
                decode=decode, idle=idle, mrope_positions=mrope_positions,
            )
        x = x + res(post("ln1_post", y))
        aux = jnp.zeros((), jnp.float32)
        h, h_wide = pre("ln2", x)
        if self.routed:
            ex = cfg.experts
            y = RoutedExperts(ex, name="experts")(h, router_input=h_wide)
            if ex.n_shared:
                y = y + MLP(
                    dataclasses.replace(
                        cfg, mlp="swiglu",
                        ffn_dim=ex.n_shared * ex.expert_dim),
                    name="shared")(h, train)
        elif cfg.n_experts > 0:
            from rocket_tpu.models.moe import MoEMLP

            y, aux = MoEMLP(
                n_experts=cfg.n_experts,
                mlp_dim=cfg.mlp_dim,
                top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                use_bias=cfg.use_bias,
                name="moe",
            )(h, train)
        else:
            y = MLP(cfg, name="mlp")(h, train)
        x = x + res(post("ln2_post", y))
        return constrain(x, "batch", "sequence", "act_embed"), aux


def remat_policies(cfg: TransformerConfig):
    """Resolve ``cfg.remat_policy`` to a jax checkpoint policy (shared by
    the sequential/scanned stack and the pipelined stage fn)."""
    policies = {
        "nothing": None,  # jax default: save nothing
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_no_batch":
            jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    }
    if cfg.remat_policy not in policies:
        raise ValueError(
            f"unknown remat_policy {cfg.remat_policy!r}; "
            f"choose from {sorted(policies)}"
        )
    return policies[cfg.remat_policy]


class PipelinedBlocks(nn.Module):
    """The block stack, pipelined over the mesh's ``pipe`` axis under
    ``config.pipeline_schedule`` (gpipe / 1f1b / interleaved — bit-equal
    in loss and grads; see ``parallel.pipeline``).

    Parameters are created by the same ``nn.scan`` stacking as
    ``scan_layers`` but with the ``stage`` logical name on the layer dim
    (rule: ``stage -> pipe``), so each pipeline stage holds its ``L/P``
    layer slice — ``v`` non-contiguous chunks of it under the interleaved
    schedule, permuted internally while checkpoints keep the canonical
    ascending-layer layout.  At apply time the stacked params are read
    back and driven through :func:`rocket_tpu.parallel.pipeline.pipeline`
    — microbatches flow stage-to-stage over ICI ``ppermute``.
    Constraints: ``dropout == 0`` (the pure per-layer fn carries no rng)
    and no MoE aux (returns 0).
    """

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids, train: bool):
        cfg = self.config
        if cfg.dropout:
            raise ValueError("pipeline_microbatches requires dropout=0.0")
        if self.is_initializing():
            # Sequential pass purely to create the stacked params (same
            # structure scan_layers would make, 'stage' on the layer dim).
            out, _ = nn.scan(
                lambda mdl, carry, _: mdl(carry, positions, segment_ids, train),
                variable_axes={"params": 0},
                split_rngs={"params": True},
                length=cfg.n_layers,
                metadata_params={nn.PARTITION_NAME: "stage"},
            )(Block(cfg, name="blocks"), x, None)
            return out
        from rocket_tpu.parallel.context import current_mesh
        from rocket_tpu.parallel.pipeline import pipeline

        mesh = current_mesh()
        if mesh is None:
            raise RuntimeError(
                "PipelinedBlocks needs an active mesh context (run through "
                "Module/Runtime, or wrap in parallel.context.mesh_context)"
            )
        B, S, D = x.shape
        n_micro = cfg.pipeline_n_micro(B)
        if B % n_micro != 0:
            raise ValueError(
                f"batch {B} not divisible by {n_micro} microbatches"
            )
        micro_b = B // n_micro
        stacked = nn.meta.unbox(
            self.scope.get_variable("params", "blocks")
        )
        # Per-microbatch side inputs (positions, segment ids) ride the
        # pipeline rotation as extra activation leaves — each microbatch
        # keeps ITS positions as it flows stage to stage.
        has_seg = segment_ids is not None

        # the layer module is created HERE, outside the traced schedule:
        # flax refuses Module construction across a jax transform
        # boundary (lax.scan / shard_map trace levels differ), while a
        # detached module's pure .apply is fine anywhere
        blk = Block(cfg, parent=None)

        def one_layer(layer_params, xtree):
            h, pos = xtree[0], xtree[1]
            seg = xtree[2] if has_seg else None
            out, _ = blk.apply(
                {"params": layer_params}, h, pos, seg, train
            )
            return (out, pos) + ((seg,) if has_seg else ())

        if cfg.remat:
            # GPipe's backward (the transposed rotation) otherwise keeps
            # EVERY microbatch's per-layer activations alive through the
            # whole schedule — remat per layer application recomputes
            # them instead, same policy knob as the sequential stack.
            one_layer = jax.checkpoint(
                one_layer, policy=remat_policies(cfg), prevent_cse=False
            )

        xs = (
            x.reshape(n_micro, micro_b, S, D),
            positions.reshape(n_micro, micro_b, S),
        )
        if has_seg:
            xs = xs + (segment_ids.reshape(n_micro, micro_b, S),)
        # positions/segments are pass-through side inputs: emit only the
        # hidden state (no output buffer or final all-reduce for them)
        emit = (True,) + (False,) * (len(xs) - 1)
        ys = pipeline(
            one_layer, stacked, xs, mesh=mesh, axis="pipe",
            schedule=cfg.pipeline_schedule, n_chunks=cfg.pipeline_chunks,
            emit=emit,
        )
        return ys[0].reshape(B, S, D)


class TransformerLM(nn.Module):
    """Batch-rewriting LM (blackboard contract): ``tokens -> logits``."""

    config: TransformerConfig
    tokens_key: str = "tokens"
    logits_key: str = "logits"

    @nn.compact
    def __call__(self, batch, train: bool = False, decode: bool = False,
                 prefill: bool = False, commit: Optional[int] = None):
        """``prefill`` (with ``decode``) says the cache holds nothing before
        this chunk and its positions start at 0: a latent-attention model
        then expands keys and values over the chunk instead of scoring it
        against every cache slot.  Other models ignore it.  ``commit``
        (with ``decode``) is how many of the chunk's tokens a state-space
        layer takes into its state (:mod:`rocket_tpu.models.mamba`; None:
        all); a model without one ignores it."""
        cfg = self.config
        if decode and (cfg.scan_layers or cfg.remat or cfg.pipelined):
            raise ValueError(
                "decode=True (KV-cache generation) requires the plain "
                "unrolled layer layout: scan_layers=False, remat=False, "
                "no pipelining (pipeline_microbatches=0 and "
                "pipeline_microbatch_size=0)"
            )
        tokens = batch[self.tokens_key]
        B, S = tokens.shape
        given_positions = batch.get("positions") if hasattr(batch, "get") else None
        positions = given_positions
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        segment_ids = batch.get("segment_ids") if hasattr(batch, "get") else None

        embed = Embed(cfg.vocab_size, cfg.hidden,
                      weights_int8=cfg.weights_int8, name="embed")
        x = embed(tokens)
        if cfg.embedding_multiplier is not None:
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        if cfg.positions == "learned":
            pos_table = self.param(
                "pos_embedding",
                nn.with_partitioning(
                    nn.initializers.normal(0.02), (None, "embed")
                ),
                (cfg.max_seq, cfg.hidden),
            )
            pos_table = jnp.asarray(pos_table, x.dtype)
            if given_positions is None:
                # Contiguous positions: a static slice beats a gather
                # (gathers from sharded tables trigger SPMD full remat).
                x = x + pos_table[None, :S, :]
            else:
                x = x + pos_table[positions]
        x = constrain(x, "batch", "sequence", "act_embed")
        if cfg.dropout and train:
            x = nn.Dropout(cfg.dropout, deterministic=False)(x)

        stream = {}
        if cfg.residual_float32:
            stream = {"act_dtype": x.dtype}
            x = x.astype(jnp.float32)
        block_cls = Block
        if cfg.remat:
            # Validate the policy name up front for EVERY layout — the
            # pipelined branch applies its own jax.checkpoint wrap after
            # the init early-return, which would defer an unknown-policy
            # error to the first real apply.
            policy = remat_policies(cfg)
            if not cfg.pipelined:
                block_cls = nn.remat(
                    Block, static_argnums=(4,), prevent_cse=False,
                    policy=policy,
                )
        if cfg.pipelined:
            x = PipelinedBlocks(cfg, name="pipeline")(
                x, positions, segment_ids, train
            )
            moe_aux = jnp.zeros((), jnp.float32)
        elif cfg.scan_layers:
            x, aux_per_layer = nn.scan(
                lambda mdl, carry, _: mdl(carry, positions, segment_ids, train),
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                length=cfg.n_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(block_cls(cfg, name="blocks"), x, None)
            moe_aux = jnp.sum(aux_per_layer)
        else:
            moe_aux = jnp.zeros((), jnp.float32)
            # nn.remat traces kwargs (static_argnums covers positional
            # 'train' only), so the decode flag — always False with remat,
            # the guard above rejects the combination — must not be passed
            # through a remat-wrapped block.
            extra = {} if cfg.remat else {"decode": decode}
            if cfg.mla is not None and decode:
                extra["prefill"] = prefill
            if decode and hasattr(batch, "get") \
                    and batch.get("idle") is not None:
                # rows whose output the caller drops (``_decode_attend``)
                extra["idle"] = batch.get("idle")
            if cfg.mrope_section is not None and hasattr(batch, "get") \
                    and batch.get("mrope_positions") is not None:
                extra["mrope_positions"] = batch.get("mrope_positions")
            if decode and cfg.keeps_state:
                extra["commit"] = commit
            for i in range(cfg.n_layers):
                pattern = {"routed": True} if (
                    cfg.experts is not None and i >= cfg.first_k_dense) else {}
                if cfg.layer_types is not None \
                        and cfg.layer_types[i] != "attention":
                    pattern["mixer"] = cfg.layer_types[i]
                x, aux = block_cls(cfg, name=f"block_{i}", **pattern,
                                   **stream)(
                    x, positions, segment_ids, train, **extra
                )
                moe_aux = moe_aux + aux

        hidden = x
        x = _Norm(cfg, name="ln_f")(x)
        if cfg.residual_float32:
            x = x.astype(stream["act_dtype"])
        out = Attributes(batch)
        if decode:
            # what a draft that reads the target's state is given
            # (:class:`MTPDraft`): the last block's output, before ln_f
            out["hidden"] = hidden
        if cfg.fused_ce and not decode:
            if not cfg.tie_embeddings:
                raise ValueError(
                    "fused_ce computes NLL from the tied embedding table; "
                    "set tie_embeddings=True (or keep the logits path)"
                )
            from rocket_tpu.ops.fused_ce import fused_ce_outputs

            # Next-token shift inside the helper (x[t] predicts
            # tokens[t+1]); the objective applies masks only.  token_lse
            # is the z-loss input (lm_cross_entropy(z_loss=...)).
            table = jnp.asarray(embed.embedding, x.dtype)
            out["token_nll"], out["token_lse"] = fused_ce_outputs(
                x, table, tokens, chunk_size=cfg.fused_ce_chunk
            )
        else:
            if cfg.tie_embeddings:
                logits = embed.attend(x)
            else:
                logits = PDense(
                    cfg.vocab_size, logical_axes=("embed", "vocab"),
                    weights_int8=cfg.weights_int8, name="head"
                )(x)
            if cfg.logits_scaling is not None:
                logits = logits / jnp.asarray(cfg.logits_scaling,
                                              logits.dtype)
            logits = constrain(logits, "batch", "sequence", "vocab")
            out[self.logits_key] = logits
        if cfg.n_experts > 0:
            # Blackboard contract: downstream Loss(moe_aux_loss()) trains
            # against it (rocket_tpu.models.moe).
            out["moe_aux"] = moe_aux
        return out


class MTPDraft(nn.Module):
    """A multi-token-prediction module (DeepSeek-V3's, depth 1 a module)
    as the draft of a speculative server: not a second language model but
    one block that reads the target's hidden state.

    At position ``i`` it is given ``hidden`` ``h_i`` (the target's last
    block output before its final norm, ``batch['hidden']`` of a decode
    pass) and ``tokens`` ``t_{i+1}`` (the token the target went on to
    emit), computes ``h' = W_eh [norm_e(Emb(t_{i+1})) | norm_h(h_i)]``,
    runs ``config.n_layers`` blocks of the target's kind over ``h'`` with
    a cache of its own, and its ``logits`` (a final norm of its own, then
    the head) propose ``t_{i+2}``.

    Embedding and head are the target's arrays, handed in with the batch
    (``embedding`` ``[V, H]``, ``head`` ``[H, V]``; :meth:`tied` takes them
    out of a :class:`TransformerLM` tree), so this module's parameter tree
    holds neither and initialises from ``{'tokens'}`` alone.
    ``reads_hidden`` is what :class:`~rocket_tpu.models.generate.
    ContinuousBatcher` looks for to run the round that feeds it."""

    config: TransformerConfig
    reads_hidden = True

    @staticmethod
    def tied(target_params):
        """The target's embedding table and head, as this module's batch
        takes them."""
        table = target_params["embed"]["embedding"]
        if "head" in target_params:
            return {"embedding": table, "head": target_params["head"]["kernel"]}
        return {"embedding": table, "head": table.T}

    @nn.compact
    def __call__(self, batch, train: bool = False, decode: bool = False,
                 prefill: bool = False):
        cfg = self.config
        if cfg.keeps_state:
            raise ValueError("a draft that reads the target's hidden state "
                             "(MTPDraft) cannot hold state-space layers yet")
        tokens = batch["tokens"]
        B, S = tokens.shape
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        hidden = batch.get("hidden")
        if hidden is None:                  # init: shapes alone
            hidden = jnp.zeros((B, S, cfg.hidden), jnp.float32)
        table = batch.get("embedding")
        emb = jnp.zeros_like(hidden) if table is None \
            else jnp.asarray(table)[tokens]
        act = emb.dtype                     # what the sublayers compute in
        stream = {"act_dtype": act} if cfg.residual_float32 else {}
        wide = jnp.float32 if cfg.residual_float32 else act
        x = PDense(cfg.hidden, logical_axes=(None, "embed"), name="eh_proj")(
            jnp.concatenate(
                [_Norm(cfg, name="enorm")(emb.astype(wide)),
                 _Norm(cfg, name="hnorm")(hidden.astype(wide))],
                axis=-1).astype(act)).astype(wide)
        extra = {"prefill": prefill} if cfg.mla is not None and decode else {}
        if decode and batch.get("idle") is not None:
            extra["idle"] = batch.get("idle")
        for i in range(cfg.n_layers):
            x, _ = Block(cfg, routed=cfg.experts is not None,
                         name=f"block_{i}", **stream)(
                x, positions, None, train, decode=decode, **extra)
        out = Attributes(batch)
        out["hidden"] = x
        final = _Norm(cfg, name="ln_f")(x).astype(act)
        if batch.get("head") is not None:
            out["logits"] = jnp.einsum(
                "bsd,dv->bsv", final, jnp.asarray(batch["head"], act))
        return out
