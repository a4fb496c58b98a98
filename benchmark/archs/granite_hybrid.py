"""Architecture ``granite_hybrid``: Granite 4.0-H as its ``config.json``
gives it (``model_type`` ``granitemoehybrid``) with no routed expert
(``num_local_experts`` 0).

- ``layer_types`` puts an ``attention`` or a ``mamba`` mixer in each layer;
- ``attention``: grouped-query attention (``num_attention_heads`` over
  ``num_key_value_heads``, heads of ``hidden_size / num_attention_heads``)
  with no position rotation (``position_embedding_type`` ``nope``) and the
  softmax scale ``attention_multiplier``;
- ``mamba``: Mamba-2's mixer with ``mamba_n_heads`` heads of
  ``mamba_d_head``, a state of ``mamba_d_state``, one group of ``B`` and
  ``C``, a causal depthwise convolution of ``mamba_d_conv`` taps with a
  bias, the gated RMSNorm over all ``mamba_expand * hidden_size`` numbers;
- every layer the shared SwiGLU MLP of ``shared_intermediate_size``;
- RMSNorm before each sublayer, each sublayer's output times
  ``residual_multiplier``, embeddings times ``embedding_multiplier``, the
  head tied to the embeddings and its logits over ``logits_scaling``.

The seven names of ``harness.FAMILY_NAMES``; the plain reference is
``reference/granite_hybrid.py`` and the counts are
``counts/granite_hybrid.py``.
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmark import harness
from benchmark.counts import granite_hybrid as counts  # noqa: F401
from benchmark.reference import granite_hybrid as reference  # noqa: F401


def normalise(config: Dict) -> Dict:
    """The sizes in the benchmark's own keys; a key the file lacks is an
    error.  Every value can be hashed (the reference keys its compiled
    functions by them)."""
    H, heads = config["hidden_size"], config["num_attention_heads"]
    ssm_heads, ssm_dim = config["mamba_n_heads"], config["mamba_d_head"]
    if config.get("num_local_experts") or config.get("num_experts_per_tok") \
            or config["mamba_n_groups"] != 1 \
            or config["position_embedding_type"] != "nope" \
            or not config["tie_word_embeddings"] \
            or config["hidden_act"] != "silu" or config["attention_bias"] \
            or config["normalization_function"] != "rmsnorm" \
            or config["mamba_proj_bias"] or not config["mamba_conv_bias"] \
            or ssm_heads * ssm_dim != config["mamba_expand"] * H \
            or len(config["layer_types"]) != config["num_hidden_layers"]:
        raise harness.BenchmarkError(
            "granite_hybrid knows no routed expert, one group of B and C, "
            "no position rotation, a tied head, SiLU, no attention bias, "
            "RMSNorm, a convolution bias and no projection bias; the "
            "configuration file says otherwise")
    return dict(
        hidden=H, layers=config["num_hidden_layers"],
        layer_types=tuple(config["layer_types"]),
        heads=heads, kv_heads=config["num_key_value_heads"],
        head_dim=H // heads, ffn=config["shared_intermediate_size"],
        ssm_heads=ssm_heads, ssm_head_dim=ssm_dim,
        d_state=config["mamba_d_state"], d_conv=config["mamba_d_conv"],
        expand=config["mamba_expand"], chunk=config["mamba_chunk_size"],
        eps=config["rms_norm_eps"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        attention_multiplier=float(config["attention_multiplier"]),
        vocab=config["vocab_size"], vocab_padded=config["vocab_size"],
        max_pos=config["max_position_embeddings"])


def draft(arch: Dict, serving: Dict) -> Dict:
    """The draft a speculative server needs (no draft is published): the
    same widths, ``draft_layer_types`` deep, a tied table of its own."""
    kinds = tuple(serving["draft_layer_types"])
    return dict(arch, layers=len(kinds), layer_types=kinds)


def program(arch: Dict, *, max_seq: int, attention: str = "auto"):
    """The program's ``TransformerLM`` for this architecture.  A program
    that lacks state-space layers cannot run the configuration: said as the
    benchmark's own refusal."""
    try:
        from rocket_tpu.models.transformer import (MambaConfig,
                                                   TransformerConfig,
                                                   TransformerLM)
    except ImportError as exc:
        raise harness.BenchmarkError(
            f"the program cannot run architecture granite_hybrid: {exc}") \
            from exc

    return TransformerLM(TransformerConfig(
        vocab_size=arch["vocab_padded"], hidden=arch["hidden"],
        n_layers=arch["layers"], n_heads=arch["heads"],
        n_kv_heads=arch["kv_heads"], head_width=arch["head_dim"],
        ffn_dim=arch["ffn"], max_seq=int(max_seq), norm="rmsnorm",
        mlp="swiglu", positions="none", tie_embeddings=True, use_bias=False,
        norm_eps=arch["eps"], attention=attention,
        layer_types=arch["layer_types"],
        mamba=MambaConfig(
            d_state=arch["d_state"], d_conv=arch["d_conv"],
            expand=arch["expand"], n_heads=arch["ssm_heads"],
            head_dim=arch["ssm_head_dim"], n_groups=1, chunk=arch["chunk"],
            conv_bias=True, proj_bias=False),
        embedding_multiplier=arch["embedding_multiplier"],
        residual_multiplier=arch["residual_multiplier"],
        logits_scaling=arch["logits_scaling"],
        attention_multiplier=arch["attention_multiplier"]))


_ATTN = {"q": "q.w", "k": "k.w", "v": "v.w", "o": "o.w"}
_MAMBA = {"in_proj": "in.w", "out_proj": "out.w", "conv_kernel": "conv.w",
          "conv_bias": "conv.b", "A_log": "A_log", "D": "D",
          "dt_bias": "dt_bias", "norm_scale": "gnorm.scale"}


def leaf_name(path) -> str:
    """The benchmark's name for a leaf of the program's tree:
    ``block_3/mamba/in_proj/kernel`` -> ``L3.in.w``."""
    keys = [str(getattr(k, "key", getattr(k, "name", k))) for k in path]
    keys = [k for k in keys if k != "value" and not k.startswith("RMSNorm_")]
    if keys[0] == "embed":
        return "embed"
    if keys[0] == "ln_f":
        return "lnf.scale"
    if keys[0].startswith("block_"):
        layer = f"L{keys[0][len('block_'):]}"
        if keys[1] in ("ln1", "ln2"):
            return f"{layer}.{keys[1]}.scale"
        if keys[1] == "attn" and keys[2] in _ATTN:
            return f"{layer}.{_ATTN[keys[2]]}"
        if keys[1] == "mamba" and keys[2] in _MAMBA:
            return f"{layer}.{_MAMBA[keys[2]]}"
        if keys[1] == "mlp":
            return f"{layer}.{keys[2]}.w"
    raise harness.BenchmarkError(f"no name for program leaf {keys}")


def leaf_shapes(arch: Dict, prefix: str = "") -> Dict[str, Tuple[int, ...]]:
    """Name -> shape of every leaf."""
    H, V, F = arch["hidden"], arch["vocab_padded"], arch["ffn"]
    nh, D = arch["heads"], arch["head_dim"]
    sh, P, N, K = (arch["ssm_heads"], arch["ssm_head_dim"], arch["d_state"],
                   arch["d_conv"])
    E = sh * P
    W = E + 2 * N
    shapes: Dict[str, Tuple[int, ...]] = {"embed": (V, H)}
    for i, kind in enumerate(arch["layer_types"]):
        L = f"L{i}"
        shapes[f"{L}.ln1.scale"] = (H,)
        if kind == "mamba":
            shapes[f"{L}.in.w"] = (H, E + W + sh)
            shapes[f"{L}.conv.w"] = (K, W)
            shapes[f"{L}.conv.b"] = (W,)
            shapes[f"{L}.A_log"] = (sh,)
            shapes[f"{L}.D"] = (sh,)
            shapes[f"{L}.dt_bias"] = (sh,)
            shapes[f"{L}.gnorm.scale"] = (E,)
            shapes[f"{L}.out.w"] = (E, H)
        else:
            shapes[f"{L}.q.w"] = (H, nh * D)
            shapes[f"{L}.k.w"] = (H, arch["kv_heads"] * D)
            shapes[f"{L}.v.w"] = (H, arch["kv_heads"] * D)
            shapes[f"{L}.o.w"] = (nh * D, H)
        shapes[f"{L}.ln2.scale"] = (H,)
        shapes[f"{L}.gate.w"] = (H, F)
        shapes[f"{L}.up.w"] = (H, F)
        shapes[f"{L}.down.w"] = (F, H)
    shapes["lnf.scale"] = (H,)
    return {prefix + k: v for k, v in shapes.items()}
