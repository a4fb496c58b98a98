"""Architecture ``keye_moe``: the language model of Keye-VL-2.0-30B-A3B as
its ``config.json`` gives it (``model_type`` ``KeyeVL2``; the widths are
Qwen3-30B-A3B's).

- grouped-query attention (``num_attention_heads`` over
  ``num_key_value_heads`` of ``head_dim``) with an RMSNorm over each query
  and key head, RoPE as M-RoPE (``rope_scaling.mrope_section``), and
  ``sa_config``: an indexer of ``indexer_num_heads`` heads of
  ``indexer_head_dim`` over one key a token that keeps ``topk`` keys a
  query; ``q_chunk_size`` queries of a prompt are admitted at a time;
- every layer ``num_experts`` SwiGLU experts of ``moe_intermediate_size``
  behind a softmax router (top ``num_experts_per_tok``, ``norm_topk_prob``),
  no shared expert, no dense layer (``decoder_sparse_step`` 1,
  ``mlp_only_layers`` empty);
- RMSNorm, an untied head.

A configuration file may state the chip's share of a deployment: its
``num_experts`` is then the number of experts held here, and ``deployment``
gives ``router_width`` (all experts of a layer, which the router still
scores) and ``expert_rank`` (which share: experts ``rank * held ..``).
Without ``deployment`` every expert is held.

The seven names of ``harness.FAMILY_NAMES``; the plain reference is
``reference/keye_moe.py`` and the counts are ``counts/keye_moe.py``.
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmark import harness
from benchmark.counts import keye_moe as counts  # noqa: F401
from benchmark.reference import keye_moe as reference  # noqa: F401


def normalise(config: Dict) -> Dict:
    """The sizes in the benchmark's own keys; a key the file lacks is an
    error.  Every value can be hashed (the reference keys its compiled
    functions by them)."""
    deployment = config.get("deployment") or {}
    held, sa = config["num_experts"], config["sa_config"]
    if config.get("attention_bias") or config["hidden_act"] != "silu" \
            or config.get("tie_word_embeddings") \
            or config.get("mlp_only_layers") \
            or config.get("decoder_sparse_step", 1) != 1 \
            or config.get("use_sliding_window") \
            or sa["indexer_num_kv_heads"] != 1 \
            or sa["kv_chunk_size"] != sa["q_chunk_size"]:
        raise harness.BenchmarkError(
            "keye_moe knows SiLU experts in every layer, no biases, no "
            "sliding window, an untied head and an indexer with one key "
            "head and equal chunks; the configuration file says otherwise")
    return dict(
        hidden=config["hidden_size"], layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        expert_ffn=config["moe_intermediate_size"],
        router=int(deployment.get("router_width", held)), held=held,
        held_start=int(deployment.get("expert_rank", 0)) * held,
        top_k=config["num_experts_per_tok"],
        norm_topk=bool(config["norm_topk_prob"]),
        index_heads=sa["indexer_num_heads"], index_dim=sa["indexer_head_dim"],
        select_top_k=sa["topk"], chunk=sa["q_chunk_size"],
        mrope=tuple(config["rope_scaling"]["mrope_section"]),
        eps=config["rms_norm_eps"], rope_theta=float(config["rope_theta"]),
        vocab=config["vocab_size"], vocab_padded=config["vocab_size"],
        max_pos=config["max_position_embeddings"])


def draft(arch: Dict, serving: Dict) -> Dict:
    """The draft a speculative server needs: the same widths, selection and
    experts, its own embeddings and head, ``draft_layers`` deep (no draft
    is published)."""
    return dict(arch, layers=int(serving["draft_layers"]))


def program(arch: Dict, *, max_seq: int, attention: str = "auto"):
    """The program's ``TransformerLM`` for this architecture.  The residual
    stream is float32 and the router reads the float32 norm, as the other
    expert model's (PERF.md, PR 28).  A program that lacks the selecting
    attention cannot run the configuration: said as the benchmark's own
    refusal."""
    try:
        from rocket_tpu.models.moe import ExpertsConfig
        from rocket_tpu.models.transformer import (SelectConfig,
                                                   TransformerConfig,
                                                   TransformerLM)
    except ImportError as exc:
        raise harness.BenchmarkError(
            f"the program cannot run architecture keye_moe: {exc}") from exc

    return TransformerLM(TransformerConfig(
        vocab_size=arch["vocab_padded"], hidden=arch["hidden"],
        n_layers=arch["layers"], n_heads=arch["heads"],
        n_kv_heads=arch["kv_heads"], head_width=arch["head_dim"],
        max_seq=int(max_seq), norm="rmsnorm", mlp="swiglu", positions="rope",
        rope_theta=arch["rope_theta"], tie_embeddings=False, use_bias=False,
        norm_eps=arch["eps"], attention=attention, residual_float32=True,
        qk_norm=True, mrope_section=arch["mrope"],
        select=SelectConfig(
            index_heads=arch["index_heads"], index_dim=arch["index_dim"],
            top_k=arch["select_top_k"], chunk=arch["chunk"]),
        experts=ExpertsConfig(
            n_routed=arch["router"], top_k=arch["top_k"],
            expert_dim=arch["expert_ffn"], n_shared=0, scale=1.0,
            norm_topk=arch["norm_topk"], router="softmax",
            held_start=arch["held_start"], n_held=arch["held"])))


_ATTN = {"q": "q.w", "k": "k.w", "v": "v.w", "o": "o.w",
         "index_q": "iq.w", "index_k": "ik.w", "index_w": "iw.w"}
_EXPERTS = {"w_gate": "eg", "w_up": "eu", "w_down": "ed"}


def leaf_name(path) -> str:
    """The benchmark's name for a leaf of the program's tree:
    ``block_3/attn/index_q/kernel`` -> ``L3.iq.w``; an expert matrix
    ``block_3/experts/w_gate`` -> ``L3eg.w``, a group of its own."""
    keys = [str(getattr(k, "key", getattr(k, "name", k))) for k in path]
    keys = [k for k in keys if k != "value" and not k.startswith("RMSNorm_")]
    if keys[0] in ("embed", "head"):
        return keys[0]
    if keys[0] == "ln_f":
        return "lnf.scale"
    if keys[0].startswith("block_"):
        layer = f"L{keys[0][len('block_'):]}"
        if keys[1] in ("ln1", "ln2"):
            return f"{layer}.{keys[1]}.scale"
        if keys[1] == "attn":
            if keys[2] in _ATTN:
                return f"{layer}.{_ATTN[keys[2]]}"
            if keys[2] in ("q_norm", "k_norm"):
                return f"{layer}.{keys[2]}.scale"
            if keys[2] == "index_k_norm":
                return f"{layer}.ik_norm.{keys[-1]}"
        if keys[1] == "experts":
            if keys[2] == "router":
                return f"{layer}.router.w"
            return f"{layer}{_EXPERTS[keys[2]]}.w"
    raise harness.BenchmarkError(f"no name for program leaf {keys}")


def leaf_shapes(arch: Dict, prefix: str = "") -> Dict[str, Tuple[int, ...]]:
    """Name -> shape of every leaf.  A layer's three stacks of expert
    matrices are groups of their own (``L3eg``, ``L3eu``, ``L3ed``), as the
    other expert architecture's."""
    H, V, D = arch["hidden"], arch["vocab_padded"], arch["head_dim"]
    J, d = arch["index_heads"], arch["index_dim"]
    E, Fe = arch["held"], arch["expert_ffn"]
    shapes: Dict[str, Tuple[int, ...]] = {"embed": (V, H)}
    for i in range(arch["layers"]):
        L = f"L{i}"
        shapes[f"{L}.ln1.scale"] = (H,)
        shapes[f"{L}.q.w"] = (H, arch["heads"] * D)
        shapes[f"{L}.k.w"] = (H, arch["kv_heads"] * D)
        shapes[f"{L}.v.w"] = (H, arch["kv_heads"] * D)
        shapes[f"{L}.o.w"] = (arch["heads"] * D, H)
        shapes[f"{L}.q_norm.scale"] = (D,)
        shapes[f"{L}.k_norm.scale"] = (D,)
        shapes[f"{L}.iq.w"] = (H, J * d)
        shapes[f"{L}.ik.w"] = (H, d)
        shapes[f"{L}.ik_norm.scale"] = (d,)
        shapes[f"{L}.ik_norm.bias"] = (d,)
        shapes[f"{L}.iw.w"] = (H, J)
        shapes[f"{L}.ln2.scale"] = (H,)
        shapes[f"{L}.router.w"] = (H, arch["router"])
        shapes[f"{L}eg.w"] = (E, H, Fe)
        shapes[f"{L}eu.w"] = (E, H, Fe)
        shapes[f"{L}ed.w"] = (E, Fe, H)
    shapes["lnf.scale"] = (H,)
    shapes["head"] = (H, V)
    return {prefix + k: v for k, v in shapes.items()}
