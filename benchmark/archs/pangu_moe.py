"""Architecture ``pangu_moe``: the openPangu-Ultra-MoE / DeepSeek-V3 family as
its ``config.json`` gives it (``model_type`` ``pangu_ultra_moe``).

- latent attention (MLA): queries and keys through low-rank latents, one
  cached row of ``kv_lora_rank + qk_rope_head_dim`` numbers a token a layer;
- ``first_k_dense_replace`` leading layers with a dense SwiGLU MLP, then
  layers of ``n_routed_experts`` gated experts behind a sigmoid router
  (top-k, normalised, scaled) plus ``n_shared_experts`` shared ones;
- sandwich norms: four RMSNorms a block;
- ``num_nextn_predict_layers`` multi-token-prediction modules, served as the
  speculative draft that reads the target's hidden state.

A configuration file may state the chip's share of a deployment: its
``n_routed_experts`` is then the number of experts held here, and
``deployment`` gives ``router_width`` (all experts of a layer, which the
router still scores) and ``expert_rank`` (which share: experts
``rank * held ..``).  Without ``deployment`` every expert is held.

The seven names of ``harness.FAMILY_NAMES``; the plain reference is
``reference/pangu_moe.py`` and the counts are ``counts/pangu_moe.py``.
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmark import harness
from benchmark.counts import pangu_moe as counts  # noqa: F401
from benchmark.reference import pangu_moe as reference  # noqa: F401


def normalise(config: Dict) -> Dict:
    """The sizes in the benchmark's own keys; a key the file lacks is an
    error.  ``kind`` is ``target`` here and ``mtp`` for :func:`draft`."""
    deployment = config.get("deployment") or {}
    held = config["n_routed_experts"]
    if not config.get("sandwich_norm") or config.get("attention_bias") \
            or config["hidden_act"] != "silu" \
            or config.get("tie_word_embeddings"):
        raise harness.BenchmarkError(
            "pangu_moe knows sandwich norms, SiLU, no biases and an untied "
            "head; the configuration file says otherwise")
    return dict(
        kind="target", hidden=config["hidden_size"],
        layers=config["num_hidden_layers"],
        first_dense=config["first_k_dense_replace"],
        heads=config["num_attention_heads"],
        q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"], ffn=config["intermediate_size"],
        expert_ffn=config["moe_intermediate_size"],
        router=int(deployment.get("router_width", held)), held=held,
        held_start=int(deployment.get("expert_rank", 0)) * held,
        top_k=config["num_experts_per_tok"],
        shared=config["n_shared_experts"],
        norm_topk=bool(config["norm_topk_prob"]),
        route_scale=float(config["routed_scaling_factor"]),
        mtp_layers=config["num_nextn_predict_layers"],
        eps=config["rms_norm_eps"], rope_theta=float(config["rope_theta"]),
        vocab=config["vocab_size"], vocab_padded=config["vocab_size"],
        max_pos=config["max_position_embeddings"])


def draft(arch: Dict, serving: Dict) -> Dict:
    """The draft a speculative server runs: the model's own
    multi-token-prediction module (its depth is the file's
    ``num_nextn_predict_layers``; ``serving`` has nothing to add)."""
    if arch["mtp_layers"] < 1:
        raise harness.BenchmarkError(
            "the configuration has no multi-token-prediction module to "
            "draft with")
    return dict(arch, kind="mtp", layers=arch["mtp_layers"], first_dense=0)


def program(arch: Dict, *, max_seq: int, attention: str = "auto"):
    """The program's module: ``TransformerLM`` for the target, ``MTPDraft``
    for the multi-token-prediction module.  ``attention`` chooses among
    the flash kernels, of which latent attention uses none.  The residual
    stream is kept in float32 (``residual_float32``): on the chip the same
    prompt's widest gap read 0.28 so against 0.36 in bfloat16 (PERF.md)."""
    from rocket_tpu.models.moe import ExpertsConfig
    from rocket_tpu.models.transformer import (MLAConfig, MTPDraft,
                                               TransformerConfig,
                                               TransformerLM)

    config = TransformerConfig(
        vocab_size=arch["vocab_padded"], hidden=arch["hidden"],
        n_layers=arch["layers"], n_heads=arch["heads"], ffn_dim=arch["ffn"],
        max_seq=int(max_seq), norm="rmsnorm", mlp="swiglu", positions="rope",
        rope_theta=arch["rope_theta"], tie_embeddings=False, use_bias=False,
        norm_eps=arch["eps"], attention="dot", sandwich_norm=True,
        residual_float32=True, first_k_dense=arch["first_dense"],
        mla=MLAConfig(
            q_lora_rank=arch["q_rank"], kv_lora_rank=arch["kv_rank"],
            qk_nope_head_dim=arch["nope"], qk_rope_head_dim=arch["rope"],
            v_head_dim=arch["v_dim"]),
        experts=ExpertsConfig(
            n_routed=arch["router"], top_k=arch["top_k"],
            expert_dim=arch["expert_ffn"], n_shared=arch["shared"],
            scale=arch["route_scale"], norm_topk=arch["norm_topk"],
            held_start=arch["held_start"], n_held=arch["held"]))
    return (MTPDraft if arch["kind"] == "mtp" else TransformerLM)(config)


_NORMS = {"ln1": "ln1", "ln1_post": "ln1p", "ln2": "ln2", "ln2_post": "ln2p"}
_EXPERTS = {"w_gate": "eg", "w_up": "eu", "w_down": "ed"}


def leaf_name(path) -> str:
    """The benchmark's name for a leaf of the program's tree:
    ``block_3/attn/q_b/kernel`` -> ``L3.q_b.w``; an expert matrix
    ``block_3/experts/w_gate`` -> ``L3eg.w``, a group of its own."""
    keys = [str(getattr(k, "key", getattr(k, "name", k))) for k in path]
    keys = [k for k in keys if k != "value" and not k.startswith("RMSNorm_")]
    if keys[0] in ("embed", "head"):
        return keys[0]
    if keys[0] == "ln_f":
        return "lnf.scale"
    if keys[0] in ("enorm", "hnorm"):
        return f"{keys[0]}.scale"
    if keys[0] == "eh_proj":
        return "eh_proj.w"
    if keys[0].startswith("block_"):
        layer = f"L{keys[0][len('block_'):]}"
        if keys[1] in _NORMS:
            return f"{layer}.{_NORMS[keys[1]]}.scale"
        if keys[1] == "attn":
            if keys[2] == "kv_b":
                return f"{layer}.kv_b.w"
            return f"{layer}.{keys[2]}." + (
                "scale" if keys[-1] == "scale" else "w")
        if keys[1] == "mlp":
            return f"{layer}.{keys[2]}.w"
        if keys[1] == "shared":
            return f"{layer}.sh_{keys[2]}.w"
        if keys[1] == "experts":
            if keys[2] == "router":
                return f"{layer}.router.w"
            return f"{layer}{_EXPERTS[keys[2]]}.w"
    raise harness.BenchmarkError(f"no name for program leaf {keys}")


def leaf_shapes(arch: Dict, prefix: str = "") -> Dict[str, Tuple[int, ...]]:
    """Name -> shape of every leaf.  A layer's three stacks of expert
    matrices are groups of their own (``L3eg``, ``L3eu``, ``L3ed``: a
    quarter of a billion numbers each at the published widths), so that no
    draw holds a whole expert layer in float32."""
    H, V = arch["hidden"], arch["vocab_padded"]
    heads, C, dr = arch["heads"], arch["kv_rank"], arch["rope"]
    E, Fe = arch["held"], arch["expert_ffn"]
    shapes: Dict[str, Tuple[int, ...]] = {}
    if arch["kind"] == "mtp":
        shapes["enorm.scale"] = (H,)
        shapes["hnorm.scale"] = (H,)
        shapes["eh_proj.w"] = (2 * H, H)
    else:
        shapes["embed"] = (V, H)
    for i in range(arch["layers"]):
        L = f"L{i}"
        shapes[f"{L}.ln1.scale"] = (H,)
        shapes[f"{L}.q_a.w"] = (H, arch["q_rank"])
        shapes[f"{L}.q_a_norm.scale"] = (arch["q_rank"],)
        shapes[f"{L}.q_b.w"] = (arch["q_rank"],
                                heads * (arch["nope"] + dr))
        shapes[f"{L}.kv_a.w"] = (H, C + dr)
        shapes[f"{L}.kv_a_norm.scale"] = (C,)
        shapes[f"{L}.kv_b.w"] = (C, heads * (arch["nope"] + arch["v_dim"]))
        shapes[f"{L}.o.w"] = (heads * arch["v_dim"], H)
        shapes[f"{L}.ln1p.scale"] = (H,)
        shapes[f"{L}.ln2.scale"] = (H,)
        if i < arch["first_dense"]:
            for name in ("gate", "up"):
                shapes[f"{L}.{name}.w"] = (H, arch["ffn"])
            shapes[f"{L}.down.w"] = (arch["ffn"], H)
        else:
            shapes[f"{L}.router.w"] = (H, arch["router"])
            if arch["shared"]:
                for name in ("sh_gate", "sh_up"):
                    shapes[f"{L}.{name}.w"] = (H, arch["shared"] * Fe)
                shapes[f"{L}.sh_down.w"] = (arch["shared"] * Fe, H)
            shapes[f"{L}eg.w"] = (E, H, Fe)
            shapes[f"{L}eu.w"] = (E, H, Fe)
            shapes[f"{L}ed.w"] = (E, Fe, H)
        shapes[f"{L}.ln2p.scale"] = (H,)
    shapes["lnf.scale"] = (H,)
    if arch["kind"] != "mtp":
        shapes["head"] = (H, V)
    return {prefix + k: v for k, v in shapes.items()}
