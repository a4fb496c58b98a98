"""Operations and bytes of the ``pangu_moe`` architecture as this chip holds
it, from shapes alone: the ``counts`` of ``archs/pangu_moe.py``.

Counted from the configuration and the live lengths, never from the HLO.  A
multiply-add is two operations.  ``arch`` is what ``archs/pangu_moe.py``'s
``normalise`` returns (``kind`` ``target``) or its ``draft`` (``kind``
``mtp``: the multi-token-prediction module, which holds no embedding and no
head of its own).  Only the experts held here are counted as held; a token
is counted through ``top_k * held / router`` routed experts, what it meets
here on average when the router is even.
"""

from __future__ import annotations

from typing import Dict


def attention_params(arch: Dict) -> int:
    """One layer's attention matrices: ``q_a``, ``q_b``, ``kv_a``, ``kv_b``,
    ``o`` (norms left out)."""
    H, nh = arch["hidden"], arch["heads"]
    return (H * arch["q_rank"]
            + arch["q_rank"] * nh * (arch["nope"] + arch["rope"])
            + H * (arch["kv_rank"] + arch["rope"])
            + arch["kv_rank"] * nh * (arch["nope"] + arch["v_dim"])
            + nh * arch["v_dim"] * H)


def expert_params(arch: Dict) -> int:
    """One SwiGLU expert (routed or shared)."""
    return 3 * arch["hidden"] * arch["expert_ffn"]


def layer_params(arch: Dict, routed: bool) -> int:
    """Parameters one layer holds here."""
    if not routed:
        return attention_params(arch) + 3 * arch["hidden"] * arch["ffn"]
    return (attention_params(arch) + arch["hidden"] * arch["router"]
            + (arch["shared"] + arch["held"]) * expert_params(arch))


def layer_params_per_token(arch: Dict, routed: bool,
                           held_share: float = None) -> float:
    """Parameters one token is multiplied by in one layer here.
    ``held_share`` is the share of routed slots that fall on a held expert
    (a run's own counter where a reader has it; ``held / router`` else)."""
    if not routed:
        return float(layer_params(arch, False))
    if held_share is None:
        held_share = arch["held"] / arch["router"]
    return (attention_params(arch) + arch["hidden"] * arch["router"]
            + (arch["shared"] + arch["top_k"] * held_share)
            * expert_params(arch))


def _layers(arch: Dict):
    return [i >= arch["first_dense"] for i in range(arch["layers"])]


def held_params(arch: Dict) -> int:
    """Parameters held in memory: the blocks and, for the target, embedding
    and head; for the module, ``eh_proj``."""
    n = sum(layer_params(arch, routed) for routed in _layers(arch))
    if arch["kind"] == "mtp":
        return n + 2 * arch["hidden"] * arch["hidden"]
    return n + 2 * arch["hidden"] * arch["vocab_padded"]


def weights_bytes(arch: Dict, draft: Dict, dtype_bytes: int = 2) -> int:
    """What a server holds of target and module."""
    return (held_params(arch) + held_params(draft)) * dtype_bytes


def cache_bytes_per_token(arch: Dict, draft: Dict,
                          dtype_bytes: int = 2) -> int:
    """One latent row a token in every layer of target and module."""
    return ((arch["kv_rank"] + arch["rope"]) * dtype_bytes
            * (arch["layers"] + draft["layers"]))


def absorbed_pair_flops(arch: Dict) -> float:
    """One query-key pair of absorbed attention over all heads: scores over
    ``kv_rank + rope`` numbers, context over ``kv_rank``."""
    return 2.0 * (arch["kv_rank"] + arch["rope"] + arch["kv_rank"]) \
        * arch["heads"]


def expanded_pair_flops(arch: Dict) -> float:
    """One query-key pair of expanded attention: scores over
    ``nope + rope``, values of ``v_dim``."""
    return 2.0 * (arch["nope"] + arch["rope"] + arch["v_dim"]) * arch["heads"]


def decode_round_cost(arch: Dict, draft: Dict, n_draft: int,
                      live_tokens: float, rows: int, dtype_bytes: int = 2,
                      held_share: float = None) -> Dict:
    """Least work of one round over ``rows`` rows that hold ``live_tokens``
    tokens of context between them: one verify pass of the target over
    ``n_draft + 1`` tokens a row, then one pass of the module over the same.

    Bytes: the target's held weights once but for the embedding table
    (gathered, not streamed), the module's once, the head a second time
    (the module's logits), the latent cache of the live tokens once a pass
    in each pass's layers.  Operations: each pass multiplies its tokens by
    what a token meets here, and absorbed attention over the live context."""
    chunk = n_draft + 1
    H, V = arch["hidden"], arch["vocab_padded"]
    latent = (arch["kv_rank"] + arch["rope"]) * dtype_bytes

    def one_pass(a):
        w_bytes = sum(layer_params(a, r) for r in _layers(a)) * dtype_bytes
        cache = live_tokens * latent * a["layers"]
        per_token = sum(layer_params_per_token(a, r, held_share)
                        for r in _layers(a)) + H * V
        if a["kind"] == "mtp":
            w_bytes += 2 * H * H * dtype_bytes
            per_token += 2 * H * H
        flops = 2.0 * per_token * chunk * rows + a["layers"] * chunk \
            * live_tokens * absorbed_pair_flops(a)
        return w_bytes + H * V * dtype_bytes + cache, flops

    t_bytes, t_flops = one_pass(arch)
    d_bytes, d_flops = one_pass(draft)
    return {"bytes": t_bytes + d_bytes, "flops": t_flops + d_flops}


def serve_flops(arch: Dict, prompt_tokens: float, output_tokens: float,
                context_token_products: float) -> float:
    """Useful work of a serving window, the target alone: every prompt
    token admitted and every output token emitted goes once through what a
    token meets here and the head, and attends over its context at the
    expanded form's cost (the fewest operations that give the result; the
    absorbed form a decode round runs does more to read less)."""
    per_token = sum(layer_params_per_token(arch, r) for r in _layers(arch)) \
        + arch["hidden"] * arch["vocab_padded"]
    return 2.0 * per_token * (prompt_tokens + output_tokens) \
        + arch["layers"] * context_token_products * expanded_pair_flops(arch)
