"""Operations and bytes of the ``keye_moe`` architecture as this chip holds
it, from shapes alone: the ``counts`` of ``archs/keye_moe.py``.

Counted from the configuration and the live lengths, never from the HLO.  A
multiply-add is two operations.  ``arch`` is what ``archs/keye_moe.py``'s
``normalise`` returns, or its ``draft``.  Only the experts held here are
counted as held; a token is counted through ``top_k * held / router`` routed
experts, what it meets here on average when the router is even.  A query
attends ``min(keys it can see, select_top_k)`` keys and the indexer scores
every key it can see: that is the work the architecture defines, and never
the whole slab.
"""

from __future__ import annotations

from typing import Dict


def attention_params(arch: Dict) -> int:
    """One layer's ``q``, ``k``, ``v``, ``o`` (norms left out)."""
    H, D = arch["hidden"], arch["head_dim"]
    return 2 * H * arch["heads"] * D + 2 * H * arch["kv_heads"] * D


def indexer_params(arch: Dict) -> int:
    """One layer's indexer: query heads, the one key, the head weights."""
    J, d = arch["index_heads"], arch["index_dim"]
    return arch["hidden"] * (J * d + d + J)


def expert_params(arch: Dict) -> int:
    """One SwiGLU expert."""
    return 3 * arch["hidden"] * arch["expert_ffn"]


def layer_params(arch: Dict) -> int:
    """Matrix parameters one layer holds here."""
    return (attention_params(arch) + indexer_params(arch)
            + arch["hidden"] * arch["router"]
            + arch["held"] * expert_params(arch))


def layer_params_per_token(arch: Dict, held_share: float = None) -> float:
    """Parameters one token is multiplied by in one layer here.
    ``held_share`` is the share of routed slots that fall on a held expert
    (``held / router`` where the router is even)."""
    if held_share is None:
        held_share = arch["held"] / arch["router"]
    return (attention_params(arch) + indexer_params(arch)
            + arch["hidden"] * arch["router"]
            + arch["top_k"] * held_share * expert_params(arch))


def held_params(arch: Dict) -> int:
    """Matrix parameters held in memory: the blocks, embedding and head."""
    return arch["layers"] * layer_params(arch) \
        + 2 * arch["hidden"] * arch["vocab_padded"]


def weights_bytes(arch: Dict, draft: Dict, dtype_bytes: int = 2) -> int:
    """What a server holds of target and draft."""
    return (held_params(arch) + held_params(draft)) * dtype_bytes


def cache_bytes_per_token_layer(arch: Dict, dtype_bytes: int = 2) -> int:
    """K, V and the indexer's key of one token in one layer."""
    return (2 * arch["kv_heads"] * arch["head_dim"] + arch["index_dim"]) \
        * dtype_bytes


def cache_bytes_per_token(arch: Dict, draft: Dict,
                          dtype_bytes: int = 2) -> int:
    """K, V and the indexer's key of one token in every layer of both."""
    return cache_bytes_per_token_layer(arch, dtype_bytes) \
        * (arch["layers"] + draft["layers"])


def attention_pair_flops(arch: Dict) -> float:
    """One query-key pair of attention over all heads: score and value."""
    return 4.0 * arch["heads"] * arch["head_dim"]


def index_pair_flops(arch: Dict) -> float:
    """One query-key pair of the indexer: ``index_heads`` dot products of
    ``index_dim`` (the ReLU, weights and sum are small beside them)."""
    return 2.0 * arch["index_heads"] * arch["index_dim"]


def decode_round_cost(arch: Dict, draft: Dict, n_draft: int,
                      live_tokens: float, rows: int, dtype_bytes: int = 2,
                      held_share: float = None) -> Dict:
    """Least work of one speculative round over ``rows`` rows that hold
    ``live_tokens`` tokens of context between them: one verify pass of the
    target over ``n_draft + 1`` tokens a row, ``n_draft + 1`` single-token
    passes of the draft.

    Bytes a pass: the model's held weights once but for the embedding table
    (gathered, not streamed), the 16 held experts among them once; in each
    layer the indexer's keys of the live tokens once, and K and V of
    ``min(row's live tokens, select_top_k)`` keys for each query (rows taken
    as equally long).  Operations: each pass multiplies its tokens by what a
    token meets here and the head, scores every live key with the indexer
    and attends the selected ones."""
    chunk = n_draft + 1
    H, V = arch["hidden"], arch["vocab_padded"]
    per_row = live_tokens / max(1, rows)

    def one_pass(a, queries_per_row):
        kept = min(per_row, a["select_top_k"])
        queries = queries_per_row * rows
        w_bytes = (a["layers"] * layer_params(a) + H * V) * dtype_bytes
        index = live_tokens * a["index_dim"] * dtype_bytes * a["layers"]
        kv = queries * kept * 2 * a["kv_heads"] * a["head_dim"] \
            * dtype_bytes * a["layers"]
        flops = 2.0 * queries * (
            a["layers"] * layer_params_per_token(a, held_share) + H * V)
        flops += a["layers"] * queries_per_row * (
            live_tokens * index_pair_flops(a)
            + rows * kept * attention_pair_flops(a))
        return w_bytes + index + kv, flops

    t_bytes, t_flops = one_pass(arch, chunk)
    d_bytes, d_flops = one_pass(draft, 1)
    return {"bytes": t_bytes + chunk * d_bytes,
            "flops": t_flops + chunk * d_flops}


def serve_flops(arch: Dict, prompt_tokens: float, output_tokens: float,
                context_token_products: float) -> float:
    """Useful work of a serving window, the target alone: every prompt
    token admitted and every output token emitted goes once through what a
    token meets here and the head; the indexer scores every key of its
    context (``context_token_products``: the sum over those tokens of the
    context each could see) and attention runs over the selected keys, at
    most ``select_top_k`` a token (the first ``select_top_k`` tokens of a
    prompt see fewer: counted from the products where those are less)."""
    tokens = prompt_tokens + output_tokens
    per_token = arch["layers"] * layer_params_per_token(arch) \
        + arch["hidden"] * arch["vocab_padded"]
    attended = min(context_token_products, tokens * arch["select_top_k"])
    return 2.0 * per_token * tokens + arch["layers"] * (
        context_token_products * index_pair_flops(arch)
        + attended * attention_pair_flops(arch))


def select_kernel_cost(arch: Dict, chunk: int, slots: int,
                       block: int = 1024, dtype_bytes: int = 2) -> Dict:
    """One call of the admission's masked-attention kernel
    (``ops/select_attention.py:masked_attention``, named
    ``select_attention_s<chunk>_t<slots>``), averaged over the chunks of an
    admission of ``slots`` tokens: chunk ``i`` of ``slots / chunk`` sees the
    key blocks up to its last position, ``ceil(i * chunk / block)`` of
    them.  Operations: scores and values of every query head over the live
    blocks.  Bytes: the chunk's queries and outputs, K and V of the live
    blocks once a KV head, the mask's live part (a byte a pair)."""
    H, KV, D = arch["heads"], arch["kv_heads"], arch["head_dim"]
    block = min(block, slots)
    chunks = max(1, slots // chunk)
    live = sum(min(slots, -(-(i * chunk) // block) * block)
               for i in range(1, chunks + 1)) / chunks
    return {"flops": 4.0 * chunk * live * H * D,
            "bytes": 2.0 * chunk * H * D * dtype_bytes
            + 2.0 * live * KV * D * dtype_bytes + live * chunk}
