"""Operations and bytes a dense decoder needs, from shapes alone: the
``counts`` of ``archs/decoder.py``.

Counted from the configuration and the live lengths, never from the HLO, so
the same work reads the same whatever later implements it.  A multiply-add
is two operations.  Causal attention is counted at the half a causal kernel
needs; recomputation is never counted.  ``arch`` is what
``archs/decoder.py``'s ``normalise`` returns.
"""

from __future__ import annotations

from typing import Dict


def layer_params(arch: Dict) -> int:
    """Parameters of one block's matrices (biases and norms left out: they
    are under a thousandth of the matrices)."""
    H, F = arch["hidden"], arch["ffn"]
    q_dim = arch["heads"] * arch["head_dim"]
    kv_dim = arch["kv_heads"] * arch["head_dim"]
    mlp = 3 * H * F if arch["mlp"] == "swiglu" else 2 * H * F
    return H * q_dim + 2 * H * kv_dim + q_dim * H + mlp


def matmul_params(arch: Dict) -> int:
    """Parameters every token is multiplied by: the blocks and the output
    head (the embedding is a gather; a tied head still multiplies)."""
    return arch["layers"] * layer_params(arch) + arch["hidden"] * arch["vocab_padded"]


def total_params(arch: Dict) -> int:
    """Parameters held in memory (matrices, embedding, positions)."""
    n = arch["layers"] * layer_params(arch) + arch["hidden"] * arch["vocab_padded"]
    if not arch["tie"]:
        n += arch["hidden"] * arch["vocab_padded"]
    if arch["positions"] == "learned":
        n += arch["max_pos"] * arch["hidden"]
    return n


def attention_flops(arch: Dict, q_len: int, kv_len: int, causal: bool) -> float:
    """QK^T and PV of one layer for one row: 4 * q * kv * heads * head_dim,
    halved where q and kv are the same causal sequence."""
    full = 4.0 * q_len * kv_len * arch["heads"] * arch["head_dim"]
    return full / 2 if causal else full


def train_flops_per_token(arch: Dict, seq: int) -> float:
    """Forward plus backward (backward = twice the forward) of one token of
    a sequence of ``seq``: 6 * matmul parameters + 3 * causal attention."""
    attn = arch["layers"] * attention_flops(arch, seq, seq, True) / seq
    return 6.0 * matmul_params(arch) + 3.0 * attn


def flash_kernel_cost(arch: Dict, batch: int, seq: int, dtype_bytes: int = 2) -> Dict:
    """Operations and bytes of the three flash kernels (forward, dq, dkv)
    over all layers of one training step.  Forward: QK^T, PV.  dq: QK^T
    again, dP = dO V^T, dQ = dS K.  dkv: QK^T again, dP, dV = P^T dO,
    dK = dS^T Q.  Each product is 2*S*S*D per head, halved for causality.
    Bytes: every operand and result once (q, k, v, o, do, dq, dk, dv and
    the row statistics are small beside them)."""
    H, D, L = arch["heads"], arch["head_dim"], arch["layers"]
    prod = 2.0 * seq * seq * D / 2 * H * batch * L
    tensor = float(batch * seq * H * D * dtype_bytes * L)
    return {
        "fwd": {"flops": 2 * prod, "bytes": 4 * tensor},        # q k v -> o
        "dq": {"flops": 3 * prod, "bytes": 6 * tensor},         # q k v o do -> dq
        "dkv": {"flops": 4 * prod, "bytes": 7 * tensor},        # q k v o do -> dk dv
    }


def kv_bytes_per_token(arch: Dict, dtype_bytes: int = 2) -> int:
    """K and V of one token in every layer."""
    return 2 * arch["kv_heads"] * arch["head_dim"] * dtype_bytes * arch["layers"]


def decode_round_cost(arch: Dict, draft: Dict, n_draft: int, live_tokens: float,
                      rows: int, dtype_bytes: int = 2) -> Dict:
    """Least work of one speculative round over ``rows`` rows that hold
    ``live_tokens`` tokens of context between them.

    Bytes: the target's weights once (one verify pass), the draft's weights
    ``n_draft + 1`` times (one pass per chain step), K and V of the live
    tokens once per model pass.  Operations: each pass multiplies its new
    tokens by the model's matrices and attends over the live context."""
    chunk = n_draft + 1

    def one_pass(a, new_tokens_per_row):
        w_bytes = total_params(a) * dtype_bytes
        if a["tie"] is False:
            # the embedding table is gathered, not streamed
            w_bytes -= a["hidden"] * a["vocab_padded"] * dtype_bytes
        kv = live_tokens * kv_bytes_per_token(a, dtype_bytes)
        flops = 2.0 * matmul_params(a) * new_tokens_per_row * rows
        flops += a["layers"] * 4.0 * new_tokens_per_row * live_tokens \
            * a["heads"] * a["head_dim"]
        return w_bytes + kv, flops

    t_bytes, t_flops = one_pass(arch, chunk)
    d_bytes, d_flops = one_pass(draft, 1)
    return {"bytes": t_bytes + chunk * d_bytes,
            "flops": t_flops + chunk * d_flops}


def serve_flops(arch: Dict, prompt_tokens: float, output_tokens: float,
                context_token_products: float) -> float:
    """Useful work of a serving window: every prompt token admitted and
    every output token emitted goes once through the target's matrices, and
    attends over its context (``context_token_products`` = the sum over those
    tokens of the context length each attended to)."""
    dense = 2.0 * matmul_params(arch) * (prompt_tokens + output_tokens)
    attn = arch["layers"] * 4.0 * context_token_products \
        * arch["heads"] * arch["head_dim"]
    return dense + attn
