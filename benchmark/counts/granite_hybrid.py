"""Operations and bytes of the ``granite_hybrid`` architecture, from shapes
alone: the ``counts`` of ``archs/granite_hybrid.py``.

Counted from the configuration and the live lengths, never from the HLO.  A
multiply-add is two operations.  ``arch`` is what
``archs/granite_hybrid.py``'s ``normalise`` returns, or its ``draft``.
Every parameter is held in memory (the norms and a mixer's vectors with
the matrices); a token is multiplied by all of them but the embedding
table, which the tied head reads whole.  A ``mamba`` layer keeps a float32
state of ``ssm_heads x ssm_head_dim x d_state`` a row, and a token costs it
``4 x`` that many operations (the decay and the outer product into the
state, the state times ``C`` out of it).
"""

from __future__ import annotations

from typing import Dict


def mamba_params(arch: Dict) -> int:
    """One ``mamba`` mixer: ``W_in``, the convolution and its bias, ``A_log``,
    ``D``, ``dt_bias``, the gated norm's scale, ``W_out``."""
    H, sh, P, N = (arch["hidden"], arch["ssm_heads"], arch["ssm_head_dim"],
                   arch["d_state"])
    E = sh * P
    W = E + 2 * N
    return H * (E + W + sh) + arch["d_conv"] * W + W + 3 * sh + E + E * H


def attention_params(arch: Dict) -> int:
    """One ``attention`` mixer's ``q``, ``k``, ``v``, ``o``."""
    H, D = arch["hidden"], arch["head_dim"]
    return 2 * H * arch["heads"] * D + 2 * H * arch["kv_heads"] * D


def mlp_params(arch: Dict) -> int:
    return 3 * arch["hidden"] * arch["ffn"]


def layer_params(arch: Dict, kind: str) -> int:
    """One layer: its mixer, the MLP and the two norms."""
    mixer = mamba_params(arch) if kind == "mamba" else attention_params(arch)
    return mixer + mlp_params(arch) + 2 * arch["hidden"]


def held_params(arch: Dict) -> int:
    """Every parameter of the model: the layers, the tied table, the final
    norm."""
    return sum(layer_params(arch, k) for k in arch["layer_types"]) \
        + arch["vocab_padded"] * arch["hidden"] + arch["hidden"]


def weights_bytes(arch: Dict, draft: Dict, dtype_bytes: int = 2) -> int:
    """What a server holds of target and draft."""
    return (held_params(arch) + held_params(draft)) * dtype_bytes


def layers_of(arch: Dict, kind: str) -> int:
    return sum(1 for k in arch["layer_types"] if k == kind)


def state_bytes(arch: Dict) -> int:
    """One row's float32 state in one ``mamba`` layer."""
    return arch["ssm_heads"] * arch["ssm_head_dim"] * arch["d_state"] * 4


def state_token_flops(arch: Dict) -> float:
    return 4.0 * arch["ssm_heads"] * arch["ssm_head_dim"] * arch["d_state"]


def kv_bytes_per_token(arch: Dict, dtype_bytes: int = 2) -> int:
    """K and V of one token in one ``attention`` layer."""
    return 2 * arch["kv_heads"] * arch["head_dim"] * dtype_bytes


def decode_round_cost(arch: Dict, draft: Dict, n_draft: int,
                      live_tokens: float, rows: int, dtype_bytes: int = 2,
                      ) -> Dict:
    """Least work of one speculative round over ``rows`` rows that hold
    ``live_tokens`` tokens of context between them: one verify pass of the
    target over ``n_draft + 1`` tokens a row, ``n_draft + 1`` single-token
    passes of the draft.

    Bytes a pass: the model's parameters once; in each ``mamba`` layer each
    row's state read once, and written once where the pass commits (the
    verify pass and the draft's first step: the draft's later steps leave it
    as it is, and the program's writing it back unchanged is not counted),
    with the convolution's ``d_conv - 1`` raw inputs and the
    ``n_draft`` held pending; in each ``attention`` layer K and V of the
    live tokens once.  Operations: each pass multiplies its tokens by every
    parameter but the table's rows it gathers (the head multiplies them all),
    attends over the live context and steps the state."""
    width = arch["ssm_heads"] * arch["ssm_head_dim"] + 2 * arch["d_state"]
    per_row = live_tokens / max(1, rows)

    def one_pass(a, queries_per_row, commits):
        queries = queries_per_row * rows
        mamba, attn = layers_of(a, "mamba"), layers_of(a, "attention")
        window = (a["d_conv"] - 1 + n_draft) * width * dtype_bytes
        state = mamba * rows * (state_bytes(a) * (2 if commits else 1)
                                + window * (2 if commits else 1))
        kv = attn * live_tokens * kv_bytes_per_token(a, dtype_bytes)
        flops = 2.0 * queries * (held_params(a) - a["hidden"])
        flops += attn * queries * per_row * 4.0 * a["heads"] * a["head_dim"]
        flops += mamba * queries * state_token_flops(a)
        return held_params(a) * dtype_bytes + state + kv, flops

    t_bytes, t_flops = one_pass(arch, n_draft + 1, True)
    d_bytes, d_flops = one_pass(draft, 1, True)
    r_bytes, r_flops = one_pass(draft, 1, False)
    return {"bytes": t_bytes + d_bytes + n_draft * r_bytes,
            "flops": t_flops + d_flops + n_draft * r_flops}


def serve_flops(arch: Dict, prompt_tokens: float, output_tokens: float,
                context_token_products: float) -> float:
    """Useful work of a serving window, the target alone: every prompt
    token admitted and every output token emitted goes once through every
    parameter but the gathered table rows (the head multiplies them all)
    and steps every ``mamba`` layer's state; attention runs over the
    context each token could see (``context_token_products``)."""
    tokens = prompt_tokens + output_tokens
    per_token = 2.0 * (held_params(arch) - arch["hidden"]) \
        + layers_of(arch, "mamba") * state_token_flops(arch)
    return per_token * tokens + layers_of(arch, "attention") \
        * context_token_products * 4.0 * arch["heads"] * arch["head_dim"]


def ssm_kernel_cost(arch: Dict, S: int, rows: int,
                    dtype_bytes: int = 2) -> Dict:
    """One call of the round's state-update kernel (``ops/ssm.py``, named
    ``ssm_decode_s<S>_r<rows>``) over one ``mamba`` layer: each row's state
    read once and written once in place (a pass that commits nothing
    writes it back as it was); the pass's ``S`` tokens' ``x``, ``B`` and
    ``C``.  Operations: ``S`` steps of the state a row."""
    inputs = S * (arch["ssm_heads"] * arch["ssm_head_dim"]
                  + 2 * arch["d_state"]) * dtype_bytes
    return {"flops": rows * S * state_token_flops(arch),
            "bytes": rows * (2 * state_bytes(arch) + inputs)}
