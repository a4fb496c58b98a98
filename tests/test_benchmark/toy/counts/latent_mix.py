"""Counts of the toy architecture ``latent_mix``, from shapes alone: every
expert multiplies every token (the mix is dense), and a token's cache is one
latent a layer.  It holds what the readers of its cells call
(``readers/round_bytes.py``: ``decode_round_cost``) and no more: a reader
that asks for a count this family lacks reads nothing."""

from __future__ import annotations

from typing import Dict


def layer_params(arch: Dict, i: int) -> int:
    H, C = arch["hidden"], arch["latent"]
    qk = arch["heads"] * arch["head_dim"]
    attn = 2 * H * qk + H * C + 2 * C * qk
    if i == 0:
        return attn + 2 * H * arch["ffn"]
    E, Fe = arch["experts"], arch["expert_ffn"]
    return attn + H * E + 2 * E * H * Fe


def matmul_params(arch: Dict) -> int:
    return (sum(layer_params(arch, i) for i in range(arch["layers"]))
            + arch["hidden"] * arch["vocab_padded"])


def total_params(arch: Dict) -> int:
    return matmul_params(arch) + arch["hidden"] * arch["vocab_padded"]


def cache_bytes_per_token(arch: Dict, dtype_bytes: int = 2) -> int:
    return arch["latent"] * dtype_bytes * arch["layers"]


def decode_round_cost(arch: Dict, draft: Dict, n_draft: int,
                      live_tokens: float, rows: int,
                      dtype_bytes: int = 2) -> Dict:
    chunk = n_draft + 1

    def one_pass(a, new):
        held = (matmul_params(a) * dtype_bytes
                + live_tokens * cache_bytes_per_token(a, dtype_bytes))
        qk = a["heads"] * a["head_dim"]
        # the live latents are expanded to K and V again in every pass
        flops = (2.0 * matmul_params(a) * new * rows
                 + a["layers"] * live_tokens * (
                     4.0 * a["latent"] * qk + 4.0 * new * qk))
        return held, flops

    t_bytes, t_flops = one_pass(arch, chunk)
    d_bytes, d_flops = one_pass(draft, 1)
    return {"bytes": t_bytes + chunk * d_bytes,
            "flops": t_flops + chunk * d_flops}
