"""Plain reference of the ``pangu_moe`` architecture (openPangu-Ultra-MoE,
the DeepSeek-V3 family): ``jax.numpy`` in float32 at ``precision=HIGHEST``,
no cache, no kernels, nothing of the program imported.  Of the dense
reference it takes ``matmul``, the roundings of the controls' lower
precisions, which knows no layer.

The published equations, each departure stated by the configuration file:

- block (sandwich norm): ``x + N2(Attn(N1(x)))``, ``x + N4(MLP(N3(x)))``;
- latent attention, expanded form only: ``c_q = norm(x W_qa)``,
  ``q = c_q W_qb`` -> per head ``[nope | rope]``; ``[c_kv | k_rope] =
  x W_kva``, ``c_kv = norm(c_kv)``; ``[k_nope_h | v_h] = c_kv W_kvb``; RoPE
  (split halves) on ``q_rope`` and on the one ``k_rope`` all heads share;
  scores ``(q_nope_h k_nope_h + q_rope_h k_rope) / sqrt(nope + rope)``;
- experts: router in float32, ``s = sigmoid(x W_r)`` over all ``router``
  experts, top-k of ``s``, weights ``s / sum(s) * route_scale``;
  ``y = Shared(x) + sum over the top-k that are held of w_e Expert_e(x)``,
  every expert SwiGLU.  It is given the same share of the experts as the
  program (``held`` from ``held_start``); what the others would add is left
  out;
- MTP (depth 1): ``h' = W_eh [norm_e(Emb(t_{i+1})) | norm_h(h_i)]``, one
  expert block, a final norm of its own, the target's head.

Weights are asked for a group at a time through ``get(group)``: ``top``,
``L<i>`` and, for an expert layer, ``L<i>eg``, ``L<i>eu``, ``L<i>ed``.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.decoder import matmul


def _norm(arch, x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + arch["eps"]) * scale


def _rope(x, positions, theta):
    """Split halves; ``x`` is ``[S, ..., d]``, ``positions`` ``[S]``."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * freqs          # [S, d/2]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def attention(arch: Dict, prec: str, x, w: Dict, positions,
              q_block: int = 512):
    """Expanded latent attention of one row ``x`` ``[S, H]``."""
    S = x.shape[0]
    nh, dn, dr, dv = arch["heads"], arch["nope"], arch["rope"], arch["v_dim"]
    C = arch["kv_rank"]
    c_q = _norm(arch, matmul(prec, "sd,dr->sr", x, w["q_a.w"]),
                w["q_a_norm.scale"])
    q = matmul(prec, "sr,rf->sf", c_q, w["q_b.w"]).reshape(S, nh, dn + dr)
    kv = matmul(prec, "sd,dc->sc", x, w["kv_a.w"])
    c_kv = _norm(arch, kv[:, :C], w["kv_a_norm.scale"])
    k_rope = _rope(kv[:, C:], positions, arch["rope_theta"])        # [S, dr]
    q = jnp.concatenate(
        [q[..., :dn], _rope(q[..., dn:], positions, arch["rope_theta"])], -1)
    heads = matmul(prec, "sc,cf->sf", c_kv, w["kv_b.w"]).reshape(
        S, nh, dn + dv)
    k = jnp.concatenate(
        [heads[..., :dn], jnp.broadcast_to(k_rope[:, None, :], (S, nh, dr))],
        -1)
    # the heads lead: the CPU multiplies bfloat16 operands (the controls)
    # only where the batch axes come first
    q, k, v = (t.swapaxes(0, 1) for t in (q, k, heads[..., dn:]))
    outs = []
    for lo in range(0, S, q_block):
        hi = min(S, lo + q_block)
        s = matmul(prec, "hqd,hkd->hqk", q[:, lo:hi], k) * (dn + dr) ** -0.5
        mask = positions[None, :] <= positions[lo:hi, None]
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        outs.append(matmul(prec, "hqk,hkd->hqd", p, v))
    att = jnp.concatenate(outs, axis=1).swapaxes(0, 1).reshape(S, nh * dv)
    return matmul(prec, "sf,fd->sd", att, w["o.w"])


def _swiglu(prec, x, gate, up, down):
    h = jax.nn.silu(matmul(prec, "sd,df->sf", x, gate)) \
        * matmul(prec, "sd,df->sf", x, up)
    return matmul(prec, "sf,fd->sd", h, down)


def route(arch: Dict, prec: str, x, router):
    """``[S, router]`` weights: ``s / sum(s) * route_scale`` on each token's
    top-k experts, nought elsewhere."""
    scores = jax.nn.sigmoid(matmul(prec, "sd,de->se", x, router))
    top, idx = jax.lax.top_k(scores, arch["top_k"])
    if arch["norm_topk"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(top * arch["route_scale"])


def experts(arch: Dict, prec: str, x, w: Dict, ew: Dict):
    """The expert layer's MLP of one row: the shared experts and the held
    share of the routed ones, an expert at a time over every token (a
    token an expert was not chosen for has weight nought)."""
    lo = arch["held_start"]
    weights = route(arch, prec, x, w["router.w"])[:, lo:lo + arch["held"]]

    def one(acc, e):
        gate, up, down, col = e
        return acc + col[:, None] * _swiglu(prec, x, gate, up, down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (ew["eg"], ew["eu"], ew["ed"], weights.T))
    if arch["shared"]:
        y = y + _swiglu(prec, x, w["sh_gate.w"], w["sh_up.w"],
                        w["sh_down.w"])
    return y


def layer(arch: Dict, prec: str, routed: bool, x, w: Dict, ew: Dict,
          positions):
    """One block of one row; ``w`` the layer's leaves by their short names,
    ``ew`` the three expert stacks (``eg``, ``eu``, ``ed``) of a routed
    layer."""
    x = x + _norm(arch, attention(arch, prec, _norm(arch, x, w["ln1.scale"]),
                                  w, positions), w["ln1p.scale"])
    h = _norm(arch, x, w["ln2.scale"])
    y = experts(arch, prec, h, w, ew) if routed else _swiglu(
        prec, h, w["gate.w"], w["up.w"], w["down.w"])
    return x + _norm(arch, y, w["ln2p.scale"])


def head(arch: Dict, prec: str, x, scale, kernel):
    return matmul(prec, "sd,dv->sv", _norm(arch, x, scale), kernel)


def mtp_input(arch: Dict, prec: str, emb, hidden, w: Dict):
    """``W_eh [norm_e(emb) | norm_h(hidden)]``."""
    return matmul(prec, "sd,dh->sh", jnp.concatenate(
        [_norm(arch, emb, w["enorm.scale"]),
         _norm(arch, hidden, w["hnorm.scale"])], -1), w["eh_proj.w"])


@functools.lru_cache(maxsize=None)
def _jitted(what: str, sizes: tuple, prec: str, *static):
    """One compiled function per (what, architecture, precision), kept for
    the life of the process so that a second seed re-traces nothing."""
    fn = {"layer": layer, "head": head, "mtp_input": mtp_input}[what]
    return jax.jit(functools.partial(fn, dict(sizes), prec, *static))


def _short(prefix: str, tree: Dict) -> Dict:
    return {k[len(prefix):]: v for k, v in tree.items()
            if k.startswith(prefix)}


def _blocks(arch: Dict, prec: str, get: Callable, x, positions,
            prefix: str = ""):
    """``x`` through every block of a model whose leaves are named
    ``<prefix>L<i>...``, a layer's weights asked for as it is reached."""
    sizes = tuple(sorted(arch.items()))
    for i in range(arch["layers"]):
        L = f"{prefix}L{i}"
        routed = i >= arch["first_dense"]
        ew = {k: get(L + k)[f"{L}{k}.w"] for k in ("eg", "eu", "ed")} \
            if routed else {}
        x = _jitted("layer", sizes, prec, routed)(
            x, _short(L + ".", get(L)), ew, positions)
    return x


def hidden_states(arch: Dict, prec: str, get: Callable, row, positions):
    """The target's last block output (before its final norm) at every
    position of ``row`` ``[S]``."""
    x = get("top")["embed"][row].astype(jnp.float32)
    return _blocks(arch, prec, get, x, positions)


def served_logits(arch: Dict, prec: str, get: Callable, tokens, first: int,
                  count: int, pad_to: int = 512, count_pad: int = 64):
    """Logits that predict ``tokens[first : first+count]`` from one causal
    pass over the whole row, as ``reference/decoder.py``'s of the same
    name: the row padded at the end to a multiple of ``pad_to``, the head
    over a multiple of ``count_pad`` positions."""
    n = len(tokens)
    S = -(-n // pad_to) * pad_to
    row = jnp.zeros((S,), jnp.int32).at[:n].set(jnp.asarray(tokens))
    positions = jnp.arange(S, dtype=jnp.int32)
    x = hidden_states(arch, prec, get, row, positions)
    count_pad = -(-count // count_pad) * count_pad
    idx = jnp.clip(first - 1 + jnp.arange(count_pad), 0, S - 1)
    top = get("top")
    out = _jitted("head", tuple(sorted(arch.items())), prec)(
        x[idx], top["lnf.scale"], top["head"])
    return out[:count]


def mtp_logits(arch: Dict, draft: Dict, prec: str, get: Callable,
               get_draft: Callable, tokens, prefix: str = "draft."):
    """What the multi-token-prediction module proposes at every position
    ``i`` of ``tokens`` but the last: logits for ``t_{i+2}`` from the
    target's ``h_i`` and ``Emb(t_{i+1})``.  ``get_draft(group)`` gives the
    module's leaves (``<prefix>top``, ``<prefix>L0`` ...); embedding and
    head are the target's."""
    row = jnp.asarray(tokens, jnp.int32)
    S = row.shape[0] - 1
    positions = jnp.arange(S, dtype=jnp.int32)
    hidden = hidden_states(arch, prec, get, row[:-1], positions)
    top, d_top = get("top"), _short(prefix, get_draft(prefix + "top"))
    sizes = tuple(sorted(draft.items()))
    x = _jitted("mtp_input", sizes, prec)(
        top["embed"][row[1:]].astype(jnp.float32), hidden, d_top)
    x = _blocks(draft, prec, get_draft, x, positions, prefix)
    return _jitted("head", sizes, prec)(x, d_top["lnf.scale"], top["head"])
