"""Plain reference of the ``keye_moe`` architecture (the language model of
Keye-VL-2.0-30B-A3B): ``jax.numpy`` in float32, every matrix product at
``precision=HIGHEST`` (what ``jax.default_matmul_precision("highest")``
sets, said at each product), no cache, no kernels, nothing of the program
imported.  Of the dense reference
it takes ``matmul``, the roundings of the controls' lower precisions, which
knows no layer.

The layer, each departure from the published description stated by the
configuration file (``assumed``):

- block: ``h = x + Attn(N1(x))``, ``y = h + Experts(N2(h))``, RMSNorm;
- heads: ``q = RoPE(Nq(W_q u))`` (``heads`` of ``head_dim``), ``k =
  RoPE(Nk(W_k u))``, ``v = W_v u`` (``kv_heads``), ``Nq``/``Nk`` RMSNorm over
  each head; RoPE in split halves, and as M-RoPE the rotary frequencies in
  three contiguous runs (``mrope``), each turned by a position stream of its
  own (text: the same position in all three);
- indexer: ``qI = RoPE(W_qI u)`` (``index_heads`` of ``index_dim``), ``kI =
  RoPE(LN(W_kI u))`` (one head, LayerNorm with scale and bias), ``w = W_w u /
  sqrt(index_heads * index_dim)``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
  kI[s])`` for ``s <= t``;
- selection, by sorting: the ``select_top_k`` keys of largest ``I[t, .]``
  (all while ``t + 1 <= select_top_k``), a tie to the lower slot;
- attention over the selected keys only, grouped queries, scale
  ``1/sqrt(head_dim)``;
- experts: ``softmax`` over all ``router`` outputs, top-k, weights ``p /
  sum(p)``; ``sum_e w_e Expert_e(z)`` over the chosen experts that are held
  (``held`` from ``held_start``), every expert SwiGLU; no shared expert.

Weights are asked for a group at a time through ``get(group)``: ``top``,
``L<i>`` and the expert stacks ``L<i>eg``, ``L<i>eu``, ``L<i>ed``.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.decoder import matmul

Q_BLOCK = 256       # queries scored and attended at a time


def _rms(arch, x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + arch["eps"]) * scale


def _layer_norm(arch, x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + arch["eps"]) * scale + bias


def rope(x, streams, theta, sections=None):
    """Split halves over the last axis of ``x`` ``[S, heads, d]``.
    ``streams`` is ``[3, S]``: frequency ``i`` turns by the stream of the
    run of ``sections`` it falls in; without ``sections`` by stream 0."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    which = jnp.zeros((d // 2,), jnp.int32) if sections is None else \
        jnp.asarray([r for r, n in enumerate(sections) for _ in range(n)])
    pos = streams.astype(jnp.float32)[which].T                  # [S, d/2]
    ang = (pos * freqs)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def select(scores, top_k):
    """``[Q, S]`` bool: each row's ``top_k`` largest ``scores``, by sorting;
    of equal scores the lower slot first; ``-inf`` is never chosen."""
    Q, S = scores.shape
    order = jnp.argsort(-(scores + 0.0), axis=-1, stable=True)
    first = order[:, :min(top_k, S)]
    chosen = jnp.zeros((Q, S), bool).at[jnp.arange(Q)[:, None], first] \
        .set(True)
    return chosen & (scores > -jnp.inf)


def attention(arch: Dict, prec: str, u, w: Dict, streams, blocks):
    """Selected attention of one row ``u`` ``[S, H]`` (the block's normed
    input); ``streams`` ``[3, S]`` positions, stream 0 the token's place.
    Only the first ``blocks`` blocks of queries are computed (a traced
    number: the blocks that hold tokens; the padding past them attends
    nothing and gives nought, which no token before it can see)."""
    S = u.shape[0]
    nh, kv, D = arch["heads"], arch["kv_heads"], arch["head_dim"]
    J, d = arch["index_heads"], arch["index_dim"]
    theta, positions = arch["rope_theta"], streams[0]
    q = _rms(arch, matmul(prec, "sd,df->sf", u, w["q.w"]).reshape(S, nh, D),
             w["q_norm.scale"])
    k = _rms(arch, matmul(prec, "sd,df->sf", u, w["k.w"]).reshape(S, kv, D),
             w["k_norm.scale"])
    v = matmul(prec, "sd,df->sf", u, w["v.w"]).reshape(S, kv, D)
    q = rope(q, streams, theta, arch["mrope"])
    k = rope(k, streams, theta, arch["mrope"])
    q_i = rope(matmul(prec, "sd,df->sf", u, w["iq.w"]).reshape(S, J, d),
               streams, theta)
    k_i = rope(_layer_norm(arch, matmul(prec, "sd,df->sf", u, w["ik.w"]),
                           w["ik_norm.scale"], w["ik_norm.bias"])[:, None],
               streams, theta)[:, 0]                               # [S, d]
    w_i = matmul(prec, "sd,dj->sj", u, w["iw.w"]) * (J * d) ** -0.5

    # the heads lead: the CPU multiplies bfloat16 operands (the controls)
    # only where the batch axes come first
    qg = q.reshape(S, kv, nh // kv, D).transpose(1, 2, 0, 3)   # [kv,G,S,D]
    kg, vg = k.swapaxes(0, 1), v.swapaxes(0, 1)                # [kv,S,D]
    q_it = q_i.swapaxes(0, 1)                                  # [J,S,d]

    qb = min(Q_BLOCK, S)
    if S % qb:
        raise ValueError(f"a row of {S} positions is not whole blocks of "
                         f"{qb} queries; pad it")

    def block(lo):
        rows = lo + jnp.arange(qb)
        dots = matmul(prec, "jqd,kd->jqk",
                      jax.lax.dynamic_slice_in_dim(q_it, lo, qb, 1), k_i)
        w_b = jax.lax.dynamic_slice_in_dim(w_i, lo, qb, 0)      # [Q, J]
        index = jnp.sum(w_b.T[:, :, None] * jax.nn.relu(dots), axis=0)
        seen = positions[None, :] <= positions[rows][:, None]
        chosen = select(jnp.where(seen, index, -jnp.inf),
                        arch["select_top_k"])
        s = matmul(prec, "gnqd,gkd->gnqk",
                   jax.lax.dynamic_slice_in_dim(qg, lo, qb, 2), kg) \
            * D ** -0.5
        p = jax.nn.softmax(jnp.where(chosen[None, None], s, -jnp.inf), -1)
        return matmul(prec, "gnqk,gkd->gnqd", p, vg)           # [kv,G,Q,D]

    def turn(i, outs):
        return jax.lax.dynamic_update_slice_in_dim(
            outs, block(i * qb)[None], i, axis=0)

    outs = jax.lax.fori_loop(                              # [n,kv,G,Q,D]
        0, blocks, turn,
        jnp.zeros((S // qb, kv, nh // kv, qb, D), jnp.float32))
    att = outs.transpose(0, 3, 1, 2, 4).reshape(S, nh * D)
    return matmul(prec, "sf,fd->sd", att, w["o.w"])


def route(arch: Dict, prec: str, z, router):
    """``[S, router]`` weights: ``p / sum(p)`` on each token's top-k experts
    (``p`` the softmax over all of them), nought elsewhere."""
    p = jax.nn.softmax(matmul(prec, "sd,de->se", z, router), axis=-1)
    top, idx = jax.lax.top_k(p, arch["top_k"])
    if arch["norm_topk"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    rows = jnp.arange(z.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, idx].set(top)


def _swiglu(prec, x, gate, up, down):
    h = jax.nn.silu(matmul(prec, "sd,df->sf", x, gate)) \
        * matmul(prec, "sd,df->sf", x, up)
    return matmul(prec, "sf,fd->sd", h, down)


def experts(arch: Dict, prec: str, z, w: Dict, ew: Dict):
    """The held share of the expert layer for one row, an expert at a time
    over every token (a token an expert was not chosen for has weight
    nought)."""
    lo = arch["held_start"]
    weights = route(arch, prec, z, w["router.w"])[:, lo:lo + arch["held"]]

    def one(acc, e):
        gate, up, down, col = e
        return acc + col[:, None] * _swiglu(prec, z, gate, up, down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(z),
                        (ew["eg"], ew["eu"], ew["ed"], weights.T))
    return y


def layer(arch: Dict, prec: str, x, w: Dict, ew: Dict, streams, blocks):
    """One block of one row; ``w`` the layer's leaves by their short names,
    ``ew`` its three expert stacks (``eg``, ``eu``, ``ed``); ``blocks`` as
    :func:`attention` takes it."""
    h = x + attention(arch, prec, _rms(arch, x, w["ln1.scale"]), w, streams,
                      blocks)
    return h + experts(arch, prec, _rms(arch, h, w["ln2.scale"]), w, ew)


def head(arch: Dict, prec: str, x, scale, kernel):
    return matmul(prec, "sd,dv->sv", _rms(arch, x, scale), kernel)


@functools.lru_cache(maxsize=None)
def _jitted(what: str, sizes: tuple, prec: str):
    """One compiled function per (what, architecture, precision), kept for
    the life of the process so that a second seed re-traces nothing."""
    fn = {"layer": layer, "head": head}[what]
    return jax.jit(functools.partial(fn, dict(sizes), prec))


def _short(prefix: str, tree: Dict) -> Dict:
    return {k[len(prefix):]: v for k, v in tree.items()
            if k.startswith(prefix)}


def hidden_states(arch: Dict, prec: str, get: Callable, row, streams,
                  prefix: str = "", tokens: int = None):
    """The last block's output (before the final norm) at every position of
    ``row`` ``[S]``, a layer's weights asked for as it is reached.  Of a
    row padded past its ``tokens`` only the blocks that hold tokens are
    attended."""
    x = get(prefix + "top")[prefix + "embed"][row].astype(jnp.float32)
    sizes = tuple(sorted(arch.items()))
    S = row.shape[0]
    qb = min(Q_BLOCK, S)
    blocks = jnp.int32(-(-(S if tokens is None else tokens) // qb))
    for i in range(arch["layers"]):
        L = f"{prefix}L{i}"
        ew = {k: get(L + k)[f"{L}{k}.w"] for k in ("eg", "eu", "ed")}
        x = _jitted("layer", sizes, prec)(x, _short(L + ".", get(L)), ew,
                                          streams, blocks)
    return x


def full_logits(arch: Dict, prec: str, get: Callable, row, streams=None):
    """Logits at every position of ``row`` ``[S]``, one causal pass; the
    tests' full forward.  A row longer than ``Q_BLOCK`` is padded at the
    end to whole blocks.  ``streams`` ``[3, S]``: M-RoPE's positions (text:
    equal)."""
    row = jnp.asarray(row, jnp.int32)
    n = row.shape[0]
    pad = -n % Q_BLOCK if n > Q_BLOCK else 0
    if streams is None:
        streams = jnp.broadcast_to(jnp.arange(n), (3, n))
    row = jnp.pad(row, (0, pad))
    streams = jnp.concatenate(
        [streams, streams[:, -1:] + 1 + jnp.arange(pad)[None]], axis=1)
    x = hidden_states(arch, prec, get, row, streams, tokens=n)
    top = get("top")
    return _jitted("head", tuple(sorted(arch.items())), prec)(
        x, top["lnf.scale"], top["head"])[:n]


def served_logits(arch: Dict, prec: str, get: Callable, tokens, first: int,
                  count: int, pad_to: int = 512, count_pad: int = 64):
    """Logits that predict ``tokens[first : first+count]`` from one causal
    pass over the whole row, as ``reference/decoder.py``'s of the same
    name: the row padded at the end to a multiple of ``pad_to``, queries in
    blocks of ``Q_BLOCK`` (a row of 20,480 positions never has more than
    256 x 20,480 scores a head alive), the head over a multiple of
    ``count_pad`` positions."""
    n = len(tokens)
    S = -(-n // pad_to) * pad_to
    if S > Q_BLOCK:
        S = -(-S // Q_BLOCK) * Q_BLOCK
    row = jnp.zeros((S,), jnp.int32).at[:n].set(jnp.asarray(tokens))
    streams = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (3, S))
    x = hidden_states(arch, prec, get, row, streams, tokens=n)
    count_pad = -(-count // count_pad) * count_pad
    idx = jnp.clip(first - 1 + jnp.arange(count_pad), 0, S - 1)
    top = get("top")
    out = _jitted("head", tuple(sorted(arch.items())), prec)(
        x[idx], top["lnf.scale"], top["head"])
    return out[:count]
