"""Plain reference of the ``granite_hybrid`` architecture (Granite 4.0-H's
``granitemoehybrid`` with no routed expert): ``jax.numpy`` in float32,
every matrix product at ``precision=HIGHEST`` (through the dense
reference's ``matmul``, which rounds the controls' lower precisions), no
cache, no kernels, nothing of the program imported.

- around the stack: embeddings times ``embedding_multiplier``; the head is
  the tied table, its logits over ``logits_scaling``;
- block (RMSNorm before each sublayer): ``x + r·mixer(N1(x))``, then ``x +
  r·W_down(silu(W_gate u) ⊙ W_up u)`` with ``u = N2(x)``, ``r`` the
  ``residual_multiplier``;
- an ``attention`` layer: ``heads`` queries over ``kv_heads`` keys of
  ``head_dim``, no rotation (NoPE), scale ``attention_multiplier``;
- a ``mamba`` layer, **one token at a time** (the definition, not the
  program's chunked form): ``[z | xBC | Δ] = W_in u``; ``xBC ←
  silu(conv(xBC) + b)``, causal and depthwise over ``d_conv`` taps; ``x``
  (``ssm_heads`` of ``ssm_head_dim``), ``B``, ``C`` (``d_state`` each, one
  group); ``Δ = softplus(Δ + dt_bias)``, ``A = -exp(A_log)``; ``S_t =
  exp(Δ_t A) S_{t-1} + Δ_t x_t ⊗ B_t``, ``y_t = S_t C_t + D x_t``; ``out =
  W_out (RMSNorm(y ⊙ silu(z)) · g)`` over all ``ssm_heads * ssm_head_dim``.

Weights are asked for a group at a time through ``get(group)``: ``top``
and ``L<i>``.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.decoder import matmul

Q_BLOCK = 256       # queries scored at a time


def _rms(arch, x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + arch["eps"]) * scale


def attention(arch: Dict, prec: str, u, w: Dict):
    """Causal grouped-query attention of one row ``u`` ``[S, H]`` with no
    position rotation, a block of queries at a time (a row that is not
    whole blocks is padded at its end, which no position before sees)."""
    n = u.shape[0]
    u = jnp.pad(u, ((0, -n % min(Q_BLOCK, n)), (0, 0)))
    S = u.shape[0]
    nh, kv, D = arch["heads"], arch["kv_heads"], arch["head_dim"]
    q = matmul(prec, "sd,df->sf", u, w["q.w"]).reshape(S, kv, nh // kv, D)
    k = matmul(prec, "sd,df->sf", u, w["k.w"]).reshape(S, kv, D)
    v = matmul(prec, "sd,df->sf", u, w["v.w"]).reshape(S, kv, D)
    # the heads lead: the CPU multiplies bfloat16 operands (the controls)
    # only where the batch axes come first
    qg, kg, vg = q.transpose(1, 2, 0, 3), k.swapaxes(0, 1), v.swapaxes(0, 1)
    qb = min(Q_BLOCK, S)

    def block(i, outs):
        lo = i * qb
        s = matmul(prec, "gnqd,gkd->gnqk",
                   jax.lax.dynamic_slice_in_dim(qg, lo, qb, 2), kg) \
            * arch["attention_multiplier"]
        seen = jnp.arange(S)[None, :] <= (lo + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jax.lax.dynamic_update_slice_in_dim(
            outs, matmul(prec, "gnqk,gkd->gnqd", p, vg)[None], i, axis=0)

    outs = jax.lax.fori_loop(0, S // qb, block, jnp.zeros(
        (S // qb, kv, nh // kv, qb, D), jnp.float32))
    att = outs.transpose(0, 3, 1, 2, 4).reshape(S, nh * D)[:n]
    return matmul(prec, "sf,fd->sd", att, w["o.w"])


def mamba(arch: Dict, prec: str, u, w: Dict, state=None):
    """The mixer of a ``mamba`` layer over one row ``u`` ``[S, H]``, the
    state stepped one token at a time from ``state`` (``[ssm_heads,
    ssm_head_dim, d_state]``; zeros).  Returns ``(out [S, H], the state
    after the last token, xBC's raw inputs [S, conv width])``."""
    S = u.shape[0]
    nh, P, N, K = (arch["ssm_heads"], arch["ssm_head_dim"], arch["d_state"],
                   arch["d_conv"])
    E = nh * P
    proj = matmul(prec, "sd,df->sf", u, w["in.w"])
    z, xbc, dt = proj[:, :E], proj[:, E:2 * E + 2 * N], proj[:, 2 * E + 2 * N:]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc], axis=0)
    conv = sum(padded[k:k + S] * w["conv.w"][k] for k in range(K)) \
        + w["conv.b"]
    conv = jax.nn.silu(conv)
    x = conv[:, :E].reshape(S, nh, P)
    B, C = conv[:, E:E + N], conv[:, E + N:]
    delta = jax.nn.softplus(dt + w["dt_bias"])                  # [S, nh]
    A = -jnp.exp(w["A_log"])

    def step(st, inp):
        x_t, B_t, C_t, d_t = inp
        st = jnp.exp(d_t * A)[:, None, None] * st \
            + (d_t[:, None] * x_t)[:, :, None] * B_t[None, None, :]
        return st, matmul(prec, "hpn,n->hp", st, C_t)

    if state is None:
        state = jnp.zeros((nh, P, N), jnp.float32)
    state, y = jax.lax.scan(step, state, (x, B, C, delta))
    y = (y + w["D"][:, None] * x).reshape(S, E) * jax.nn.silu(z)
    y = _rms(arch, y, w["gnorm.scale"])
    return matmul(prec, "se,ed->sd", y, w["out.w"]), state, xbc


def layer(arch: Dict, kind: str, prec: str, x, w: Dict):
    """One block of one row; ``w`` the layer's leaves by their short names.
    Returns ``(x, None)``, or for a ``mamba`` layer ``(x, (its state after
    the row, xBC's raw inputs))``."""
    u = _rms(arch, x, w["ln1.scale"])
    state = None
    if kind == "mamba":
        mixed, *state = mamba(arch, prec, u, w)
    else:
        mixed = attention(arch, prec, u, w)
    x = x + arch["residual_multiplier"] * mixed
    u = _rms(arch, x, w["ln2.scale"])
    h = jax.nn.silu(matmul(prec, "sd,df->sf", u, w["gate.w"])) \
        * matmul(prec, "sd,df->sf", u, w["up.w"])
    return x + arch["residual_multiplier"] * matmul(
        prec, "sf,fd->sd", h, w["down.w"]), state


def head(arch: Dict, prec: str, x, scale, table):
    return matmul(prec, "sd,vd->sv", _rms(arch, x, scale), table) \
        / arch["logits_scaling"]


@functools.lru_cache(maxsize=None)
def _jitted(what: str, sizes: tuple, prec: str, kind: str = ""):
    """One compiled function per (what, architecture, precision, layer
    kind), kept for the life of the process."""
    if what == "head":
        return jax.jit(functools.partial(head, dict(sizes), prec))
    return jax.jit(functools.partial(layer, dict(sizes), kind, prec))


def _short(prefix: str, tree: Dict) -> Dict:
    return {k[len(prefix):]: v for k, v in tree.items()
            if k.startswith(prefix)}


def hidden_states(arch: Dict, prec: str, get: Callable, row,
                  prefix: str = "", keep: bool = False):
    """The last block's output (before the final norm) at every position of
    ``row`` ``[S]``, a layer's weights asked for as it is reached; with
    ``keep``, also each ``mamba`` layer's ``(state, raw xBC)`` by index."""
    top = get(prefix + "top")
    x = top[prefix + "embed"][row].astype(jnp.float32) \
        * arch["embedding_multiplier"]
    sizes = tuple(sorted(arch.items()))
    states = {}
    for i, kind in enumerate(arch["layer_types"]):
        L = f"{prefix}L{i}"
        x, state = _jitted("layer", sizes, prec, kind)(
            x, _short(L + ".", get(L)))
        if keep and state is not None:
            states[i] = state
    return x, states


def full_logits(arch: Dict, prec: str, get: Callable, row):
    """Logits at every position of ``row`` ``[S]``, one causal pass; the
    tests' full forward."""
    x, _ = hidden_states(arch, prec, get, jnp.asarray(row, jnp.int32))
    top = get("top")
    return _jitted("head", tuple(sorted(arch.items())), prec)(
        x, top["lnf.scale"], top["embed"])


def states_after(arch: Dict, get: Callable, row):
    """Each ``mamba`` layer's ``(state after the tokens of row, xBC's raw
    inputs at each of them)`` (``row`` unpadded): what a server's state and
    convolution window have to hold."""
    _, states = hidden_states(arch, "f32", get, jnp.asarray(row, jnp.int32),
                              keep=True)
    return states


def served_logits(arch: Dict, prec: str, get: Callable, tokens, first: int,
                  count: int, pad_to: int = 512, count_pad: int = 64):
    """Logits that predict ``tokens[first : first+count]`` from one causal
    pass over the whole row, as the other references' of the same name: the
    row padded at the end to a multiple of ``pad_to`` (and of ``Q_BLOCK``),
    the head over a multiple of ``count_pad`` positions."""
    n = len(tokens)
    S = -(-n // pad_to) * pad_to
    if S > Q_BLOCK:
        S = -(-S // Q_BLOCK) * Q_BLOCK
    row = jnp.zeros((S,), jnp.int32).at[:n].set(jnp.asarray(tokens))
    x, _ = hidden_states(arch, prec, get, row)
    count_pad = -(-count // count_pad) * count_pad
    idx = jnp.clip(first - 1 + jnp.arange(count_pad), 0, S - 1)
    top = get("top")
    out = _jitted("head", tuple(sorted(arch.items())), prec)(
        x[idx], top["lnf.scale"], top["embed"])
    return out[:count]
