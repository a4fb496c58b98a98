"""Plain reference for decoder-only transformers (GPT-2 and Mistral kinds).

Straight ``jax.numpy`` in float32 with ``precision=HIGHEST``: no kernels, no
cache, no batching tricks, nothing imported from ``rocket_tpu`` and nothing
taken from it.  It follows the published layer equations:

- GPT-2: learned positions, LayerNorm (with bias), fused-tanh GELU MLP,
  biases on every projection, output head tied to the embedding.
- Mistral: RoPE (split-halves convention, as Hugging Face's ``rotate_half``),
  RMSNorm, SwiGLU, grouped-query attention, sliding window, untied head.

Departures, both stated by the configuration files: the vocabulary may be
padded (``vocab_padded``; the loss and the arg-max run over the padded
width, ids are drawn below the published one), and depth may be reduced.

``prec`` chooses how matrix products round, everything else stays float32:
``"f32"`` is the reference; ``"bf16"`` and ``"fp8"`` are the lower
precisions the controls use (fp8 = e4m3 with one scale per tensor).

Weights are asked for by name, one layer at a time, through ``get(name)``
(see :mod:`benchmark.weights`), so a 2-billion-parameter model never has to
sit in float32 on the chip at once.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _fp8_round(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16), scale


def matmul(prec: str, spec: str, a, b):
    """``einsum(spec, a, b)`` with operands rounded as ``prec`` says and the
    sum kept in float32."""
    if prec == "f32":
        return jnp.einsum(spec, a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    if prec == "bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if prec == "fp8":
        (aq, sa), (bq, sb) = _fp8_round(a), _fp8_round(b)
        return jnp.einsum(spec, aq, bq,
                          preferred_element_type=jnp.float32) * (sa * sb)
    raise ValueError(f"unknown precision {prec!r}")


def _norm(arch, x, w, name):
    x = x.astype(jnp.float32)
    if arch["norm"] == "layernorm":
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + arch["eps"]) * w[f"{name}.scale"]
        return y + w[f"{name}.bias"] if f"{name}.bias" in w else y
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + arch["eps"]) * w[f"{name}.scale"]


def _rope(x, positions, theta):
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * freqs       # [B,S,d/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _dense(prec, x, w, name):
    y = matmul(prec, "bsd,df->bsf", x, w[f"{name}.w"])
    return y + w[f"{name}.b"] if f"{name}.b" in w else y


def layer(arch: Dict, prec: str, x, w: Dict, positions, q_block: int = 512):
    """One block: ``x + attn(norm(x))``, then ``+ mlp(norm(.))``.  ``w`` has
    the layer's leaves under their short names.  Attention runs in blocks
    of ``q_block`` query rows so the score matrix stays small."""
    B, S, _ = x.shape
    nh, nkv, hd = arch["heads"], arch["kv_heads"], arch["head_dim"]
    h = _norm(arch, x, w, "ln1")
    q = _dense(prec, h, w, "q").reshape(B, S, nh, hd)
    k = _dense(prec, h, w, "k").reshape(B, S, nkv, hd)
    v = _dense(prec, h, w, "v").reshape(B, S, nkv, hd)
    if arch["positions"] == "rope":
        q = _rope(q, positions, arch["rope_theta"])
        k = _rope(k, positions, arch["rope_theta"])
    group = nh // nkv
    q = q.reshape(B, S, nkv, group, hd)
    k_pos = positions[:, None, :]                                  # [B,1,S]
    outs = []
    for lo in range(0, S, q_block):
        hi = min(S, lo + q_block)
        s = matmul(prec, "bqkgd,bskd->bkgqs", q[:, lo:hi], k) * hd ** -0.5
        q_pos = positions[:, lo:hi, None]                          # [B,q,1]
        mask = k_pos <= q_pos
        if arch["window"]:
            mask &= (q_pos - k_pos) < arch["window"]
        s = jnp.where(mask[:, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(matmul(prec, "bkgqs,bskd->bqkgd", p, v))
    att = jnp.concatenate(outs, axis=1).reshape(B, S, nh * hd)
    x = x + _dense(prec, att, w, "o")
    h = _norm(arch, x, w, "ln2")
    if arch["mlp"] == "swiglu":
        h = jax.nn.silu(_dense(prec, h, w, "gate")) * _dense(prec, h, w, "up")
    else:
        h = jax.nn.gelu(_dense(prec, h, w, "up"), approximate=True)
    return x + _dense(prec, h, w, "down")


def embed(arch: Dict, tokens, w: Dict, positions):
    x = w["embed"][tokens]
    if arch["positions"] == "learned":
        x = x + w["pos"][positions]
    return x.astype(jnp.float32)


def head(arch: Dict, prec: str, x, w: Dict):
    x = _norm(arch, x, w, "lnf")
    if arch["tie"]:
        return matmul(prec, "bsd,vd->bsv", x, w["embed"])
    return matmul(prec, "bsd,dv->bsv", x, w["head"])


# -- training: loss, gradients and AdamW, three steps ------------------------


def _short(prefix: str, tree: Dict) -> Dict:
    return {k[len(prefix):]: v for k, v in tree.items() if k.startswith(prefix)}


def loss_sum(arch: Dict, prec: str, params: Dict, tokens):
    """Sum over rows and positions of the next-token cross-entropy."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = embed(arch, tokens, params, positions)
    # One layer's program, scanned over the layers' stacked weights: the
    # same equations in the same order as a Python loop, at a 24th of the
    # program (a fresh process traces, lowers and loads it in seconds).
    shorts = list(_short("L0.", params))
    stacked = {k: jnp.stack([params[f"L{i}.{k}"]
                             for i in range(arch["layers"])]) for k in shorts}
    block = jax.checkpoint(functools.partial(layer, arch, prec))
    x, _ = jax.lax.scan(lambda h, w: (block(h, w, positions), None),
                        x, stacked)
    logits = head(arch, prec, x, params)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.sum(picked)


def loss_and_grads(arch: Dict, prec: str, params: Dict, tokens,
                   row_block: int = 2, rows: Optional[Sequence[int]] = None):
    """Mean loss over the batch and its gradients, accumulated over blocks
    of ``row_block`` rows.  ``rows`` (a fault for the tests and the limit
    readings: half of the batch left out) takes the mean over those rows
    alone."""
    if rows is not None:
        tokens = tokens[jnp.asarray(list(rows))]
    B, S = tokens.shape
    grad_fn = _jitted("grad", arch, prec)
    total, grads = 0.0, None
    for lo in range(0, B, row_block):
        val, g = grad_fn(params, tokens[lo:lo + row_block])
        total = total + val
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    n = B * (S - 1)
    return total / n, jax.tree_util.tree_map(lambda g: g / n, grads)


_JITTED: Dict = {}


def _jitted(what: str, arch: Dict, prec: str):
    """One compiled function per (what, architecture, precision), kept for
    the life of the process so that a second seed re-traces nothing."""
    key = (what, tuple(sorted((k, str(v)) for k, v in arch.items())), prec)
    if key not in _JITTED:
        fn = {"grad": lambda: jax.value_and_grad(
                  functools.partial(loss_sum, arch, prec)),
              "embed": lambda: functools.partial(embed, arch),
              "layer": lambda: functools.partial(layer, arch, prec),
              "head": lambda: functools.partial(head, arch, prec)}[what]()
        _JITTED[key] = jax.jit(fn)
    return _JITTED[key]


def lr_at(opt: Dict, step: int) -> float:
    """Linear warm-up from ``lr_init`` to ``lr_peak`` over ``warmup_steps``,
    then a cosine to ``lr_end`` at ``decay_steps`` (step counts from 0)."""
    import math

    warm, total = opt["warmup_steps"], opt["decay_steps"]
    if step < warm:
        return opt["lr_init"] + (opt["lr_peak"] - opt["lr_init"]) * step / warm
    frac = min(1.0, (step - warm) / max(1, total - warm))
    cos = 0.5 * (1.0 + math.cos(math.pi * frac))
    return opt["lr_end"] + (opt["lr_peak"] - opt["lr_end"]) * cos


@jax.jit
def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in tree.values()))


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd"))
def _adamw(params, grads, mu, nu, lr, t, clip_scale, *, b1, b2, eps, wd):
    def one(p, g, m, n):
        g = g * clip_scale
        m = b1 * m + (1 - b1) * g
        n = b2 * n + (1 - b2) * g * g
        upd = (m / (1 - b1 ** t)) / (jnp.sqrt(n / (1 - b2 ** t)) + eps)
        return p - lr * (upd + wd * p), m, n

    out = {k: one(params[k], grads[k], mu[k], nu[k]) for k in params}
    return ({k: v[0] for k, v in out.items()}, {k: v[1] for k, v in out.items()},
            {k: v[2] for k, v in out.items()})


def train_steps(arch: Dict, opt: Dict, params: Dict, batches, prec="f32",
                rows=None, skip_update=False):
    """Follow the trainer for ``len(batches)`` steps: loss and gradients,
    clipping by the global norm, AdamW with decoupled weight decay on every
    leaf, the learning rate of :func:`lr_at`.

    Returns each step's loss, the per-leaf norm of the first gradient as
    the optimizer gets it (after clipping), and the per-leaf norm of the
    parameters' change over all the steps.  ``rows`` and ``skip_update``
    plant the faults the tests and the limit readings need."""
    zeros = lambda: {k: jnp.zeros_like(v) for k, v in params.items()}  # noqa: E731
    mu, nu, p = zeros(), zeros(), dict(params)
    losses, first_grad = [], None
    for t, tokens in enumerate(batches, start=1):
        loss, grads = loss_and_grads(arch, prec, p, jnp.asarray(tokens),
                                     rows=rows)
        losses.append(float(loss))
        gnorm = _global_norm(grads)
        clip = opt["clip_norm"] / jnp.maximum(gnorm, opt["clip_norm"])
        if first_grad is None:
            first_grad = {k: float(jnp.linalg.norm(g.ravel()) * clip)
                          for k, g in grads.items()}
        if not skip_update:
            p, mu, nu = _adamw(
                p, grads, mu, nu, jnp.float32(lr_at(opt, t - 1)),
                jnp.float32(t), clip, b1=opt["b1"], b2=opt["b2"],
                eps=opt["eps"], wd=opt["weight_decay"])
    change = {k: float(jnp.linalg.norm((p[k] - params[k]).ravel()))
              for k in params}
    return {"losses": losses, "first_grad": first_grad, "change": change}


# -- serving: one teacher-forced pass over prompt + served tokens ------------


def served_logits(arch: Dict, prec: str, get: Callable, tokens, first: int,
                  count: int, pad_to: int = 512, count_pad: int = 64):
    """Logits that predict ``tokens[first : first+count]`` (positions
    ``first-1 ..``), from one causal pass over the whole row.
    ``get(group)`` returns one group's leaves by name (``top``, ``L0`` ...),
    already rounded as the configuration serves them.  The row
    is padded at the end to a multiple of ``pad_to`` (causality keeps the
    padding out of every real position) and the head runs over a multiple
    of ``count_pad`` positions, so few programs compile: one, where the
    caller passes the longest row and the longest output."""
    n = len(tokens)
    S = -(-n // pad_to) * pad_to
    row = jnp.zeros((1, S), jnp.int32).at[0, :n].set(jnp.asarray(tokens))
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    top = get("top")
    x = _jitted("embed", arch, prec)(row, top, positions)
    step = _jitted("layer", arch, prec)
    for i in range(arch["layers"]):
        x = step(x, _short(f"L{i}.", get(f"L{i}")), positions)
    count_pad = -(-count // count_pad) * count_pad
    lo = first - 1
    idx = jnp.clip(lo + jnp.arange(count_pad), 0, S - 1)
    out = _jitted("head", arch, prec)(x[:, idx], top)
    return out[0, :count]
