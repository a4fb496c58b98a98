"""Plain reference of the toy architecture ``latent_mix``: ``jax.numpy`` in
float32 at ``precision=HIGHEST``, no cache, nothing of the program's.  The
layers and the optimizer's steps are written out here; of the dense
reference (``benchmark.reference.decoder``, which this file leaves as it
is) it takes two public helpers that know no layer: ``matmul``, the
rounding of the lower precisions, and ``lr_at``, the schedule.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from benchmark.reference import decoder as dense

matmul = dense.matmul


def _norm(arch, x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + arch["eps"]) * scale


def logits_of(arch: Dict, prec: str, w: Dict, tokens):
    """``[B, S, V]`` logits of one causal pass; ``w`` holds every leaf."""
    B, S = tokens.shape
    nh, hd = arch["heads"], arch["head_dim"]
    x = w["embed"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((S, S), bool))
    for i in range(arch["layers"]):
        L = f"L{i}"
        h = _norm(arch, x, w[f"{L}.ln1.scale"])
        q = matmul(prec, "bsh,hd->bsd", h, w[f"{L}.q.w"])
        latent = matmul(prec, "bsh,hc->bsc", h, w[f"{L}.latent.w"])
        k = matmul(prec, "bsc,cd->bsd", latent, w[f"{L}.k.w"])
        v = matmul(prec, "bsc,cd->bsd", latent, w[f"{L}.v.w"])
        q, k, v = (t.reshape(B, S, nh, hd) for t in (q, k, v))
        s = matmul(prec, "bqnd,bknd->bnqk", q, k) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        att = matmul(prec, "bnqk,bknd->bqnd", p, v).reshape(B, S, nh * hd)
        x = x + matmul(prec, "bsd,dh->bsh", att, w[f"{L}.o.w"])
        h = _norm(arch, x, w[f"{L}.ln2.scale"])
        if i == 0:
            up = matmul(prec, "bsh,hf->bsf", h, w[f"{L}.up.w"])
            x = x + matmul(prec, "bsf,fh->bsh",
                           jax.nn.gelu(up, approximate=True),
                           w[f"{L}.down.w"])
        else:
            gates = jax.nn.softmax(
                matmul(prec, "bsh,he->bse", h, w[f"{L}.router.w"]), axis=-1)
            # the experts lead: the CPU multiplies bfloat16 operands (the
            # controls) only where the batch axes come first
            up = matmul(prec, "bsh,ehf->ebsf", h, w[f"{L}.up.w"])
            each = matmul(prec, "ebsf,efh->ebsh",
                          jax.nn.gelu(up, approximate=True),
                          w[f"{L}.down.w"])
            x = x + jnp.einsum("bse,ebsh->bsh", gates, each,
                               precision=dense.HIGHEST)
    return matmul(prec, "bsh,hv->bsv", _norm(arch, x, w["lnf.scale"]),
                  w["head"])


def loss_sum(arch: Dict, prec: str, params: Dict, tokens):
    logp = jax.nn.log_softmax(logits_of(arch, prec, params, tokens)[:, :-1],
                              axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


@functools.lru_cache(maxsize=None)
def _jitted(what: str, sizes: tuple, prec: str):
    """One compiled function per (what, architecture, precision), kept for
    the life of the process so that a second seed re-traces nothing."""
    fn = {"grad": jax.value_and_grad(loss_sum, argnums=2),
          "logits": logits_of}[what]
    return jax.jit(functools.partial(fn, dict(sizes), prec))


def loss_and_grads(arch: Dict, prec: str, params: Dict, tokens,
                   rows: Optional[Sequence[int]] = None):
    """Mean loss over the batch and its gradients; ``rows`` (the fault
    "half of the batch left out") takes the mean over those rows alone."""
    if rows is not None:
        tokens = tokens[jnp.asarray(list(rows))]
    B, S = tokens.shape
    total, grads = _jitted("grad", tuple(sorted(arch.items())), prec)(
        params, tokens)
    n = B * (S - 1)
    return total / n, jax.tree_util.tree_map(lambda g: g / n, grads)


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd"))
def _adamw(params, grads, mu, nu, lr, t, clip, *, b1, b2, eps, wd):
    def one(p, g, m, n):
        g = g * clip
        m = b1 * m + (1 - b1) * g
        n = b2 * n + (1 - b2) * g * g
        upd = (m / (1 - b1 ** t)) / (jnp.sqrt(n / (1 - b2 ** t)) + eps)
        return p - lr * (upd + wd * p), m, n

    out = {k: one(params[k], grads[k], mu[k], nu[k]) for k in params}
    return tuple({k: v[i] for k, v in out.items()} for i in range(3))


def train_steps(arch: Dict, opt: Dict, params: Dict, batches, prec="f32",
                rows=None, skip_update=False):
    """Follow the trainer for ``len(batches)`` steps: clipping by the global
    norm, AdamW with decoupled weight decay on every leaf, the learning
    rate of ``lr_at``.  Returns each step's loss, the per-leaf norm of the
    first gradient as the optimizer gets it, and the per-leaf norm of the
    parameters' change.  ``rows`` and ``skip_update`` plant faults."""
    zeros = lambda: {k: jnp.zeros_like(v) for k, v in params.items()}  # noqa: E731
    mu, nu, p = zeros(), zeros(), dict(params)
    losses, first_grad = [], None
    for t, tokens in enumerate(batches, start=1):
        loss, grads = loss_and_grads(arch, prec, p, jnp.asarray(tokens),
                                     rows=rows)
        losses.append(float(loss))
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
        clip = opt["clip_norm"] / jnp.maximum(gnorm, opt["clip_norm"])
        if first_grad is None:
            first_grad = {k: float(jnp.linalg.norm(g.ravel()) * clip)
                          for k, g in grads.items()}
        if not skip_update:
            p, mu, nu = _adamw(
                p, grads, mu, nu, jnp.float32(dense.lr_at(opt, t - 1)),
                jnp.float32(t), clip, b1=opt["b1"], b2=opt["b2"],
                eps=opt["eps"], wd=opt["weight_decay"])
    change = {k: float(jnp.linalg.norm((p[k] - params[k]).ravel()))
              for k in params}
    return {"losses": losses, "first_grad": first_grad, "change": change}


def served_logits(arch: Dict, prec: str, get: Callable, tokens, first: int,
                  count: int, pad_to: int = 512, count_pad: int = 64):
    """Logits that predict ``tokens[first : first+count]``, from one causal
    pass over the row.  ``get(group)`` returns a group's leaves as they are
    served; the toy is small enough to hold them all."""
    groups = ["top"] + [f"L{i}" for i in range(arch["layers"])]
    w = {k: v for g in groups for k, v in get(g).items()}
    row = jnp.asarray(tokens)[None, :]
    logits = _jitted("logits", tuple(sorted(arch.items())), prec)(w, row)
    return logits[0, first - 1:first - 1 + count]
