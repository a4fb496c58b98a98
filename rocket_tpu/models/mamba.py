"""A state-space layer (Mamba-2's mixer, one group) for the transformer's
stack: :class:`MambaConfig` and :class:`MambaMixer`.

``[z | xBC | Δ] = W_in h``; ``xBC ← silu(conv(xBC) + b)`` (causal,
depthwise, ``d_conv`` taps), split into ``x`` (``n_heads`` of
``head_dim``), ``B`` and ``C`` (``d_state`` each, shared by every head);
``Δ = softplus(Δ + dt_bias)``, ``A = -exp(A_log)``; the selective scan of
:mod:`rocket_tpu.ops.ssm` gives ``y``, then ``y + D x``; ``out = W_out
RMSNorm(y ⊙ silu(z))`` over all ``n_heads * head_dim`` numbers.

**The cache** (the ``"cache"`` collection, a row's state where attention
keeps keys): ``ssm_state`` ``[rows, n_heads, head_dim, d_state]`` float32,
the state after the first ``state_pos`` tokens of the row; ``conv_state``
``[rows, d_conv - 1 + pending, conv width]``, the raw ``xBC`` of the
``d_conv - 1`` tokens before ``state_pos`` and then of tokens held
*pending*; ``dt_state`` ``[rows, pending, n_heads]``, the pending tokens'
raw ``Δ``; ``state_pos`` ``[rows]``.

**Pending tokens.**  A speculative round feeds a chunk of which only a
prefix will be accepted, and a recurrent state cannot be masked by
position the way a stale key is.  So a decode pass at positions ``p0 ..
p0 + S - 1`` first applies the ``p0 - state_pos`` pending inputs (the
tokens before ``p0`` are confirmed by the caller's frontier), then runs
its ``S`` tokens and *commits* the first ``commit`` of them: the state
after them is written, the inputs of the rest are held pending.  With
``commit`` 0 the state stays as it is and the new inputs are held after
the ones already pending (a draft chain's later steps); ``commit`` may be
traced, so one scan serves a chain whose first step commits.  No state is
ever kept per position: a pending token costs its raw inputs, and a row
holds at most ``MambaConfig.pending`` of them (a round's unconfirmed
drafts: the server sizes it to its ``n_draft``).  Rows the batch marks
``idle`` change nothing.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from rocket_tpu.models.layers import PDense, _init
from rocket_tpu.ops import ssm


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    """The sizes of a Mamba-2 mixer (the ``mamba_*`` keys of a hybrid
    model's ``config.json``).  ``chunk`` is the chunked scan's tile: it
    changes no result.  ``pending`` is how many unconfirmed tokens' inputs
    a decode cache holds a row (a speculative round's ``n_draft``; at most
    ``ops.ssm.MAX_CHUNK - 1``): it sizes the cache, not the model."""

    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    n_heads: int = 64
    head_dim: int = 64
    n_groups: int = 1
    chunk: int = 256
    conv_bias: bool = True
    proj_bias: bool = False
    pending: int = ssm.MAX_CHUNK - 1

    def __post_init__(self) -> None:
        if min(self.d_state, self.d_conv, self.expand, self.n_heads,
               self.head_dim, self.chunk) < 1:
            raise ValueError(f"MambaConfig needs positive sizes, got {self}")
        if not 0 <= self.pending < ssm.MAX_CHUNK:
            raise ValueError(
                f"a state-space layer holds 0 to {ssm.MAX_CHUNK - 1} tokens "
                f"pending (a round's chunk is at most {ssm.MAX_CHUNK}), got "
                f"pending={self.pending}")
        if self.n_groups != 1:
            raise ValueError(
                f"a state-space layer with n_groups={self.n_groups} (B and "
                f"C a group of heads) cannot run yet: one group only")

    @property
    def inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_width(self) -> int:
        return self.inner + 2 * self.n_groups * self.d_state


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A = -exp(A_log)`` uniform in [1, 16] (Mamba-2's initialisation)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """``softplus(dt_bias)`` log-uniform in [0.001, 0.1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _conv(ctx, kernel, bias, L: int):
    """Causal depthwise convolution: ``out[t] = Σ_k kernel[k] ctx[t + k]``
    (+ ``bias``) for ``t < L``, ``ctx`` holding ``d_conv - 1`` inputs of
    context before the ``L`` it is asked for; then SiLU."""
    K = kernel.shape[0]
    out = sum(ctx[:, k:k + L] * kernel[k] for k in range(K))
    if bias is not None:
        out = out + bias
    return jax.nn.silu(out)


def _rows(a, idx):
    """``a[r, idx[r, i]]`` for every row ``r``: ``[R, M, ...]`` by ``[R, L]``."""
    return jnp.take_along_axis(
        a, idx.reshape(idx.shape + (1,) * (a.ndim - 2)), axis=1)


class MambaMixer(nn.Module):
    """The mixer of a ``mamba`` layer (module docstring).  ``commit`` (a
    decode pass's tokens to commit, an int or a traced scalar; ``None``:
    all) is what a speculative round sets; a pass longer than
    ``ops.ssm.MAX_CHUNK`` commits them all."""

    config: Any

    @nn.compact
    def __call__(self, h, positions, train: bool = False,
                 decode: bool = False, idle=None, commit=None):
        cfg, m = self.config, self.config.mamba
        R, S, _ = h.shape
        H, P, N, K = m.n_heads, m.head_dim, m.d_state, m.d_conv
        E, W = m.inner, m.conv_width
        f32 = jnp.float32
        zxbcdt = PDense(E + W + H, logical_axes=("embed", "mlp"),
                        use_bias=m.proj_bias, name="in_proj")(h)
        z, xbc, dt = jnp.split(zxbcdt, [E, E + W], axis=-1)
        kernel = self.param(
            "conv_kernel", _init(nn.initializers.normal(K ** -0.5), None, "mlp"),
            (K, W)).astype(xbc.dtype)
        bias = self.param(
            "conv_bias", _init(nn.initializers.zeros_init(), "mlp"), (W,)
        ).astype(xbc.dtype) if m.conv_bias else None
        A = -jnp.exp(self.param("A_log", _init(_a_log_init, None), (H,))
                     .astype(f32))
        D = self.param("D", _init(nn.initializers.ones_init(), None), (H,))
        dt_bias = self.param("dt_bias", _init(_dt_bias_init, None), (H,))
        scale = self.param("norm_scale",
                           _init(nn.initializers.ones_init(), "mlp"), (E,))

        def delta(raw):
            return jax.nn.softplus(raw.astype(f32) + dt_bias.astype(f32))

        def split(conv_out):
            x, Bm, Cm = jnp.split(conv_out, [E, E + N], axis=-1)
            return x.reshape(x.shape[:2] + (H, P)), Bm, Cm

        def out(y, x, gate):
            y = y + D.astype(f32)[:, None] * x.astype(f32)
            y = y.reshape(R, -1, E) * jax.nn.silu(gate.astype(f32))
            y = y * jax.lax.rsqrt(
                jnp.mean(jnp.square(y), axis=-1, keepdims=True) + cfg.norm_eps)
            y = (y * scale.astype(f32)).astype(h.dtype)
            return PDense(cfg.hidden, logical_axes=("mlp", "embed"),
                          use_bias=m.proj_bias, name="out_proj")(y)

        if decode:
            M = m.pending
            filled = self.has_variable("cache", "ssm_state")
            state = self.variable("cache", "ssm_state", jnp.zeros,
                                  (R, H, P, N), f32)
            window = self.variable("cache", "conv_state", jnp.zeros,
                                   (R, K - 1 + M, W), xbc.dtype)
            pending_dt = self.variable("cache", "dt_state", jnp.zeros,
                                       (R, M, H), dt.dtype)
            state_pos = self.variable("cache", "state_pos", jnp.zeros, (R,),
                                      jnp.int32)
        if not decode or not filled:
            # a whole sequence from an empty state (training, init)
            ctx = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
            x, Bm, Cm = split(_conv(ctx, kernel, bias, S))
            y, _ = ssm.chunked_scan(x, delta(dt), A, Bm, Cm, chunk=m.chunk)
            return out(y, x, z)

        c = S if commit is None else commit
        if isinstance(c, int) and (not 0 <= c <= S
                                   or (S > ssm.MAX_CHUNK and c != S)):
            raise ValueError(
                f"a decode pass of {S} tokens cannot commit {commit}: a round "
                f"commits 0 to {S}, a longer pass (a prompt) all of them")
        if S <= ssm.MAX_CHUNK and commit is not None and S - 1 > M:
            raise ValueError(
                f"a decode pass of {S} tokens holds up to {S - 1} pending; "
                f"the cache holds {M} (MambaConfig.pending)")
        idle = jnp.zeros((R,), bool) if idle is None else idle
        p0 = positions[:, 0].astype(jnp.int32)
        # pending inputs confirmed by the frontier the pass starts at
        n = jnp.clip(p0 - state_pos.value, 0, M)
        L = M + S
        t = jnp.arange(L)[None, :]
        # the pass's steps: pending 0 .. n-1, the S new tokens, then nothing
        src = jnp.where(t < n[:, None], t,
                        jnp.clip(M + t - n[:, None], 0, L - 1))
        raw = _rows(jnp.concatenate([window.value[:, K - 1:], xbc], axis=1),
                    src)
        raw_dt = _rows(jnp.concatenate([pending_dt.value, dt], axis=1), src)
        live = (t < (n + S)[:, None]) & ~idle[:, None]
        ctx = jnp.concatenate([window.value[:, :K - 1], raw], axis=1)
        x, Bm, Cm = split(_conv(ctx, kernel, bias, L))
        dt_v = jnp.where(live[..., None], delta(raw_dt), 0.0)
        commits = (c > 0) & ~idle
        commit_at = jnp.where(commits, n + c - 1, -1)
        if S > ssm.MAX_CHUNK:
            y, kept = ssm.chunked_scan(x, dt_v, A, Bm, Cm, chunk=m.chunk,
                                       state=state.value)
        else:
            y, kept = ssm.round_update(state.value, x, dt_v, A, Bm, Cm,
                                       commit_at, S=S)
        new = n[:, None] + jnp.arange(S)[None, :]          # the new tokens
        result = out(_rows(y, new), _rows(x, new), z)

        # the window now starts d_conv - 1 inputs before the committed
        # state's position; what follows it are the tokens held pending
        # (with nothing committed: the old pending ones, then the new)
        state.value = kept
        from_ = jnp.where(commits, n + c, 0)
        ctx_pad = jnp.pad(ctx, ((0, 0), (0, M), (0, 0)))
        dt_pad = jnp.pad(raw_dt, ((0, 0), (0, M), (0, 0)))
        take = from_[:, None] + jnp.arange(K - 1 + M)[None, :]
        still = idle[:, None, None]
        window.value = jnp.where(still, window.value, _rows(ctx_pad, take))
        pending_dt.value = jnp.where(
            still, pending_dt.value, _rows(dt_pad, take[:, :M]))
        state_pos.value = jnp.where(commits, p0 + c, state_pos.value)
        return result
