"""Mixture-of-Experts MLP — makes the mesh's ``expert`` axis real.

The reference has no MoE (no model code at all, SURVEY §5.7); this is the
beyond-parity expert-parallel path, built the TPU way (GShard/Switch
recipe):

- routing is **static-shaped**: top-k gates with a fixed per-expert
  capacity ``C = ceil(k * S * capacity_factor / E)``; overflow tokens are
  dropped (their combine weight is zero) — no dynamic shapes under jit;
- two dispatch implementations behind one module:

  * ``'sort'`` (default) — argsort tokens by expert, rank-within-expert
    seat assignment, one scatter into the ``[E, C, D]`` expert buffers and
    one gather back, weighted by the gates.  Memory/FLOPs are
    O(B·S·K·D) + the expert buffers — scales to production expert counts
    (VERDICT r2 weak #6: the one-hot path is O(B·S·E·C)).
  * ``'onehot'`` — the GShard einsum formulation against one-hot
    ``[B,S,E,C]`` dispatch/combine tensors; kept as the correctness
    oracle (the seat assignment is bit-identical: both process seats in
    slot-major order).

- expert weights are 3-D ``[E, D, F]`` with logical axes
  ``('expert', 'embed', 'mlp')``: expert-parallel over the ``expert`` mesh
  axis and tensor-parallel over ``tensor`` simultaneously; the
  batch↔expert resharding around the expert matmuls becomes GSPMD
  all-to-alls.

Load balancing: the standard Switch aux loss ``E * Σ_e f_e · p_e`` is
returned by the layer; :class:`~rocket_tpu.models.transformer.Block` threads
it out and ``TransformerLM`` publishes the per-batch total as
``batch['moe_aux']`` — add ``rt.Loss(moe_aux_loss(), weight=0.01)`` to
train against it (blackboard contract, reference ``module.py:139``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from rocket_tpu.models.layers import _init


def _seats_slot_major(top_idx: jax.Array, E: int, C: int):
    """Seat assignment for one row's ``[S, K]`` expert choices.

    Entries are ordered slot-major (all slot-0 choices in token order, then
    slot 1, …), matching the GShard cumsum semantics: a token's slot-j
    choice sees every seat taken by slots < j.  Returns, per flat entry
    (``[K*S]`` slot-major): the linear index into the ``E*C`` seat buffer
    (``E*C`` = dropped/out-of-bounds) and the fits mask.
    """
    S, K = top_idx.shape
    flat_e = top_idx.T.reshape(-1)  # [K*S] slot-major
    order = jnp.argsort(flat_e, stable=True)  # group by expert
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts  # exclusive cumsum [E]
    ranks = jnp.arange(K * S) - starts[sorted_e]  # seat within expert
    inv = jnp.argsort(order)
    seat = ranks[inv]  # back to slot-major entry order
    fits = seat < C
    lin = jnp.where(fits, flat_e * C + seat, E * C)
    return lin, fits


class MoEMLP(nn.Module):
    """Top-k routed expert MLP (GELU experts).

    Attributes
    ----------
    n_experts: number of experts ``E``.
    mlp_dim: hidden width ``F`` of each expert.
    top_k: experts per token (1 = Switch, 2 = GShard default).
    capacity_factor: slack over the perfectly-balanced per-expert load.
    use_bias: bias on the expert projections.
    dispatch: ``'sort'`` (scalable scatter/gather) or ``'onehot'``
        (einsum oracle) — identical outputs, different memory scaling.
    """

    n_experts: int
    mlp_dim: int
    top_k: int = 2
    capacity_factor: float = 1.25
    use_bias: bool = False
    dispatch: str = "sort"

    @nn.compact
    def __call__(self, x, train: bool = False):
        B, S, D = x.shape
        E, F, K = self.n_experts, self.mlp_dim, self.top_k
        if K > E:
            raise ValueError(f"top_k {K} > n_experts {E}")
        if self.dispatch not in ("sort", "onehot"):
            raise ValueError(f"unknown dispatch {self.dispatch!r}")
        capacity = max(4, math.ceil(K * S * self.capacity_factor / E))

        # -- routing (f32 for a stable softmax regardless of compute dtype)
        router = self.param(
            "router", _init(nn.initializers.lecun_normal(), "embed", "expert"),
            (D, E),
        )
        logits = jnp.einsum("bsd,de->bse", x, router.astype(x.dtype))
        gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [B,S,E]

        top_vals, top_idx = jax.lax.top_k(gates, K)  # [B,S,K]
        top_vals = top_vals / jnp.maximum(
            top_vals.sum(-1, keepdims=True), 1e-9
        )

        w_up = self.param(
            "w_up", _init(nn.initializers.lecun_normal(), "expert", "embed", "mlp"),
            (E, D, F),
        )
        w_down = self.param(
            "w_down", _init(nn.initializers.lecun_normal(), "expert", "mlp", "embed"),
            (E, F, D),
        )
        b_up = None
        if self.use_bias:
            b_up = self.param(
                "b_up", _init(nn.initializers.zeros_init(), "expert", "mlp"),
                (E, F),
            )

        if self.dispatch == "sort":
            y = self._sort_path(x, top_idx, top_vals, w_up, w_down, b_up,
                                capacity)
        else:
            y = self._onehot_path(x, top_idx, top_vals, w_up, w_down, b_up,
                                  capacity)

        # -- Switch load-balancing aux: E * Σ_e (fraction routed to e as
        # slot-0 choice) * (mean gate prob of e); minimized at uniform.
        f_e = jnp.mean(
            jax.nn.one_hot(top_idx[..., 0], E, dtype=jnp.float32), axis=(0, 1)
        )
        p_e = jnp.mean(gates, axis=(0, 1))
        aux = E * jnp.sum(f_e * p_e)
        return y, aux

    def _experts(self, expert_in, w_up, w_down, b_up):
        """GELU expert stack on ``[E, B, C, D]`` buffers — all MXU einsums;
        GSPMD turns the batch↔expert resharding into all-to-alls."""
        h = jnp.einsum("ebcd,edf->ebcf", expert_in, w_up.astype(expert_in.dtype))
        if b_up is not None:
            h = h + b_up.astype(expert_in.dtype)[:, None, None, :]
        h = nn.gelu(h)
        return jnp.einsum("ebcf,efd->ebcd", h, w_down.astype(expert_in.dtype))

    def _sort_path(self, x, top_idx, top_vals, w_up, w_down, b_up, C):
        B, S, D = x.shape
        E, K = self.n_experts, self.top_k

        lin, fits = jax.vmap(
            lambda ti: _seats_slot_major(ti, E, C)
        )(top_idx)  # [B, K*S] each
        gate_flat = top_vals.swapaxes(1, 2).reshape(B, K * S)  # slot-major
        gate_flat = gate_flat * fits.astype(gate_flat.dtype)

        # dispatch: one scatter per row into the E*C seat buffer; dropped
        # entries target index E*C which is out of bounds -> mode='drop'.
        x_rep = jnp.tile(x, (1, K, 1))  # [B, K*S, D] slot-major token copies

        def scatter_row(xr, lr):
            return jnp.zeros((E * C, D), x.dtype).at[lr].set(xr, mode="drop")

        expert_in = jax.vmap(scatter_row)(x_rep, lin)  # [B, E*C, D]
        expert_in = expert_in.reshape(B, E, C, D).transpose(1, 0, 2, 3)

        expert_out = self._experts(expert_in, w_up, w_down, b_up)  # [E,B,C,D]

        out_rows = expert_out.transpose(1, 0, 2, 3).reshape(B, E * C, D)

        def gather_row(orow, lr):
            return jnp.take(orow, lr, axis=0, mode="fill", fill_value=0)

        picked = jax.vmap(gather_row)(out_rows, lin)  # [B, K*S, D]
        y = picked * gate_flat.astype(x.dtype)[..., None]
        return y.reshape(B, K, S, D).sum(axis=1)

    def _onehot_path(self, x, top_idx, top_vals, w_up, w_down, b_up, C):
        B, S, D = x.shape
        E, K = self.n_experts, self.top_k
        # static-capacity dispatch: process the K slots in order; slot j
        # sees the seats already taken by slots < j (GShard cumsum trick).
        combine = jnp.zeros((B, S, E, C), dtype=jnp.float32)
        taken = jnp.zeros((B, 1, E), dtype=jnp.int32)  # seats used per expert
        for j in range(K):
            mask_j = jax.nn.one_hot(top_idx[..., j], E, dtype=jnp.int32)
            pos = jnp.cumsum(mask_j, axis=1) - 1 + taken  # seat index [B,S,E]
            fits = (pos < C) & (mask_j > 0)
            seat = jax.nn.one_hot(
                jnp.where(fits, pos, 0).sum(-1), C, dtype=jnp.float32
            )  # [B,S,C] — each token occupies one seat of its chosen expert
            combine = combine + (
                top_vals[..., j, None, None]
                * fits.astype(jnp.float32)[..., None]
                * seat[:, :, None, :]
            )
            taken = taken + mask_j.sum(axis=1, keepdims=True)

        dispatch = (combine > 0).astype(x.dtype)  # [B,S,E,C]
        expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch, x)
        expert_out = self._experts(expert_in, w_up, w_down, b_up)
        return jnp.einsum("bsec,ebcd->bsd", combine.astype(x.dtype), expert_out)


# Tokens at or below which RoutedExperts runs every held expert over every
# token instead of grouping the routed slots.  A v5e multiplies 240 times
# for each byte pair it streams (197 TFLOP/s over 819 GB/s), so below that
# many tokens the held experts' weights, read once either way, cost more
# than the products wasted on tokens an expert was not chosen for; and the
# time no longer depends on where the router sent them.
DENSE_BELOW = 128


@dataclasses.dataclass(frozen=True)
class ExpertsConfig:
    """A layer of gated experts as DeepSeek-V3-like models publish it
    (:class:`RoutedExperts`), and the share of it held here.

    ``n_routed`` is the router's width: every expert of the layer, on
    whatever chip it lives.  ``n_held`` of them, from ``held_start`` on,
    are this program's (``None``: all).  ``scale`` is the published
    ``routed_scaling_factor``; ``n_shared`` shared experts of the same
    width see every token (0: none).  ``router`` is how a token's scores
    are made of the router's outputs: ``"sigmoid"`` of each (DeepSeek-V3),
    or a ``"softmax"`` over all ``n_routed`` of them (Mixtral, Qwen-MoE)."""

    n_routed: int
    top_k: int
    expert_dim: int
    n_shared: int = 1
    scale: float = 1.0
    norm_topk: bool = True
    held_start: int = 0
    n_held: Optional[int] = None
    router: str = "sigmoid"

    def __post_init__(self) -> None:
        held = self.held
        if self.router not in ("sigmoid", "softmax"):
            raise ValueError(
                f"router {self.router!r} is neither 'sigmoid' nor 'softmax'")
        if self.n_shared < 0:
            raise ValueError(f"n_shared {self.n_shared} is negative")
        if not 0 < self.top_k <= self.n_routed:
            raise ValueError(
                f"top_k {self.top_k} must lie in 1..n_routed {self.n_routed}")
        if held < 1 or self.held_start < 0 \
                or self.held_start + held > self.n_routed:
            raise ValueError(
                f"held experts {self.held_start}..{self.held_start + held} "
                f"are not among the {self.n_routed} routed ones")

    @property
    def held(self) -> int:
        return self.n_routed if self.n_held is None else self.n_held


class RoutedExperts(nn.Module):
    """SwiGLU experts behind a sigmoid or softmax router, of which this
    program holds a share; nothing is dropped.

    The router scores every token against all ``n_routed`` experts in
    float32 (``config.router``: the ``sigmoid`` of each output, or the
    ``softmax`` over all of them; no groups, no bias), keeps the ``top_k``
    and weighs them ``s / sum(s) * scale``.  Of a token's slots those that fell
    on a held expert are computed here, ``sum_e w_e * Expert_e(x)``; what
    the experts held elsewhere would add is left out (their chips add it
    in a deployment; on one chip the layer runs without its exchange).
    There is no capacity.  A prefill's ``tokens * top_k`` slots are sorted
    by held expert (the rest last) and one grouped product a matrix
    (``jax.lax.ragged_dot``) runs over them, so every slot of a held
    expert is computed even when all tokens choose the same one.  A decode
    round's few tokens (``DENSE_BELOW``) go through every held expert and
    are weighed nought where the expert was not chosen: the same sums, the
    weights read once, no sort and no gather.

    The chosen experts are sown as ``routing/top_idx`` (``[B, S, top_k]``,
    ids among all ``n_routed``) for the serving round's counters; the
    shared experts are the caller's (:class:`Block` adds them)."""

    config: ExpertsConfig

    @nn.compact
    def __call__(self, x, router_input=None):
        """``router_input`` is what the router scores where it is not ``x``
        itself: the same activations before they were cast for the experts'
        matrix products (a float32 residual stream's norm), so that the
        cast decides no near tie."""
        cfg = self.config
        B, S, D = x.shape
        N, K, E, F = B * S, cfg.top_k, cfg.held, cfg.expert_dim
        flat = x.reshape(N, D)
        scored = flat if router_input is None else router_input.reshape(N, D)
        router = self.param(
            "router", _init(nn.initializers.lecun_normal(), "embed", None),
            (D, cfg.n_routed))
        # float32 end to end: a top-k over scores that a bf16 product
        # rounded picks other experts for the near ties
        logits = jnp.einsum(
            "nd,de->ne", scored.astype(jnp.float32),
            router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits) if cfg.router == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        top_s, top_i = jax.lax.top_k(scores, K)                 # [N, K]
        if cfg.norm_topk:
            top_s = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
        top_s = top_s * cfg.scale
        self.sow("routing", "top_idx", top_i.reshape(B, S, K))

        def matrix(name, d_in, d_out, axes):
            return self.param(
                name, _init(nn.initializers.lecun_normal(), "expert", *axes),
                (E, d_in, d_out)).astype(x.dtype)

        w_gate = matrix("w_gate", D, F, ("embed", "mlp"))
        w_up = matrix("w_up", D, F, ("embed", "mlp"))
        w_down = matrix("w_down", F, D, ("mlp", "embed"))

        local = top_i - cfg.held_start
        held = (local >= 0) & (local < E)
        if N <= DENSE_BELOW:
            weight = jnp.sum(                              # [N, E]
                jnp.where(held, top_s, 0.0)[..., None]
                * jax.nn.one_hot(local, E, dtype=jnp.float32), axis=1)
            h = nn.silu(jnp.einsum("nd,edf->enf", flat, w_gate)) \
                * jnp.einsum("nd,edf->enf", flat, w_up)
            y = jnp.einsum("enf,efd->end", h, w_down)
            # weighed on the vector unit: a float32 matrix product would
            # round the weights to bfloat16 on its way into the MXU
            out = jnp.sum(weight.T[..., None] * y.astype(jnp.float32), axis=0)
            return out.astype(x.dtype).reshape(B, S, D)
        group = jnp.where(held, local, E).reshape(N * K)   # the rest: last
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=E + 1)[:E].astype(jnp.int32)
        rows = flat[order // K]                            # [N*K, D]
        h = nn.silu(jax.lax.ragged_dot(rows, w_gate, sizes)) \
            * jax.lax.ragged_dot(rows, w_up, sizes)
        y = jax.lax.ragged_dot(h, w_down, sizes)
        # back in slot order; a slot of no held expert lies past the groups,
        # and whatever the grouped product left there is not read
        y = y[jnp.argsort(order)].reshape(N, K, D)
        y = jnp.where(held[..., None], y, 0).astype(jnp.float32)
        out = jnp.sum(y * top_s[..., None], axis=1)
        return out.astype(x.dtype).reshape(B, S, D)


def moe_aux_loss(key: str = "moe_aux"):
    """Objective reading the LM's published load-balancing aux
    (``rt.Loss(moe_aux_loss(), name='moe_aux', weight=0.01)``)."""

    def fn(batch):
        return batch[key]

    return fn
