"""State-space layers' mathematics — Mamba-2's selective scan with one group
of ``B`` and ``C`` shared by every head — and a decode round's state update
as a Pallas TPU kernel.

For each head ``h`` of ``P`` numbers over a state of ``N``:

    S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t ⊗ B_t,        y_t = S_t C_t

(``D x_t``, the gate and the norm are the caller's).  A step whose ``Δ`` is
0 changes nothing: padding and masked steps are written that way, so a
sequence may hold steps that do not count.  Three forms of the same sums:

- :func:`chunked_scan`, for a prompt or a training sequence: chunks of
  ``chunk`` steps, inside a chunk a masked ``C·Bᵀ`` with the decays (the
  "SSD" form), the states passed from chunk to chunk;
- :func:`step_scan`, one step at a time: the recurrence as written, for a
  decode round's few steps where the kernel is not taken;
- :func:`ssm_decode`, the same few steps in closed form, the part that
  touches the state a Pallas kernel (``name="ssm_decode_s<S>_r<rows>"``):
  the row's state is read once and written once, in place.

:func:`round_update` is what the layer calls for a round's pass: the kernel
where :func:`why_not` finds no reason against it, :func:`step_scan`
otherwise, the choice counted at trace time (``ssm/decode/kernel``,
``ssm/decode/fallback``).  States are float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST
# The longest pass a round makes (a verify chunk of n_draft + 1); a longer
# one is a prompt and takes the chunked form.
MAX_CHUNK = 8
# Bytes of state a kernel block: 32 heads of 64 x 128 float32.
BLOCK_BYTES = 1 << 20
VMEM_LIMIT = 64 * 1024 * 1024


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def chunked_scan(x, dt, A, B, C, *, chunk: int, state=None):
    """``(y, S_T)`` of the recurrence over a whole sequence, in chunks.

    ``x`` ``[b, T, H, P]``, ``dt`` ``[b, T, H]`` (``Δ``, after the softplus;
    0 on a step that does not count), ``A`` ``[H]``, ``B`` and ``C`` ``[b, T,
    N]``, ``state`` ``[b, H, P, N]`` (``None``: zeros).  ``y`` is ``[b, T, H,
    P]`` and every sum is float32.  ``T`` need not be whole chunks: the tail
    is padded with steps of ``Δ`` 0."""
    from rocket_tpu.observe.trace import counter

    f32 = jnp.float32
    b, T, H, P = x.shape
    N = B.shape[-1]
    pad = -T % chunk
    nc = (T + pad) // chunk
    counter("ssm/prefill", 1, T=T, chunk=chunk, chunks=nc)

    def cut(a):
        a = jnp.pad(a.astype(f32), [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        return a.reshape((b, nc, chunk) + a.shape[2:])

    x, dt, B, C = cut(x), cut(dt), cut(B), cut(C)
    if state is None:
        state = jnp.zeros((b, H, P, N), f32)
    l = jnp.cumsum(dt * A.astype(f32), axis=2)              # [b, c, Q, H]
    u = x * dt[..., None]                                   # [b, c, Q, H, P]
    q = jnp.arange(chunk)
    causal = (q[:, None] >= q[None, :])[None, None, :, :, None]
    decay = jnp.exp(jnp.where(
        causal, l[:, :, :, None, :] - l[:, :, None, :, :], -jnp.inf))
    G = jnp.einsum("bcqn,bcsn->bcqs", C, B, precision=HIGHEST)
    y = jnp.einsum("bcqs,bcqsh,bcshp->bcqhp", G, decay, u, precision=HIGHEST)
    # each chunk's own contribution to the state at its end, from zeros
    to_end = jnp.exp(l[:, :, -1:, :] - l)                   # [b, c, Q, H]
    own = jnp.einsum("bcsh,bcshp,bcsn->bchpn", to_end, u, B,
                     precision=HIGHEST)

    def carry(S, inp):
        own_c, decay_c = inp
        return decay_c[..., None, None] * S + own_c, S

    final, before = jax.lax.scan(
        carry, state.astype(f32),
        (own.swapaxes(0, 1), jnp.exp(l[:, :, -1, :]).swapaxes(0, 1)))
    before = before.swapaxes(0, 1)                          # [b, c, H, P, N]
    y = y + jnp.exp(l)[..., None] * jnp.einsum(
        "bcqn,bchpn->bcqhp", C, before, precision=HIGHEST)
    return y.reshape(b, nc * chunk, H, P)[:, :T], final


def step_scan(state, x, dt, A, B, C, commit_at):
    """The recurrence one step at a time over a pass's ``L`` steps.

    ``state`` ``[R, H, P, N]`` float32; ``x`` ``[R, L, H, P]``, ``dt`` ``[R,
    L, H]`` (0 on a step that does not count), ``A`` ``[H]``, ``B``, ``C``
    ``[R, L, N]``; ``commit_at`` ``[R]``: the step after which a row's state
    is kept (-1: the row keeps ``state``).  Returns ``(y [R, L, H, P],
    kept)``."""
    f32 = jnp.float32
    A = A.astype(f32)

    def step(carry, inp):
        S, kept = carry
        t, x_t, dt_t, B_t, C_t = inp
        S = jnp.exp(dt_t * A)[..., None, None] * S \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, None, None, :]
        y_t = jnp.einsum("rhpn,rn->rhp", S, C_t, precision=HIGHEST)
        kept = jnp.where((commit_at == t)[:, None, None, None], S, kept)
        return (S, kept), y_t

    L = x.shape[1]
    xs = (jnp.arange(L), x.astype(f32).swapaxes(0, 1),
          dt.astype(f32).swapaxes(0, 1), B.astype(f32).swapaxes(0, 1),
          C.astype(f32).swapaxes(0, 1))
    state = state.astype(f32)
    (_, kept), y = jax.lax.scan(step, (state, state), xs)
    return y.swapaxes(0, 1), kept


def heads_per_block(H: int, P: int, N: int) -> Optional[int]:
    """Heads of a kernel block: ``BLOCK_BYTES`` of float32 state in whole
    heads that divide ``H``, with ``heads * P`` a whole number of 128 lanes
    (the outputs' last axis); ``None`` where no count does."""
    best = None
    for hb in range(1, H + 1):
        if H % hb or hb * P * N * 4 > max(BLOCK_BYTES, P * N * 4):
            continue
        if (hb * P) % 128 == 0 or hb == H:
            best = hb
    return best


def _split(a):
    """``a`` (float32) as two bfloat16 terms whose sum is ``a`` to about
    2**-17 of it: a product with a bfloat16 operand, summed in float32, is
    then a float32 product in two passes of the MXU."""
    hi = a.astype(jnp.bfloat16)
    return hi, (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _kernel(s_ref, c_ref, b_ref, w_ref, g_ref, z_ref, kept_ref, *, heads: int,
            P: int):
    # s [heads * P, N] float32 (a head's P rows one after the other); c and
    # b [L', N] bfloat16; w [L', heads * P] float32; g [heads, 128]
    s = s_ref[0]
    nt = (((1,), (1,)), ((), ()))
    hi, lo = _split(s)
    c = c_ref[0]
    z_ref[0] = jax.lax.dot_general(c, hi, nt,
                                   preferred_element_type=jnp.float32) \
        + jax.lax.dot_general(c, lo, nt, preferred_element_type=jnp.float32)
    tn = (((0,), (0,)), ((), ()))
    w_hi, w_lo = _split(w_ref[0])
    b = b_ref[0]
    inc = jax.lax.dot_general(w_hi, b, tn, preferred_element_type=jnp.float32) \
        + jax.lax.dot_general(w_lo, b, tn, preferred_element_type=jnp.float32)
    for h in range(heads):
        rows = slice(h * P, (h + 1) * P)
        kept_ref[0, rows, :] = g_ref[0, 0, h:h + 1, :] * s[rows] + inc[rows]


@functools.partial(jax.jit, static_argnames=("S",))
def ssm_decode(state, x, dt, A, B, C, commit_at, *, S: Optional[int] = None):
    """:func:`step_scan`'s result in closed form, its one pass over the
    state a Pallas kernel.  With ``l_t = Σ_{r≤t} Δ_r A`` and ``u_s = Δ_s
    x_s``:

        y_t = e^{l_t} (S_0 C_t) + Σ_{s≤t} e^{l_t - l_s} (C_t·B_s) u_s
        S_k = e^{l_k} S_0 + Σ_{s≤k} e^{l_k - l_s} u_s ⊗ B_s

    The kernel (a grid of rows × blocks of heads) computes ``S_0 Cᵀ`` for
    every step and ``S_k`` in place of ``S_0`` (``S_0`` itself where
    ``commit_at`` is -1; the state is donated through the call's
    input-output alias); the sums over
    the pass's own steps are small and XLA's.  ``B`` and ``C`` are
    bfloat16 (a bfloat16 model's activations, exact); the products with
    the state are float32 in two passes of bfloat16 (:func:`_split`).
    ``S`` (new tokens of the pass) names the kernel, nothing else."""
    f32 = jnp.float32
    R, L, H, P = x.shape
    N = B.shape[-1]
    hb = heads_per_block(H, P, N)
    Lp = -(-L // 16) * 16
    l = jnp.cumsum(dt.astype(f32) * A.astype(f32), axis=1)      # [R, L, H]
    u = x.astype(f32) * dt.astype(f32)[..., None]               # [R, L, H, P]
    t = jnp.arange(L)
    G = jnp.einsum("rtn,rsn->rts", C.astype(f32), B.astype(f32),
                   precision=HIGHEST)
    causal = (t[:, None] >= t[None, :])[None, :, :, None]
    decay = jnp.exp(jnp.where(
        causal, l[:, :, None, :] - l[:, None, :, :], -jnp.inf))
    y = jnp.einsum("rts,rtsh,rshp->rthp", G, decay, u, precision=HIGHEST)

    k = jnp.asarray(commit_at, jnp.int32)
    lk = jnp.take_along_axis(l, jnp.clip(k, 0, L - 1)[:, None, None],
                             axis=1)[:, 0]                      # [R, H]
    g = jnp.where((k >= 0)[:, None], jnp.exp(lk), 1.0)
    w = jnp.exp(jnp.where((t[None, :] <= k[:, None])[..., None],
                          lk[:, None, :] - l, -jnp.inf))         # [R, L, H]
    w = (u * w[..., None]).reshape(R, L, H * P)
    rows = lambda a: jnp.pad(a, ((0, 0), (0, Lp - L), (0, 0)))  # noqa: E731
    c_in = rows(C.astype(jnp.bfloat16))
    b_in = rows(B.astype(jnp.bfloat16))
    w_in = rows(w)
    g_in = jnp.broadcast_to(g.reshape(R, H // hb, hb, 1),
                            (R, H // hb, hb, 128))
    s_in = state.reshape(R, H * P, N)

    state_spec = pl.BlockSpec((1, hb * P, N), lambda r, j: (r, j, 0))
    step_spec = pl.BlockSpec((1, Lp, N), lambda r, j: (r, 0, 0))
    lane_spec = pl.BlockSpec((1, Lp, hb * P), lambda r, j: (r, 0, j))
    z, kept = pl.pallas_call(
        functools.partial(_kernel, heads=hb, P=P),
        grid=(R, H // hb),
        in_specs=[state_spec, step_spec, step_spec, lane_spec,
                  pl.BlockSpec((1, 1, hb, 128), lambda r, j: (r, j, 0, 0))],
        out_specs=[lane_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((R, Lp, H * P), f32),
                   jax.ShapeDtypeStruct(s_in.shape, f32)],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=not _on_tpu(),
        name=f"ssm_decode_s{L if S is None else S}_r{R}",
    )(s_in, c_in, b_in, w_in, g_in)
    y = y + jnp.exp(l)[..., None] * z[:, :L].reshape(R, L, H, P)
    return y, kept.reshape(R, H, P, N)


def why_not(state, B, S: int) -> Optional[str]:
    """The reason the kernel does not take this pass, or ``None``: read from
    shapes, dtypes, the active mesh and the backend alone."""
    from rocket_tpu.parallel.context import current_mesh

    _, H, P, N = state.shape
    if S > MAX_CHUNK:
        return f"S > {MAX_CHUNK}"
    if state.dtype != jnp.float32 or B.dtype != jnp.bfloat16:
        return f"{state.dtype} state, {B.dtype} B"
    if P % 8 or N % 128 or heads_per_block(H, P, N) is None:
        return f"P={P} N={N}"
    mesh = current_mesh()
    if mesh is not None and mesh.devices.size > 1:
        return "mesh"
    return None if _on_tpu() else "backend"


def round_update(state, x, dt, A, B, C, commit_at, *, S: int):
    """A round's pass over a state-space layer: :func:`ssm_decode` where
    :func:`why_not` says nothing against it, :func:`step_scan` elsewhere;
    the choice is counted when the pass is traced."""
    from rocket_tpu.observe.trace import counter

    reason = why_not(state, B, S)
    if reason is not None:
        counter("ssm/decode/fallback", 1, reason=reason, S=S)
        return step_scan(state, x, dt, A, B, C, commit_at)
    R, H, P, N = state.shape
    counter("ssm/decode/kernel", 1, S=S, rows=R, heads=H,
            block=heads_per_block(H, P, N))
    return ssm_decode(state, x, dt, A, B, C, commit_at, S=S)
