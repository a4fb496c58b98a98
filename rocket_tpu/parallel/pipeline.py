"""Pipeline parallelism over the mesh's ``pipe`` axis — schedule-
parameterized SPMD pipelining (GPipe / 1F1B / interleaved 1F1B).

The reference has no pipeline parallelism (SURVEY §2.2 lists PP as absent;
the mesh API must merely not preclude it).  This makes the ``pipe`` axis
real, the TPU way:

- the layer-stacked parameters (the ``nn.scan`` layout, leading ``layers``
  dim) are **sharded over ``pipe``** — each stage holds ``L/P`` layers;
- activations flow stage-to-stage via ``lax.ppermute`` inside one
  ``shard_map``-ped program: microbatch ``m`` enters stage 0 at tick ``m``,
  reaches stage ``p`` at tick ``m + p`` (the classic GPipe schedule with
  ``P - 1`` bubble ticks at each end);
- every stage runs the identical SPMD program; bubbles are masked
  ``where``s, so shapes are static and XLA overlaps the ``ppermute`` with
  the next tick's compute;
- the backward pass needs no hand-written schedule: ``ppermute``
  transposes to the reverse rotation under ``jax.grad``, giving the
  reverse pipeline automatically.

Schedules (:func:`pipeline`, ``schedule=``):

``"gpipe"``
    All forwards, then the transposed reverse pipeline.  Every
    microbatch's per-layer residuals stay live until its backward —
    ``n_micro`` live microbatches per stage.
``"1f1b"``
    Same forward tick placement as GPipe (their *forward* schedules are
    identical); the difference is backward-phase residency.  In the
    single-controller SPMD form the backward cannot start before the
    caller's loss, so the 1F1B memory bound is realized two ways: here,
    rematerialization (``jax.checkpoint`` around each per-layer unit in
    ``_chunk_apply``) shrinks the autodiff stash to the per-layer
    boundary activations per tick; in :mod:`rocket_tpu.parallel.mpmd`,
    the per-stage runner starts each microbatch's backward as soon as it
    leaves the last stage, holding ≤P live microbatches exactly.
    :func:`schedule_plan` is the analytic accounting for both.
``"interleaved"``
    Interleaved 1F1B (arXiv 2412.14374 / Megatron): each stage owns
    ``n_chunks`` (= v) NON-contiguous layer chunks — global chunk
    ``k = c·P + p`` lives on stage ``p`` — so a microbatch visits stage
    ``p`` v times and the fill/drain bubble shrinks to ``(P-1)`` ticks of
    ``1/v``-height work: bubble fraction ``(P-1)/(v·M + P - 1)`` vs
    GPipe's ``(P-1)/(M + P - 1)``.  Requires ``L % (P·v) == 0`` and
    ``n_micro % P == 0``.

All three schedules are bit-equal in outputs and parameter gradients:
every schedule applies the identical per-layer op sequence to each
microbatch, and the transposed scan accumulates each layer's gradient
contributions in the same (descending-microbatch) order — IEEE float
addition is commutative but not associative, so the engine keeps the
*order* fixed across schedules rather than relying on tolerance.  The
same reasoning forces the per-layer *compiled program* to be shared:
``_chunk_apply`` applies layers through one remat'd length-1-scan unit
in every schedule, because XLA fuses the backward of a length-l scan
differently from l length-1 scans, which would otherwise shift low-order
grad bits between schedules whose chunk lengths differ.

Parameter layout: the caller always passes the canonical checkpoint
layout (ascending layers, leading dim annotated ``stage`` → ``pipe``).
The interleaved schedule permutes layers to its stage-chunked layout with
a static ``jnp.take`` *outside* ``shard_map`` — manifests, elastic
restore, and ``check_reshard`` keep stamping the canonical layout, and
the permutation transposes to an exact scatter under ``jax.grad``.

Composing with gradient accumulation: ``Module(fuse_accumulation=True)``
+ ``pipeline_microbatch_size`` feeds the WHOLE accumulation window
through one pipeline call — ``k x n_micro`` microbatches pay the
fill/drain bubble once per effective step instead of once per micro-call
(looped schedules; see ``engine.step.build_window_step``).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from rocket_tpu.parallel.collectives import shard_map

Carry = Any

#: The schedule vocabulary (validated by :func:`pipeline`,
#: ``TransformerConfig.pipeline_schedule`` and ``build_window_step``).
SCHEDULES = ("gpipe", "1f1b", "interleaved")


def _chunk_apply(fn: Callable, local_params: Any, x: Any, consts: tuple = ()) -> Any:
    """Apply this stage's stack of layers (leading dim = local layers).

    Layers are applied ONE AT A TIME, each as a remat'd length-1 scan over
    its parameter row.  Every schedule — and the MPMD chunk programs and
    the degraded single-stage path — composes this exact unit, which is
    the foundation of the cross-schedule bit-equality contract: a single
    scan over the whole chunk is NOT equivalent, because XLA fuses the
    transpose of a length-l scan differently from a length-1 scan's,
    shifting low-order grad bits between schedules whose chunk lengths
    differ (gpipe l = L/P vs interleaved l = L/(P*v)).  The checkpoint
    doubles as the 1F1B stash bound: autodiff saves only each layer's
    boundary input, not its internal residuals.
    """
    n_local = jax.tree_util.tree_leaves(local_params)[0].shape[0]

    def body(carry, layer_params):
        return fn(layer_params, carry, *consts), None

    unit = jax.checkpoint(
        lambda c, row: jax.lax.scan(body, c, row)[0], prevent_cse=False
    )
    carry = x
    for i in range(n_local):
        row = jax.tree_util.tree_map(
            lambda leaf: jax.lax.dynamic_slice_in_dim(leaf, i, 1, 0),
            local_params,
        )
        carry = unit(carry, row)
    return carry


def schedule_plan(
    schedule: str,
    n_stages: int,
    n_micro: int,
    n_chunks: int = 1,
    micro_act_bytes: int = 0,
) -> dict:
    """Analytic tick/residency accounting for a pipeline schedule — the
    ``memory_plan()``-style numbers the residency test asserts on (bytes from shapes and schedule structure, not
    measured allocations).

    Returns ``ticks_forward`` (stage-granularity forward ticks — an
    interleaved tick is ``1/n_chunks`` the work of a GPipe tick, which the
    ``bubble_fraction`` already normalizes away), ``ticks_total`` (forward
    + transposed backward), ``bubble_fraction`` (idle fraction per stage:
    ``(P-1)/(M+P-1)`` for gpipe/1f1b, ``(P-1)/(v·M+P-1)`` interleaved),
    ``live_microbatches`` (peak microbatches whose activations a stage
    holds for backward: ``M`` for gpipe, ``min(P, M)`` for 1f1b and
    interleaved — the 1F1B bound the MPMD runner realizes exactly), and
    ``live_activation_bytes`` (= live × ``micro_act_bytes`` when given).
    """
    if schedule not in SCHEDULES:
        raise ValueError(
            f"unknown schedule {schedule!r}; choose from {SCHEDULES}"
        )
    if n_chunks < 1:
        raise ValueError(f"n_chunks must be >= 1, got {n_chunks}")
    if schedule != "interleaved" and n_chunks != 1:
        raise ValueError(
            f"n_chunks={n_chunks} requires schedule='interleaved' "
            f"(got {schedule!r})"
        )
    P_, M, v = int(n_stages), int(n_micro), int(n_chunks)
    slots = v * M if schedule == "interleaved" else M
    ticks_forward = slots + P_ - 1
    bubble_ticks = 2 * (P_ - 1)
    bubble_fraction = (P_ - 1) / ticks_forward if ticks_forward else 0.0
    live = M if schedule == "gpipe" else min(P_, M)
    return {
        "schedule": schedule,
        "n_stages": P_,
        "n_micro": M,
        "n_chunks": v,
        "ticks_forward": ticks_forward,
        "ticks_total": 2 * ticks_forward,
        "bubble_ticks": bubble_ticks,
        "bubble_fraction": bubble_fraction,
        "live_microbatches": live,
        "live_activation_bytes": live * int(micro_act_bytes),
    }


def interleave_order(n_layers: int, n_stages: int, n_chunks: int) -> np.ndarray:
    """Layer permutation canonical → stage-chunked: stage ``p``'s shard
    (a contiguous ``L/P`` slice under ``P('pipe')``) holds its ``v``
    chunks ``k = c·P + p`` back to back (chunk slot ``c`` = local rows
    ``[c·ℓ, (c+1)·ℓ)``, ``ℓ = L/(P·v)``)."""
    ell = n_layers // (n_stages * n_chunks)
    return np.concatenate([
        np.arange((c * n_stages + p) * ell, (c * n_stages + p + 1) * ell)
        for p in range(n_stages)
        for c in range(n_chunks)
    ])


def pipeline(
    fn: Callable[..., Any],
    stacked_params: Any,
    xs: Any,
    mesh: Mesh,
    axis: str = "pipe",
    schedule: str = "gpipe",
    n_chunks: int = 1,
    xs_spec: Optional[Any] = None,
    consts: tuple = (),
    emit: Optional[Any] = None,
) -> Any:
    """Run ``xs`` (microbatched on dim 0) through layer-stacked params,
    pipelined over ``mesh`` axis ``axis`` under ``schedule``.

    Parameters
    ----------
    fn:
        ``fn(one_layer_params, x, *consts) -> x`` — a single layer.  ``x``
        may be a pytree (e.g. ``(hidden, positions, segment_ids)``); ``fn``
        must return the SAME structure — side inputs that attention needs
        per-microbatch (position ids, segment ids) ride the pipeline
        rotation with the activation and pass through each layer unchanged.
    stacked_params:
        pytree whose leaves share a leading layer dim ``L`` with
        ``L % P == 0`` (``P`` = size of the pipe axis); the interleaved
        schedule additionally needs ``L % (P * n_chunks) == 0``.
    xs:
        pytree of ``[n_micro, micro_batch, ...]`` microbatched arrays (a
        bare array is the single-leaf case).
    schedule:
        one of :data:`SCHEDULES` — see the module docstring for the
        bubble/residency trade.  All schedules are bit-equal in outputs
        and gradients.
    n_chunks:
        interleaved chunk count ``v`` (layer chunks per stage); must be 1
        for the other schedules.
    xs_spec:
        PartitionSpec for dims ``1:`` of each ``xs`` leaf/output (e.g.
        batch sharded over data axes); default fully replicated.  When
        ``xs`` has leaves of different ranks, pass a matching pytree of
        specs instead of a single spec.
    consts:
        extra microbatch-invariant arrays threaded to every ``fn`` call.
        Passed as explicit replicated shard_map arguments — closing over
        traced values from the outer (auto) mesh context inside the manual
        stage program is not allowed.
    emit:
        optional pytree of bools matching ``xs``: leaves marked False are
        pure pass-through side inputs — no output buffer is accumulated
        and no final all-reduce is paid for them; their slot in the result
        is ``None``.  Default: emit every leaf.

    Returns ``ys`` with the structure of ``xs`` (non-emitted leaves None).
    """
    if schedule not in SCHEDULES:
        raise ValueError(
            f"unknown schedule {schedule!r}; choose from {SCHEDULES}"
        )
    if n_chunks < 1:
        raise ValueError(f"n_chunks must be >= 1, got {n_chunks}")
    if schedule != "interleaved" and n_chunks != 1:
        raise ValueError(
            f"n_chunks={n_chunks} requires schedule='interleaved' "
            f"(got {schedule!r})"
        )
    n_stages = mesh.shape[axis]
    n_chunks = n_chunks if schedule == "interleaved" else 1
    xs_leaves, treedef = jax.tree_util.tree_flatten(xs)
    n_micro = xs_leaves[0].shape[0]
    for leaf in xs_leaves:
        if leaf.shape[0] != n_micro:
            raise ValueError(
                f"xs leaves disagree on microbatch count: {leaf.shape[0]} "
                f"vs {n_micro}"
            )
    param_leaves = jax.tree_util.tree_leaves(stacked_params)
    n_layers = param_leaves[0].shape[0]
    for leaf in param_leaves:
        if leaf.shape[0] != n_layers:
            raise ValueError(
                f"stacked_params leaves disagree on layer dim: "
                f"{leaf.shape[0]} vs {n_layers}"
            )
        if leaf.shape[0] % n_stages != 0:
            raise ValueError(
                f"layer dim {leaf.shape[0]} not divisible by {n_stages} "
                f"pipeline stages"
            )
    if schedule == "interleaved":
        if n_layers % (n_stages * n_chunks) != 0:
            raise ValueError(
                f"interleaved schedule: layer dim {n_layers} not divisible "
                f"by n_stages*n_chunks = {n_stages}*{n_chunks} = "
                f"{n_stages * n_chunks} (every chunk needs the same layer "
                f"count); pick n_chunks so L % (P*n_chunks) == 0, or use "
                f"schedule='1f1b'"
            )
        if n_micro % n_stages != 0:
            raise ValueError(
                f"interleaved schedule: n_micro {n_micro} not divisible by "
                f"the {n_stages}-stage pipe axis (microbatches stream in "
                f"groups of P); pad the microbatch count to a multiple of "
                f"{n_stages}, or use schedule='1f1b'"
            )
    if emit is None:
        emit_flags = [True] * len(xs_leaves)
    else:
        emit_flags = jax.tree_util.tree_leaves(emit)
        if len(emit_flags) != len(xs_leaves):
            raise ValueError(
                f"emit has {len(emit_flags)} leaves, xs has {len(xs_leaves)}"
            )
    if not any(emit_flags):
        raise ValueError("emit must keep at least one output leaf")

    def _mask_outputs(ys):
        leaves = jax.tree_util.tree_leaves(ys)
        return treedef.unflatten(
            [y if e else None for y, e in zip(leaves, emit_flags)]
        )

    if n_stages == 1:
        # Degraded single-stage path (any schedule): still apply per
        # microbatch — fn sees one [micro_batch, ...] slice at a time,
        # exactly as in the pipelined schedules.  The interleaved chunk
        # walk on one stage is the canonical ascending layer order, so
        # all three schedules collapse to the same program here.
        return _mask_outputs(jax.lax.map(
            lambda x: _chunk_apply(fn, stacked_params, x, consts), xs
        ))

    is_spec = lambda s: isinstance(s, P)  # noqa: E731
    if xs_spec is None:
        inner_specs = [P()] * len(xs_leaves)
    elif is_spec(xs_spec):
        if len({leaf.ndim for leaf in xs_leaves}) > 1 and len(xs_spec) > 0:
            raise ValueError(
                "xs has leaves of different ranks; pass xs_spec as a "
                "matching pytree of PartitionSpecs, not one spec"
            )
        inner_specs = [xs_spec] * len(xs_leaves)
    else:
        inner_specs = jax.tree_util.tree_leaves(xs_spec, is_leaf=is_spec)
        if len(inner_specs) != len(xs_leaves):
            raise ValueError(
                f"xs_spec has {len(inner_specs)} specs, xs has "
                f"{len(xs_leaves)} leaves"
            )
    full_specs = [P(None, *s) for s in inner_specs]
    xs_full_spec = treedef.unflatten(full_specs)
    out_spec = tuple(s for s, e in zip(full_specs, emit_flags) if e)
    param_spec = jax.tree_util.tree_map(
        lambda leaf: P(axis, *([None] * (leaf.ndim - 1))), stacked_params
    )
    const_spec = jax.tree_util.tree_map(lambda _: P(), consts)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    tmap = jax.tree_util.tree_map

    # every schedule applies layers through the same remat'd per-layer
    # unit inside _chunk_apply — identical compiled backward everywhere,
    # which is what makes cross-schedule grads bit-equal (see its doc)
    apply_chunk = lambda lp, a, cl: _chunk_apply(fn, lp, a, cl)  # noqa: E731

    if schedule == "interleaved":
        ell = n_layers // (n_stages * n_chunks)
        order = jnp.asarray(
            interleave_order(n_layers, n_stages, n_chunks)
        )
        stacked_params = tmap(
            lambda leaf: jnp.take(leaf, order, axis=0), stacked_params
        )
        v = n_chunks
        slots = v * n_micro

        def stage_program(local_params, xs_local, consts_local):
            p = jax.lax.axis_index(axis)
            ticks = slots + n_stages - 1

            def emitted(tree):
                return tuple(
                    leaf for leaf, e
                    in zip(jax.tree_util.tree_leaves(tree), emit_flags) if e
                )

            def tick(carry, t):
                act, ys = carry
                # this stage's work slot; slot s at stage 0 is item
                # (micro m, chunk slot c): s = g·v·P + c·P + i with
                # m = g·P + i — each rotation hands the item to the next
                # stage one tick later, and chunk c's exit from stage
                # P-1 re-enters stage 0 as chunk c+1 exactly P ticks on.
                s = t - p
                active = (s >= 0) & (s < slots)
                sc = jnp.clip(s, 0, slots - 1)
                r = sc % (v * n_stages)
                c = r // n_stages
                m = (sc // (v * n_stages)) * n_stages + (r % n_stages)
                ingest = (p == 0) & active & (c == 0)
                feed = tmap(
                    lambda a: a[jnp.clip(m, 0, n_micro - 1)], xs_local
                )
                act = tmap(
                    lambda f, a: jnp.where(ingest, f, a).astype(a.dtype),
                    feed,
                    act,
                )
                chunk_params = tmap(
                    lambda lp: jax.lax.dynamic_slice_in_dim(
                        lp, c * ell, ell, 0
                    ),
                    local_params,
                )
                y = apply_chunk(chunk_params, act, consts_local)
                do_emit = (p == n_stages - 1) & active & (c == v - 1)
                ys = tuple(
                    jnp.where(
                        do_emit,
                        jax.lax.dynamic_update_index_in_dim(
                            buf, yv, jnp.clip(m, 0, n_micro - 1), 0
                        ),
                        buf,
                    )
                    for buf, yv in zip(ys, emitted(y))
                )
                act = tmap(lambda yv: jax.lax.ppermute(yv, axis, perm), y)
                return (act, ys), None

            act0 = tmap(lambda a: jnp.zeros_like(a[0]), xs_local)
            ys0 = tuple(jnp.zeros_like(leaf) for leaf in emitted(xs_local))
            (_, ys), _ = jax.lax.scan(tick, (act0, ys0), jnp.arange(ticks))
            # only the last stage's buffer is the real output; replicate
            return tuple(
                jax.lax.psum(
                    jnp.where(p == n_stages - 1, buf, 0).astype(buf.dtype),
                    axis,
                )
                for buf in ys
            )

    else:

        def stage_program(local_params, xs_local, consts_local):
            p = jax.lax.axis_index(axis)
            ticks = n_micro + n_stages - 1

            def emitted(tree):
                return tuple(
                    leaf for leaf, e
                    in zip(jax.tree_util.tree_leaves(tree), emit_flags) if e
                )

            def tick(carry, t):
                act, ys = carry
                idx = jnp.minimum(t, n_micro - 1)
                feed = tmap(lambda a: a[idx], xs_local)
                # stage 0 ingests microbatch t (zeros in the drain phase)
                ingest = (p == 0) & (t < n_micro)
                act = tmap(
                    lambda f, a: jnp.where(
                        ingest, f, jnp.where(p == 0, 0, a).astype(a.dtype)
                    ),
                    feed,
                    act,
                )
                y = apply_chunk(local_params, act, consts_local)
                # last stage emits microbatch t-(P-1) from the fill's end
                out_idx = t - (n_stages - 1)
                do_emit = (p == n_stages - 1) & (out_idx >= 0)
                ys = tuple(
                    jnp.where(
                        do_emit,
                        jax.lax.dynamic_update_index_in_dim(
                            buf, yv, jnp.maximum(out_idx, 0), 0
                        ),
                        buf,
                    )
                    for buf, yv in zip(ys, emitted(y))
                )
                act = tmap(lambda yv: jax.lax.ppermute(yv, axis, perm), y)
                return (act, ys), None

            act0 = tmap(lambda a: jnp.zeros_like(a[0]), xs_local)
            ys0 = tuple(jnp.zeros_like(leaf) for leaf in emitted(xs_local))
            (_, ys), _ = jax.lax.scan(tick, (act0, ys0), jnp.arange(ticks))
            # only the last stage's buffer is the real output; replicate it
            return tuple(
                jax.lax.psum(
                    jnp.where(p == n_stages - 1, buf, 0).astype(buf.dtype),
                    axis,
                )
                for buf in ys
            )

    ys_out = shard_map(
        stage_program,
        mesh=mesh,
        in_specs=(param_spec, xs_full_spec, const_spec),
        out_specs=out_spec,
        check_vma=False,
    )(stacked_params, xs, consts)
    it = iter(ys_out)
    return treedef.unflatten(
        [next(it) if e else None for e in emit_flags]
    )


def gpipe(
    fn: Callable[..., Any],
    stacked_params: Any,
    xs: Any,
    mesh: Mesh,
    axis: str = "pipe",
    xs_spec: Optional[Any] = None,
    consts: tuple = (),
    emit: Optional[Any] = None,
) -> Any:
    """Back-compat spelling: :func:`pipeline` with ``schedule="gpipe"``
    (the schedule oracle the others are bit-equality-tested against)."""
    return pipeline(
        fn, stacked_params, xs, mesh, axis=axis, schedule="gpipe",
        xs_spec=xs_spec, consts=consts, emit=emit,
    )
