"""Jitted step builders — the execution core.

SURVEY §7.1: the per-iteration work the reference does in Python (forward →
loss → backward → step, ``module.py:110-142`` → ``loss.py:64-119`` →
``optimizer.py:111-147`` → ``scheduler.py:94-113``) becomes ONE pure,
donated-argument function compiled by XLA under a ``jax.sharding.Mesh``:

    ``state, logs = train_step(state, batch)``

What the compiler swallows (vs the reference's per-iteration Python):

- forward + backward — XLA-fused kernels on the MXU, bf16 per the policy
  (replaces autocast, ``module.py:210``);
- gradient all-reduce — inserted by GSPMD because the batch is sharded over
  the ``data``/``fsdp`` axes while params are replicated/sharded (replaces
  DDP's bucketed NCCL all-reduce armed in ``accelerator.prepare``,
  ``module.py:106``);
- the cross-process loss mean — the reference blocks on
  ``accelerator.gather(loss).mean()`` EVERY micro-batch purely for logging
  (``loss.py:95``, flagged as a defect in SURVEY §2.4); here ``jnp.mean``
  over the globally-sharded batch IS the global mean, compiled into the same
  program — zero extra launches;
- optimizer + scheduler step — optax transform application.

Gradient accumulation (reference ``accumulate()`` ctx + ``sync_gradients``
gating, ``module.py:211``, ``loss.py:101``, ``optimizer.py:133``) compiles to
TWO step variants instead of a data-dependent branch:

- ``micro`` — fwd/bwd, add grads into ``state.grad_accum``, no update;
- ``sync``  — fwd/bwd, apply ``(accum + g) / n`` through optax, reset.

The host picks the variant by a Python counter (the accumulation boundary is
statically known), so neither program contains dynamic control flow.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax

from rocket_tpu.engine.ema import find_params_ema
from rocket_tpu.engine.precision import Policy
from rocket_tpu.engine.state import TrainState
from rocket_tpu.observe.ledger import get_goodput, ledger_call
from rocket_tpu.observe.trace import span

# ``apply_fn(params, mutable, rng, batch, train)`` -> ``(batch_out, mutable)``
# — the model rewrites the batch blackboard-style, the functional analogue of
# ``attrs.batch = module.forward(attrs.batch)`` (reference ``module.py:139``).
ApplyFn = Callable[[Any, Any, jax.Array, Any, bool], Tuple[Any, Any]]

# ``objective(batch_out)`` -> scalar loss or ``(scalar, aux_logs)``.
ObjectiveFn = Callable[[Any], Any]


STEP_SPAN = "train/step_dispatch"
_GOODPUT = get_goodput()


class _AnnotatedStep:
    """Wrap a jitted step so each invocation runs inside the
    ``train/step_dispatch`` span (one name for every step variant; the
    jit edge's own name rides as the ``edge`` field) — a profiler
    annotation always, a ring event when the tracer is armed.  The span
    covers the HOST-side dispatch — tracing the args and enqueueing the
    async executable — which in a healthy pipeline is microseconds; any
    host fetch shows up elsewhere (``looper/host_fetch``).  Calls forward
    positionally, so donated buffers donate exactly as before, and every
    other ``PjitFunction`` attribute (``lower``, ``_cache_size``, ...)
    delegates to the wrapped function, which stays reachable as
    ``.jitted``.

    Dispatch routes through :func:`~rocket_tpu.observe.ledger.ledger_call`
    (ISSUE 9): the edge's first call is recorded as start-up; when the
    retrace ledger is armed, every compile at this edge is recorded and an
    unexpected post-warmup retrace escalates to a flight-recorder dump.
    The return of the dispatch is stamped on the goodput ledger: from
    there the device has work."""

    __slots__ = ("jitted", "_name", "_span")

    def __init__(self, fn: Callable, name: str,
                 span_name: str = STEP_SPAN) -> None:
        self.jitted = fn
        self._name = name
        self._span = span_name

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        with span(self._span, edge=self._name):
            out = ledger_call(self.jitted, self._name, *args, **kwargs)
        if _GOODPUT.armed:
            _GOODPUT.mark_dispatch()
        return out

    def __getattr__(self, attr: str) -> Any:
        return getattr(self.jitted, attr)


def _annotated_dispatch(fn: Callable, name: str,
                        span_name: str = STEP_SPAN) -> Callable:
    return _AnnotatedStep(fn, name, span_name)


@dataclasses.dataclass(frozen=True)
class Objective:
    """A named, weighted loss term (reference ``Loss`` capsule config,
    ``loss.py:51-62``)."""

    name: str
    fn: ObjectiveFn
    weight: float = 1.0


def _call_objective(obj: Objective, batch: Any) -> Tuple[jax.Array, Dict[str, Any]]:
    out = obj.fn(batch)
    if isinstance(out, tuple):
        value, aux = out
    else:
        value, aux = out, {}
    return jnp.asarray(value), dict(aux)


def _total_loss(
    objectives: Sequence[Objective], batch: Any
) -> Tuple[jax.Array, Dict[str, Any]]:
    logs: Dict[str, Any] = {}
    total = jnp.zeros((), dtype=jnp.float32)
    for obj in objectives:
        value, aux = _call_objective(obj, batch)
        logs[obj.name] = value
        for k, v in aux.items():
            logs[f"{obj.name}/{k}"] = v
        total = total + obj.weight * value.astype(jnp.float32)
    logs["loss"] = total
    return total, logs


def build_loss_fn(
    apply_fn: ApplyFn,
    objectives: Sequence[Objective],
    policy: Policy,
):
    """``(params, mutable, rng, batch) -> (loss, (logs, mutable, batch_out))``
    with the precision policy applied around the forward pass."""

    def loss_fn(params, mutable, rng, batch):
        # Autocast analogue (reference ``module.py:210``): params enter the
        # model in the compute dtype; the model families cast their own
        # INPUT leaves (images/tokens) to it.  The batch itself is NOT cast —
        # supervision targets and masks must keep full precision for the
        # objectives.
        compute_params = policy.cast_to_compute(params)
        batch_out, new_mutable = apply_fn(compute_params, mutable, rng, batch, True)
        total, logs = _total_loss(objectives, batch_out)
        return total, (logs, new_mutable, batch_out)

    return loss_fn


def build_train_step(
    apply_fn: ApplyFn,
    objectives: Sequence[Objective],
    tx: optax.GradientTransformation,
    policy: Policy = Policy(),
    gradient_accumulation_steps: int = 1,
    log_grad_norm: bool = True,
    donate: Optional[bool] = True,
    skip_nonfinite: bool = False,
    shard_plan: Optional[Any] = None,
) -> Dict[str, Callable[[TrainState, Any], Tuple[TrainState, Dict[str, Any]]]]:
    """Build the jitted training step(s).

    ``shard_plan`` (a :class:`rocket_tpu.parallel.sharding.ShardingPlan`
    with ``zero_stage >= 1``) turns on ZeRO-style cross-replica
    weight-update sharding (arXiv 2004.13336) inside the step.  At
    **stage 1** gradients are pinned to the params' sharding (so the
    backward subprogram stays identical to the unsharded step), then
    sliced to the data-composed shard domain; the optax update and the
    ``params + update`` add both run on the shard; the updated params
    are all-gathered back to the base domain; the new optimizer state
    stays on the shard.  The two explicit pins around the apply-add keep
    XLA's mul+add FMA contraction on-shard — exactly the grouping the
    unsharded step fuses — which is what makes the trajectory bit-equal,
    not just numerically close.

    **Stage 2** drops the base-domain pin on gradients: fresh grads are
    constrained straight to the zero shard, so GSPMD lowers the data-axis
    gradient reduction as a **reduce-scatter into the shard owner**
    instead of an all-reduce followed by a local slice — half the comm
    volume and no full-gradient replica.  Accumulation buffers live on
    the shard too (``specs_for_state`` re-partitions them), so the
    micro-window sum is an elementwise on-shard add — still exact.
    **Stage 3** additionally stores the params themselves on the zero
    shard: the top of the forward pins ``state.params`` to the base
    compute domain (the **all-gather on demand**), the update runs
    shard-to-shard, and the new params are pinned back to — and stay on —
    the shard, keeping the jit signature and the donation path intact.
    With ``shard_plan=None`` (or ``zero_stage=0``) the step body is
    byte-identical to the pre-ZeRO one.

    Returns ``{"sync": fn}`` when not accumulating, else
    ``{"sync": fn, "micro": fn}`` — the host calls ``micro`` for the first
    ``n-1`` batches of each window and ``sync`` on the boundary (reference
    ``sync_gradients`` cadence, ``loss.py:101``/``optimizer.py:133``).

    ``skip_nonfinite=True`` compiles the divergence guard INTO the step: a
    ``lax.cond`` applies the optimizer update (and adopts the new mutable
    collections) only when the loss and the gradient norm are finite, so
    one NaN batch cannot poison params or Adam moments.  The predicate
    lives on device — no host sync, no extra trace: the guard is part of
    the single compiled step body, and the happy path costs one scalar
    ``isfinite`` + select.  Skipped sync steps leave ``step``/params/
    opt_state untouched, still reset the accumulation window, and report
    ``logs['skipped'] = 1.0``.

    Every step additionally accepts a trailing ``lr_scale`` operand (device
    scalar); ``None`` (the default call signature) compiles without it.
    The DivergenceSentinel's rollback policy passes a cooldown factor
    through it — a changed VALUE is just a new input, only the None→scalar
    transition re-traces once.

    ``donate=True`` (the default) donates the ``TrainState`` argument's
    buffers to XLA (``donate_argnums=(0,)``): the output state reuses the
    input's storage, halving peak state memory and sparing a copy per
    step.  The caller contract is that the OLD state object is dead after
    the call — the Module upholds it by overwriting ``self._state`` with
    the step's result before anything else runs, and async checkpoint
    saves are safe because Orbax's D2H snapshot completes before ``save``
    returns.  ``donate=False`` (or ``Runtime(donate_train_state=False)``)
    is the escape hatch for callers that must keep consecutive states
    alive at once.  ``donate=None`` is True.
    """
    if gradient_accumulation_steps < 1:
        raise ValueError("gradient_accumulation_steps must be >= 1")
    loss_fn = build_loss_fn(apply_fn, objectives, policy)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    n = gradient_accumulation_steps
    stage = getattr(shard_plan, "zero_stage", 0) if shard_plan is not None else 0
    zero = stage >= 1

    def forward_backward(state: TrainState, batch: Any):
        rng = jax.random.fold_in(state.rng, state.step)
        if state.micro is not None:
            rng = jax.random.fold_in(rng, state.micro)
        params = state.params
        if stage >= 3:
            # All-gather on demand: storage is the ZeRO shard; the compute
            # domain is the base param sharding.  One pin at the top of the
            # forward is the whole gather — backward reuses the gathered
            # buffers, so the loss/grad subprogram matches the unsharded
            # step bit-for-bit.
            params = jax.lax.with_sharding_constraint(
                params, shard_plan.param_shardings
            )
        (loss, (logs, new_mutable, _)), grads = grad_fn(
            params, state.mutable, rng, batch
        )
        if stage >= 2:
            # Stage 2+: constrain fresh grads straight to the ZeRO shard.
            # GSPMD lowers the data-axis psum feeding a sharded consumer as
            # a reduce-scatter into the shard owner — no full-gradient
            # replica ever materializes.
            grads = jax.lax.with_sharding_constraint(
                grads, shard_plan.zero_param_shardings
            )
        return loss, grads, new_mutable, logs

    def micro_step(state: TrainState, batch: Any, lr_scale=None):
        loss, grads, new_mutable, logs = forward_backward(state, batch)
        if skip_nonfinite:
            finite = jnp.isfinite(loss) & jnp.isfinite(optax.global_norm(grads))
            # A nonfinite micro-batch contributes ZERO gradient to the
            # window (cond keeps the running sum) but still advances the
            # micro counter so the host's sync cadence stays aligned.
            accum = jax.lax.cond(
                finite,
                lambda: jax.tree_util.tree_map(
                    jnp.add, state.grad_accum, grads
                ),
                lambda: state.grad_accum,
            )
            new_mutable = jax.lax.cond(
                finite, lambda: new_mutable, lambda: state.mutable
            )
            logs["skipped"] = 1.0 - finite.astype(jnp.float32)
        else:
            accum = jax.tree_util.tree_map(jnp.add, state.grad_accum, grads)
        new_state = state.replace(
            grad_accum=accum,
            mutable=new_mutable,
            micro=state.micro + 1,
        )
        return new_state, logs

    def sync_step(state: TrainState, batch: Any, lr_scale=None):
        loss, grads, new_mutable, logs = forward_backward(state, batch)
        if n > 1:
            grads = jax.tree_util.tree_map(
                lambda a, g: (a + g) / n, state.grad_accum, grads
            )
        if log_grad_norm or skip_nonfinite:
            grad_norm = optax.global_norm(grads)
        if log_grad_norm:
            logs["grad_norm"] = grad_norm

        def apply_update(grads):
            if zero:
                if stage == 1:
                    # Stage 1: pin grads to the base param domain first
                    # (forces the backward to match the unsharded step
                    # bit-for-bit), then slice them — and the params — to
                    # the ZeRO shard.  Stage 2+ grads are already on-shard
                    # (reduce-scattered in forward_backward).
                    grads = jax.lax.with_sharding_constraint(
                        grads, shard_plan.param_shardings
                    )
                    grads = jax.lax.with_sharding_constraint(
                        grads, shard_plan.zero_param_shardings
                    )
                params_in = jax.lax.with_sharding_constraint(
                    state.params, shard_plan.zero_param_shardings
                )
            else:
                params_in = state.params
            updates, new_opt_state = tx.update(
                grads, state.opt_state, params_in
            )
            if lr_scale is not None:
                updates = jax.tree_util.tree_map(
                    lambda u: u * lr_scale, updates
                )
            new_params = optax.apply_updates(params_in, updates)
            if zero:
                # The shard-domain pin BEFORE the gather keeps the
                # params+update add (and its FMA contraction) on-shard;
                # at stages 1/2 the second constraint is then a pure
                # all-gather back to the base storage domain.  Stage 3
                # params are STORED on the shard — no gather, the output
                # sharding matches the (donated) input's.
                new_params = jax.lax.with_sharding_constraint(
                    new_params, shard_plan.zero_param_shardings
                )
                if stage < 3:
                    new_params = jax.lax.with_sharding_constraint(
                        new_params, shard_plan.param_shardings
                    )
                new_opt_state = jax.lax.with_sharding_constraint(
                    new_opt_state, shard_plan.opt_shardings
                )
            return new_params, new_opt_state, state.step + 1, new_mutable

        if skip_nonfinite:
            finite = jnp.isfinite(loss) & jnp.isfinite(grad_norm)
            new_params, new_opt_state, new_step, kept_mutable = jax.lax.cond(
                finite,
                apply_update,
                lambda grads: (
                    state.params, state.opt_state, state.step, state.mutable
                ),
                grads,
            )
            logs["skipped"] = 1.0 - finite.astype(jnp.float32)
        else:
            new_params, new_opt_state, new_step, kept_mutable = apply_update(
                grads
            )
        replacements = dict(
            step=new_step,
            params=new_params,
            opt_state=new_opt_state,
            mutable=kept_mutable,
        )
        if n > 1:
            # The window resets in BOTH cond branches — a skipped boundary
            # discards the whole window, keeping device micro/accum aligned
            # with the host's cadence counter.
            replacements["grad_accum"] = jax.tree_util.tree_map(
                jnp.zeros_like, state.grad_accum
            )
            replacements["micro"] = jnp.zeros((), dtype=jnp.int32)
        return state.replace(**replacements), logs

    donate_argnums = (0,) if donate is None or donate else ()
    steps = {"sync": _annotated_dispatch(
        jax.jit(sync_step, donate_argnums=donate_argnums),
        "train_step/dispatch/sync",
    )}
    if n > 1:
        steps["micro"] = _annotated_dispatch(
            jax.jit(micro_step, donate_argnums=donate_argnums),
            "train_step/dispatch/micro",
        )
    return steps


def build_window_step(
    apply_fn: ApplyFn,
    objectives: Sequence[Objective],
    tx: optax.GradientTransformation,
    policy: Policy = Policy(),
    window: int = 1,
    log_grad_norm: bool = True,
    donate: Optional[bool] = True,
    pipeline_schedule: str = "gpipe",
) -> Callable[[TrainState, Tuple[Any, ...]], Tuple[TrainState, Dict[str, Any]]]:
    """Fused gradient-accumulation step: ONE jitted call consumes the whole
    ``window``-batch accumulation window, concatenated on the batch dim,
    with one forward/backward.

    Built for pipelined models (``pipeline_microbatch_size``): the
    concatenated window flows through a single GPipe pass, so the
    ``2(P-1)``-tick fill/drain bubble is paid once per EFFECTIVE step
    instead of once per micro-batch (VERDICT r3 next #5).  Also skips the
    ``grad_accum`` buffer entirely — the window's activations replace it.

    Objective semantics match the micro/sync pair: each objective is
    evaluated per window slice and averaged with equal weight (NOT one
    mean over the concatenated batch — a per-token mean would weight
    slices by their valid-token counts when masks vary).  Two documented
    divergences from micro/sync: (a) the rng folds once per EFFECTIVE
    step, not once per micro-batch — deterministic (dropout-free) models
    only, which pipelining already requires; (b) mutable collections
    would update once per window — Module rejects them at materialize.

    Slicing contract: ``batch_out`` leaves whose leading dim equals the
    concatenated window row count are treated as batch-major per-example
    outputs (the blackboard batch-rewriting contract); other leaves pass
    through to every slice's objective unsliced.

    ``pipeline_schedule`` names the schedule the pipelined model inside
    ``apply_fn`` runs (selected by ``TransformerConfig.pipeline_schedule``;
    Module threads it through automatically).  The schedule itself lives
    in the model — here it keys the dispatch edge's trace/ledger name
    (``train_step/dispatch/window_1f1b`` etc.), so retrace sentinels and
    goodput attribution separate per schedule; all schedules are bit-equal
    in loss/grads, so swapping them never changes training math.
    """
    if window < 1:
        raise ValueError("window must be >= 1")
    from rocket_tpu.parallel.pipeline import SCHEDULES

    if pipeline_schedule not in SCHEDULES:
        raise ValueError(
            f"pipeline_schedule {pipeline_schedule!r} unknown; choose "
            f"from {SCHEDULES}"
        )

    def _concat_rows(*xs):
        # Row-concat via scatter into a zeros buffer instead of
        # jnp.concatenate: GSPMD mis-partitions batch-dim concats of
        # sharded operands under a pipe/tensor mesh (the same bug
        # documented in ops/fused_ce.py padding), silently corrupting
        # the window's rows before the GPipe pass.
        n = sum(x.shape[0] for x in xs)
        out = jnp.zeros((n,) + xs[0].shape[1:], xs[0].dtype)
        off = 0
        for x in xs:
            out = jax.lax.dynamic_update_slice_in_dim(out, x, off, 0)
            off += x.shape[0]
        return out

    def window_loss(params, mutable, rng, batches: Tuple[Any, ...]):
        concat = jax.tree_util.tree_map(_concat_rows, *batches)
        compute_params = policy.cast_to_compute(params)
        batch_out, new_mutable = apply_fn(
            compute_params, mutable, rng, concat, True
        )
        sizes = [
            jax.tree_util.tree_leaves(b)[0].shape[0] for b in batches
        ]
        offsets = [0]
        for s in sizes:
            offsets.append(offsets[-1] + s)
        total = jnp.zeros((), jnp.float32)
        logs: Dict[str, Any] = {}

        def slice_out(i):
            return jax.tree_util.tree_map(
                lambda x: jax.lax.slice_in_dim(
                    x, offsets[i], offsets[i + 1], axis=0
                )
                if hasattr(x, "ndim") and x.ndim > 0
                and x.shape[0] == offsets[-1]
                else x,
                batch_out,
            )

        for i in range(len(batches)):
            part, part_logs = _total_loss(objectives, slice_out(i))
            total = total + part / len(batches)
            for k, v in part_logs.items():
                logs[k] = logs.get(k, 0.0) + jnp.asarray(v, jnp.float32) / len(batches)
        logs["loss"] = total
        return total, (logs, new_mutable)

    grad_fn = jax.value_and_grad(window_loss, has_aux=True)

    def window_step(state: TrainState, batches: Tuple[Any, ...]):
        rng = jax.random.fold_in(state.rng, state.step)
        (loss, (logs, new_mutable)), grads = grad_fn(
            state.params, state.mutable, rng, batches
        )
        if log_grad_norm:
            logs["grad_norm"] = optax.global_norm(grads)
        updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        return (
            state.replace(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt_state,
                mutable=new_mutable,
            ),
            logs,
        )

    donate_argnums = (0,) if donate is None or donate else ()
    edge = "train_step/dispatch/window"
    if pipeline_schedule != "gpipe":
        edge = f"{edge}_{pipeline_schedule}"
    return _annotated_dispatch(
        jax.jit(window_step, donate_argnums=donate_argnums),
        edge,
    )


def build_eval_step(
    apply_fn: ApplyFn,
    objectives: Sequence[Objective] = (),
    policy: Policy = Policy(),
    use_ema: bool = False,
    shard_plan: Optional[Any] = None,
) -> Callable[[TrainState, Any], Tuple[Any, Dict[str, Any]]]:
    """Jitted evaluation step: forward only (reference eval path — grads off
    make Loss/Optimizer/Scheduler no-ops, ``loss.py:88-89``,
    ``optimizer.py:128``).  Returns ``(batch_out, logs)`` — the augmented
    batch feeds Meter/Metric capsules downstream (``meter.py:63-105``).

    ``use_ema=True`` evaluates with the parameter EMA maintained by
    ``Optimizer(ema_decay=...)`` instead of the live params (the usual
    inference weights for EMA-trained models); requires the transform to
    be in the chain.

    ``shard_plan`` with ``zero_stage >= 1`` pins the eval params to the
    base compute domain: a no-op at stages 1/2, and the all-gather from
    ZeRO-3's sharded storage (live params OR the EMA, which lives in the
    shard-domain opt_state) at stage 3."""
    eval_stage = (
        getattr(shard_plan, "zero_stage", 0) if shard_plan is not None else 0
    )

    def eval_step(state: TrainState, batch: Any):
        params = state.params
        if use_ema:
            ema = find_params_ema(state.opt_state)
            if ema is None:
                raise ValueError(
                    "eval_with_ema: no params_ema transform in the "
                    "optimizer chain — set Optimizer(ema_decay=...)"
                )
            params = ema
        if eval_stage >= 1:
            params = jax.lax.with_sharding_constraint(
                params, shard_plan.param_shardings
            )
        params = policy.cast_to_compute(params)
        batch_out, _ = apply_fn(params, state.mutable, state.rng, batch, False)
        logs: Dict[str, Any] = {}
        if objectives:
            _, logs = _total_loss(objectives, batch_out)
        return batch_out, logs

    return _annotated_dispatch(jax.jit(eval_step), "eval_step/dispatch",
                               "eval/step_dispatch")
