"""Module — the compute capsule: model + losses + optimizer + scheduler.

Capability parity: reference ``rocket/core/module.py:25-219`` — a Dispatcher
wrapping the model whose children are ``Loss``/``Optimizer``/``Scheduler``
capsules, running forward (+ children) once per iteration with AMP and
gradient accumulation (``module.py:110-142,175-219``).

TPU-first redesign (SURVEY §7.4 "hard parts"): the reference executes
forward → backward → step as separate Python-driven phases every iteration;
here Module **compiles them into one jitted, donated train step** at setup
time.  The child capsules are split into two roles:

- *in-step* (traced, pure): each ``Loss`` child contributes its pure
  objective fn; the ``Optimizer`` child contributes the optax transform; the
  ``Scheduler`` child contributes the LR schedule.  These are collected once
  and baked into ``engine.step.build_train_step``.
- *out-of-step* (host, evented): the same children still receive LAUNCH each
  iteration — but now only for their host-side duties (tracker records, loop
  status, counters), reading the step's log dict from ``attrs.step_logs``.

State is an explicit :class:`~rocket_tpu.engine.state.TrainState` pytree
owned by this capsule — the functional replacement for accelerate's
``_models``/``_optimizers`` registries.  It materializes lazily on the first
batch (or eagerly from ``input_spec``), jit-initialized with
``out_shardings`` so parameters are *born sharded* across the mesh.

Blackboard protocol:

- reads  ``attrs.batch`` (global device arrays), ``attrs.looper.grad_enabled``
- train: ``attrs.step_logs`` = per-step scalars (device) + ``synced`` flag
- eval:  rewrites ``attrs.batch`` with model outputs (reference
  ``module.py:139``) for downstream ``Meter`` capsules
"""

from __future__ import annotations

import math
from typing import Any, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp

from rocket_tpu.core.attributes import Attributes
from rocket_tpu.core.capsule import Capsule
from rocket_tpu.core.dispatcher import Dispatcher
from rocket_tpu.engine.adapter import FlaxModel, ModelAdapter, state_shardings
from rocket_tpu.engine.state import TrainState, param_count
from rocket_tpu.engine.ema import reseed_ema
from rocket_tpu.engine.step import (
    build_eval_step,
    build_train_step,
    build_window_step,
)
from rocket_tpu.observe.trace import span
from rocket_tpu.parallel.sharding import (
    DEFAULT_PARTITION_RULES,
    specs_for_state,
    tree_shardings,
)


def _as_adapter(model: Any) -> ModelAdapter:
    if isinstance(model, ModelAdapter):
        return model
    try:
        import flax.linen as nn

        if isinstance(model, nn.Module):
            return FlaxModel(model)
    except ImportError:  # pragma: no cover
        pass
    raise TypeError(
        f"Module expects a ModelAdapter or flax.linen.Module, got "
        f"{type(model).__name__}"
    )


class Module(Dispatcher):
    """Compute capsule (reference ``rocket/core/module.py``).

    Parameters
    ----------
    model:
        A :class:`~rocket_tpu.engine.adapter.ModelAdapter` or a
        ``flax.linen.Module`` whose ``__call__(batch, train)`` rewrites the
        batch (auto-wrapped in :class:`FlaxModel`).
    capsules:
        Child capsules — ``Loss`` / ``Optimizer`` / ``Scheduler`` (reference
        ``module.py:53-55``).
    input_spec:
        Optional abstract batch (pytree of ``jax.ShapeDtypeStruct``) for
        eager state materialization at setup; default is lazy
        materialization on the first batch.
    fuse_accumulation:
        With ``gradient_accumulation_steps > 1``: buffer the window's
        batches on host and run ONE jitted step over all of them
        (objectives averaged per window slice — numerically the micro/sync
        semantics).  Built for pipelined models (the GPipe fill/drain
        bubble is paid once per effective step, and
        ``pipeline_microbatch_size`` keeps microbatch size constant as the
        window widens); memory scales with the window's activations, so
        leave off for non-pipelined models.  A mid-window resume restarts
        the window (no ``grad_accum`` buffer exists to checkpoint) —
        align ``Checkpointer(save_every=...)`` to the accumulation
        boundary.
    """

    # Array state restores at materialization (sharded, direct to mesh) —
    # the Launcher's host-state resume pass skips this capsule.
    lazy_state = True

    def __init__(
        self,
        model: Any,
        capsules: Iterable[Capsule] = (),
        input_spec: Optional[Any] = None,
        statefull: bool = True,
        priority: int = 1000,
        donate: Optional[bool] = None,
        eval_with_ema: bool = False,
        fuse_accumulation: bool = False,
        skip_nonfinite: Optional[bool] = None,
        logger: Optional[Any] = None,
    ) -> None:
        super().__init__(
            capsules=capsules, statefull=statefull, priority=priority, logger=logger
        )
        self._adapter = _as_adapter(model)
        self._input_spec = input_spec
        # None = defer to runtime.donate_train_state (default True): the
        # TrainState argument's buffers are donated to the jitted step, so
        # XLA reuses them for the output state instead of holding both
        # alive.  Pass False explicitly (or Runtime(donate_train_state=
        # False)) as the escape hatch when the OLD state must outlive a
        # step — e.g. custom capsules diffing consecutive states.
        self._donate = donate
        self._eval_with_ema = eval_with_ema
        self._fuse_accum = fuse_accumulation
        # None = defer to runtime.skip_nonfinite_updates (set by a sibling
        # DivergenceSentinel(policy='skip')) at step-build time.  Pass True
        # explicitly when the steps build at setup (input_spec given) and
        # the sentinel mounts at a lower priority.
        self._skip_nonfinite = skip_nonfinite
        self._lr_scale: Optional[float] = None
        self._built = False
        self._state: Optional[TrainState] = None
        self._steps: Optional[dict] = None
        self._eval_step = None
        self._tx = None
        self._schedule = None
        self._micro_idx = 0
        self._accum = 1
        self._window_buffer: list = []
        self._pending_restore: Optional[Any] = None
        # ZeRO opt-state host-offload round-trip driver (engine.offload);
        # built at materialization when Runtime(zero_offload=True).
        self._offloader: Optional[Any] = None

    # -- setup / teardown ---------------------------------------------------

    def setup(self, attrs: Optional[Attributes] = None) -> None:
        if self._built:
            return  # dedupe: mounted in a second (eval) looper branch
        super().setup(attrs)
        if not self._runtime.register_unique("model", self._adapter):
            raise RuntimeError(
                "the same model adapter is wrapped by two Module capsules — "
                "share one Module instance across loopers instead "
                "(reference dedupe contract, module.py:92-96)."
            )
        self._collect_components()
        self._accum = self._runtime.gradient_accumulation_steps
        if self._runtime.resume_spec is not None and self.statefull:
            self._pending_restore = self._runtime.resume_spec
        if self._input_spec is not None:
            self.materialize(self._input_spec)
        self._built = True

    def destroy(self, attrs: Optional[Attributes] = None) -> None:
        if not self._built:
            return
        if self._runtime is not None:
            self._runtime.deregister_unique("model", self._adapter)
        if self._offloader is not None:
            self._offloader.close()
            self._offloader = None
        # Keep self._state: the trained params outlive the run, the way the
        # reference's torch module keeps its weights after launch.
        self._steps = None
        self._eval_step = None
        self._window_buffer = []
        self._built = False
        super().destroy(attrs)

    def _collect_components(self) -> None:
        from rocket_tpu.core.loss import Loss
        from rocket_tpu.core.optimizer import Optimizer
        from rocket_tpu.core.scheduler import Scheduler

        self._objectives = [
            c.objective for c in self._capsules if isinstance(c, Loss)
        ]
        optimizers = [c for c in self._capsules if isinstance(c, Optimizer)]
        schedulers = [c for c in self._capsules if isinstance(c, Scheduler)]
        if len(schedulers) > 1:
            raise RuntimeError(
                "a Module hosts at most one Scheduler (it is the default "
                "schedule; per-group schedules go on each Optimizer)"
            )
        self._schedule = schedulers[0].schedule if schedulers else None
        self._group_label_fn = None
        if self._eval_with_ema and not any(o.has_ema for o in optimizers):
            # Fail at setup, not at the first eval launch hours into a run.
            raise RuntimeError(
                "Module(eval_with_ema=True) requires an Optimizer with "
                "ema_decay set"
            )
        if len(optimizers) == 1 and optimizers[0].params_filter is None:
            opt = optimizers[0]
            effective = opt.own_schedule or self._schedule
            self._tx = opt.build_tx(effective)
            opt.attach_schedule(self._log_schedule_for(opt, effective))
        elif optimizers:
            # One optimizer WITH a params_filter also routes here: its
            # group trains, everything unmatched is frozen.
            self._tx = self._build_multi_tx(optimizers)
        if self._tx is not None and not self._objectives:
            raise RuntimeError(
                "Module has an Optimizer but no Loss — nothing to minimize"
            )

    def _build_multi_tx(self, optimizers: Sequence[Any]):
        """Compose N Optimizer capsules into one transform — the reference's
        per-optimizer torch param groups (``rocket/core/module.py:50-60``),
        done the optax way: ``multi_transform`` over path-labelled groups,
        params matched by no group frozen (``set_to_zero``)."""
        import optax

        tags = [o.tag for o in optimizers]
        if len(set(tags)) != len(tags):
            raise RuntimeError(
                f"multiple Optimizer capsules need distinct tag= for LR "
                f"logging, got {tags}"
            )
        if "frozen" in tags:
            # 'frozen' labels the unmatched-params bucket; a group with
            # that tag would merge into it in the accounting and dodge the
            # empty-group check.
            raise RuntimeError(
                "Optimizer tag='frozen' is reserved for the "
                "unmatched-params bucket — pick another tag"
            )
        for opt in optimizers:
            if len(optimizers) > 1 and opt.params_filter is None:
                raise RuntimeError(
                    "with multiple Optimizer capsules every one needs "
                    "params_filter=(path, leaf) -> bool to define its "
                    "param group"
                )
            if opt.has_ema:
                # Under multi_transform's masking the EMA would cover only
                # the group's leaves — Module.ema_params / eval_with_ema
                # would silently evaluate a partial tree.
                raise RuntimeError(
                    "ema_decay is not supported together with "
                    "params_filter param groups (the EMA would cover one "
                    "group only); for LoRA-style freezing with EMA use "
                    "wrap= (e.g. wrap=freeze_non_lora) instead"
                )

        filters = [o.params_filter for o in optimizers]

        def label(path, leaf):
            matches = [i for i, f in enumerate(filters) if f(path, leaf)]
            if len(matches) > 1:
                raise ValueError(
                    f"param {jax.tree_util.keystr(path)} matched by "
                    f"multiple Optimizers (tags "
                    f"{[tags[i] for i in matches]}); param groups must be "
                    f"disjoint"
                )
            return f"g{matches[0]}" if matches else "frozen"

        def label_fn(params):
            return jax.tree_util.tree_map_with_path(label, params)

        self._group_label_fn = label_fn
        transforms = {"frozen": optax.set_to_zero()}
        for i, opt in enumerate(optimizers):
            # A ready tx= owns its learning rate — the sibling Scheduler
            # default applies only to optimizers it CAN configure.
            if opt.has_ready_tx:
                effective = None
            else:
                effective = opt.own_schedule or self._schedule
            transforms[f"g{i}"] = opt.build_tx(effective)
            opt.attach_schedule(self._log_schedule_for(opt, effective))
        self._group_tags = tags
        return optax.multi_transform(transforms, label_fn)

    @staticmethod
    def _log_schedule_for(opt: Any, effective: Optional[Any]) -> Any:
        """What the Optimizer capsule should LOG as its LR: the effective
        schedule; a ready ``tx=`` owns its LR opaquely, so log nothing
        rather than a fabricated constant."""
        if effective is not None:
            return effective
        if opt.has_ready_tx:
            return None
        return opt.constant_schedule()

    # -- state materialization ---------------------------------------------

    def materialize(self, batch: Any) -> None:
        """Build (or restore) the TrainState + jitted steps for this batch
        structure.  ``batch`` may be concrete arrays or ShapeDtypeStructs."""
        runtime = self._runtime
        self.check_runtime()
        mesh = runtime.mesh
        policy = runtime.policy
        rng = jax.random.PRNGKey(runtime.seed)
        configure = getattr(self._adapter, "configure", None)
        if configure is not None:
            configure(mesh, runtime.rules)
        apply_policy = getattr(self._adapter, "apply_policy", None)
        if apply_policy is not None:
            apply_policy(policy)

        abstract_batch = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)), batch
        )

        def init_fn() -> TrainState:
            params, mutable = self._adapter.init_variables(rng, abstract_batch_concrete())
            params = policy.cast_to_param(params)
            tx = self._tx if self._tx is not None else _null_tx()
            return TrainState.create(
                params,
                tx,
                rng=rng,
                mutable=mutable,
                # Fused windows hold the whole window's batches instead of
                # a grad_accum buffer — the state needs none.
                gradient_accumulation_steps=(
                    1 if self._use_window else self._accum
                ),
            )

        def abstract_batch_concrete() -> Any:
            # Inside jit/eval_shape we need traceable zeros, not structs.
            return jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), abstract_batch
            )

        abstract_state = jax.eval_shape(init_fn)
        if self._use_window and jax.tree_util.tree_leaves(
            abstract_state.mutable
        ):
            # One fused forward updates mutable collections (batch stats)
            # once per window, not once per micro-batch — silently
            # different statistics vs the micro/sync path.
            raise RuntimeError(
                "fuse_accumulation=True does not support models with "
                "mutable collections (batch stats); use the default "
                "micro/sync accumulation"
            )
        if getattr(self, "_group_label_fn", None) is not None:
            # Param-group visibility: silent group membership is the
            # multi-optimizer footgun (a filter matching nothing trains
            # nothing) — log leaf/param counts per group up front.
            labels = self._group_label_fn(abstract_state.params)
            counts: dict = {}
            for lbl, leaf in zip(
                jax.tree_util.tree_leaves(labels),
                jax.tree_util.tree_leaves(abstract_state.params),
            ):
                name = (
                    self._group_tags[int(lbl[1:])]
                    if lbl.startswith("g") else lbl
                )
                n_leaves, n_params = counts.get(name, (0, 0))
                counts[name] = (
                    n_leaves + 1,
                    n_params + int(math.prod(leaf.shape)),
                )
            self._logger.info(
                "optimizer param groups: %s",
                {k: f"{v[1]:,} params / {v[0]} leaves"
                 for k, v in counts.items()},
            )
            for i, tag in enumerate(self._group_tags):
                if tag not in counts:
                    raise RuntimeError(
                        f"Optimizer tag={tag!r}: params_filter matched no "
                        f"parameters — group would train nothing"
                    )
        param_specs = self._adapter.partition_specs(
            abstract_state.params, runtime.rules
        )
        # One coherent resolution for the whole TrainState (params, optax
        # mirrors, mutable collections) from the runtime's PartitionRules
        # table — the same table the checkpoint manifest stamps and
        # check_reshard validates against.  zero_stage=1 re-partitions the
        # optimizer state over the data axis (engine.step all-gathers the
        # updated params inside the jitted step).
        plan = specs_for_state(
            mesh,
            abstract_state,
            rules=getattr(
                runtime, "partition_rules", DEFAULT_PARTITION_RULES
            ),
            param_specs=param_specs,
            zero_stage=getattr(runtime, "zero_stage", 0),
        )
        self._sharding_plan = plan
        self._abstract_state = abstract_state
        shardings = plan.state_shardings

        self._weights_override = None
        if self._pending_restore is not None:
            self._restore_state(abstract_state, shardings)
        if self._state is None:
            with jax.transfer_guard("allow"):
                self._state = jax.jit(init_fn, out_shardings=shardings)()
            if self._weights_override is not None:
                params, mutable = self._weights_override
                self._weights_override = None
                replacements = {"params": params}
                if mutable is not None:
                    replacements["mutable"] = mutable
                # Weights-only restore keeps the fresh optimizer state —
                # re-seed any parameter EMA to the restored weights so
                # eval_with_ema never runs the stale random-init snapshot.
                replacements["opt_state"] = reseed_ema(
                    self._state.opt_state, params
                )
                self._state = self._state.replace(**replacements)
            self._logger.info(
                "materialized %s params (%d leaves) on mesh %s",
                f"{param_count(self._state.params):,}",
                len(jax.tree_util.tree_leaves(self._state.params)),
                dict(mesh.shape),
            )
        self._shardings = shardings
        self._build_steps(policy)

    @property
    def _use_window(self) -> bool:
        return self._fuse_accum and self._accum > 1

    def _build_steps(self, policy) -> None:
        # The jit edges built here are the ledger's training chokepoints:
        # every step variant comes back as an ``_AnnotatedStep`` whose
        # dispatch routes through ``observe.ledger.ledger_call``, so a
        # post-warmup retrace of any of them trips the runtime sentinel.
        # The span times only host-side jit construction (compilation
        # happens at first dispatch, where the ledger attributes it —
        # :meth:`warm_start` moves that compile ahead of the first real
        # batch, against the persistent compile cache).
        with span("module/build_steps", fused=self._use_window):
            self._build_steps_inner(policy)

    def warm_start(self, batch: Any) -> Optional[dict]:
        """AOT-compile the built train step against a representative
        ``batch`` (ISSUE 15): ``lower().compile()`` — served from /
        written to the persistent compile cache, with executable
        serialization where the backend supports it — so the first real
        step dispatches a pre-built executable instead of compiling
        inline.  Returns the warmup stats dict, or ``None`` when steps
        are not built yet.  Never raises; a failed warm just means the
        first dispatch compiles as before."""
        try:
            from rocket_tpu.tune.warmup import warm_module_step

            stats = warm_module_step(self, batch)
            if stats is not None:
                self._logger.info(
                    "warm_start: %d edge(s) in %.0fms (%d cache hits)",
                    stats["edges"], stats["compile_ms"],
                    stats["cache_hits"])
            return stats
        except Exception:
            self._logger.warning("warm_start failed; first dispatch will "
                                 "compile inline", exc_info=True)
            return None

    def _build_steps_inner(self, policy) -> None:
        skip = (
            self._skip_nonfinite
            if self._skip_nonfinite is not None
            else bool(getattr(self._runtime, "skip_nonfinite_updates", False))
        )
        donate = self._donate
        if donate is None:
            donate = getattr(self._runtime, "donate_train_state", True)
        donate = bool(donate)
        self._donate = donate  # resolved: later rebuilds stay consistent
        # Capability gate, applied at the jit edge (the resolved intent
        # above is what rebuilds and user code see): XLA's CPU client does
        # not implement buffer donation — it warns and ignores the aliasing
        # — but a call with donated operands still dispatches
        # SYNCHRONOUSLY, which would serialize the non-blocking loop's
        # in-flight window for zero memory benefit.
        donate = donate and jax.default_backend() != "cpu"
        if self._tx is not None:
            if self._use_window:
                from rocket_tpu.parallel.sharding import ZeroIncompatibleError

                plan = getattr(self, "_sharding_plan", None)
                if plan is not None and plan.zero_stage >= 1:
                    raise ZeroIncompatibleError(
                        "fuse_accumulation", plan.zero_stage,
                        "use the default micro/sync accumulation "
                        "(fuse_accumulation=False)",
                        detail="the fused window step applies the update "
                        "outside the ZeRO shard domain",
                    )
                if getattr(self._runtime, "zero_offload", False):
                    raise ZeroIncompatibleError(
                        "zero_offload + fuse_accumulation",
                        getattr(plan, "zero_stage", 0),
                        "use the default micro/sync accumulation so the "
                        "offloader sees a sync boundary per window",
                        detail="zero_offload prefetches opt state at "
                        "micro/sync boundaries the fused window step does "
                        "not expose",
                    )
                if skip:
                    self._logger.warning(
                        "skip_nonfinite guard is not supported with "
                        "fuse_accumulation — fused window steps run unguarded"
                    )
                # the pipelined model's schedule keys the dispatch edge
                # name so per-schedule retrace/goodput attribution works
                sched = getattr(
                    getattr(
                        getattr(self._adapter, "module", None),
                        "config", None,
                    ),
                    "pipeline_schedule", "gpipe",
                )
                self._steps = {
                    "window": build_window_step(
                        self._adapter.apply_fn,
                        self._objectives,
                        self._tx,
                        policy=policy,
                        window=self._accum,
                        donate=donate,
                        pipeline_schedule=sched,
                    )
                }
            else:
                self._steps = build_train_step(
                    self._adapter.apply_fn,
                    self._objectives,
                    self._tx,
                    policy=policy,
                    gradient_accumulation_steps=self._accum,
                    donate=donate,
                    skip_nonfinite=skip,
                    shard_plan=getattr(self, "_sharding_plan", None),
                )
        self._eval_step = build_eval_step(
            self._adapter.apply_fn, self._objectives, policy=policy,
            use_ema=self._eval_with_ema,
            shard_plan=getattr(self, "_sharding_plan", None),
        )
        self._configure_offload()

    def _configure_offload(self) -> None:
        """(Re)build the opt-state host-offload driver when the runtime
        asks for it — one per materialization, closed on rebuild."""
        if self._offloader is not None:
            self._offloader.close()
            self._offloader = None
        plan = getattr(self, "_sharding_plan", None)
        if (
            not getattr(self._runtime, "zero_offload", False)
            or self._tx is None
            or plan is None
            or plan.zero_stage < 1
        ):
            return
        from rocket_tpu.engine.offload import ZeroOffloader

        self._offloader = ZeroOffloader(plan.opt_shardings)
        self._logger.info(
            "zero_offload armed: opt state round-trips host RAM per sync "
            "boundary (double-buffered prefetch; see docs/performance.md)"
        )

    def _restore_state(self, abstract_state: TrainState, shardings: Any) -> None:
        from rocket_tpu.persist.orbax_io import default_io

        spec = self._pending_restore
        self._pending_restore = None
        # Stage-transition visibility: the manifest stamps the SAVING
        # run's ZeRO stage; the restore target's specs come from THIS
        # run's plan, so a stage change is just a reshard — but a silent
        # one is undebuggable, so log it.  Legacy stage-less manifests
        # (no stamp) restore through the unchanged strict path.
        try:
            from rocket_tpu.persist.integrity import manifest_mesh

            saved_stage = (manifest_mesh(str(spec.path)) or {}).get(
                "zero_stage"
            )
        except Exception:
            saved_stage = None
        run_stage = int(getattr(self._runtime, "zero_stage", 0) or 0)
        if saved_stage is not None and int(saved_stage) != run_stage:
            self._logger.info(
                "elastic restore across ZeRO stage transition: snapshot "
                "saved at zero_stage=%d, run uses zero_stage=%d — "
                "resharding through this run's plan",
                int(saved_stage), run_stage,
            )
        if spec.load_capsules:
            # Full resume: whole TrainState (params, optimizer moments, step,
            # rng), restored sharded, direct to mesh layout.
            target = jax.tree_util.tree_map(
                lambda leaf, s: jax.ShapeDtypeStruct(
                    leaf.shape, leaf.dtype, sharding=s
                ),
                abstract_state,
                shardings,
            )
            restored = default_io().restore_item(
                str(spec.path), self._ckpt_key, target={"state": target}
            )
            self._state = restored["state"]
            self._sync_micro_idx()
            self._logger.info("restored full module state from %s", spec.path)
            return
        # Weights-only (reference ``launcher.py:349-359``): restore params +
        # mutable collections; optimizer state, step and rng start fresh —
        # the fine-tune-from-weights contract.  A partial target keeps the
        # restore sharded and tolerates a checkpoint whose optimizer
        # structure differs from this run's.
        partial = {"params": (abstract_state.params, shardings.params)}
        if jax.tree_util.tree_leaves(abstract_state.mutable):
            partial["mutable"] = (abstract_state.mutable, shardings.mutable)
        target = {
            field: jax.tree_util.tree_map(
                lambda leaf, s: jax.ShapeDtypeStruct(
                    leaf.shape, leaf.dtype, sharding=s
                ),
                abstract,
                shard,
            )
            for field, (abstract, shard) in partial.items()
        }
        restored = default_io().restore_item(
            str(spec.path), self._ckpt_key, target={"state": target}, partial=True
        )["state"]
        self._weights_override = (restored["params"], restored.get("mutable"))
        self._logger.info("restored weights only from %s", spec.path)

    # -- iteration ----------------------------------------------------------

    def launch(self, attrs: Optional[Attributes] = None) -> None:
        attrs = attrs if attrs is not None else Attributes()
        batch = attrs.batch
        if batch is None:
            return  # upstream Dataset exhausted / skipped
        if self._state is None or self._eval_step is None:
            # No eval step ⇒ steps were never built for this state (e.g. the
            # state arrived via load_state_dict); materialize keeps an
            # existing state and (re)builds the jitted steps.
            self.materialize(batch)

        looper = attrs.looper
        grad_enabled = True if looper is None else bool(looper.grad_enabled)

        if grad_enabled and self._steps is not None:
            if "window" in self._steps:
                # Fused accumulation: buffer the window, run ONE jitted
                # call on the boundary — a pipelined model pays its
                # fill/drain bubble once per effective step.
                self._window_buffer.append(batch)
                if len(self._window_buffer) < self._accum:
                    attrs.step_logs = None  # mid-window: nothing ran
                    self._launch_children(attrs)
                    return
                batches = tuple(self._window_buffer)
                self._window_buffer = []
                self._state, logs = self._steps["window"](
                    self._state, batches
                )
                logs = Attributes(logs)
                logs.synced = True
                logs.window_averaged = True  # Loss must not divide again
                attrs.step_logs = logs
            else:
                synced = (self._micro_idx + 1) % self._accum == 0
                step = self._steps["sync" if synced else "micro"]
                if synced and self._offloader is not None:
                    # Join the opt-state prefetch started after the LAST
                    # sync step: same tree structure and shardings as the
                    # live opt_state, so swapping it in re-uses the
                    # compiled step (zero retrace).  Any wait here books
                    # into the ledger's offload_wait bucket.
                    self._state = self._state.replace(
                        opt_state=self._offloader.fetch(self._state.opt_state)
                    )
                if self._lr_scale is None:
                    self._state, logs = step(self._state, batch)
                else:
                    # Cooldown scale rides in as a device scalar operand —
                    # changing its VALUE re-uses the compiled step; only the
                    # None↔scalar signature change traces once.
                    self._state, logs = step(
                        self._state, batch, jnp.float32(self._lr_scale)
                    )
                if synced and self._offloader is not None:
                    # Start the D2H writeback + next-step H2D prefetch;
                    # it overlaps the next window's forward/backward.
                    self._offloader.stash(self._state.opt_state)
                self._micro_idx = 0 if synced else self._micro_idx + 1
                logs = Attributes(logs)
                logs.synced = synced
                attrs.step_logs = logs
        else:
            batch_out, logs = self._eval_step(self._state, batch)
            attrs.batch = batch_out
            logs = Attributes(logs)
            logs.synced = False
            attrs.step_logs = logs

        # Children (Loss/Optimizer/Scheduler) do host-side logging only.
        self._launch_children(attrs)

    # -- resilience hooks (DivergenceSentinel) -------------------------------

    def set_lr_scale(self, value: Optional[float]) -> None:
        """Scale every optimizer update by ``value`` until reset with
        ``None`` — the sentinel's post-rollback LR cooldown.  Ignored by
        fused-window steps (which take no scale operand)."""
        self._lr_scale = None if value is None else float(value)

    def restore_from(self, path: Any) -> None:
        """Replace the live TrainState with the snapshot at ``path``
        (restored sharded, direct to mesh layout) — the sentinel's
        rollback-to-last-good hook."""
        if self._state is None:
            raise RuntimeError(
                "Module.restore_from before materialization — nothing to "
                "shape the restore target from"
            )
        from rocket_tpu.persist.orbax_io import default_io

        target = jax.tree_util.tree_map(
            lambda leaf, s: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=s
            ),
            self._state,
            self._shardings,
        )
        restored = default_io().restore_item(
            str(path), self._ckpt_key, target={"state": target}
        )
        self._state = restored["state"]
        self._sync_micro_idx()
        self._logger.info("rolled back module state to %s", path)

    # -- state --------------------------------------------------------------

    @property
    def state(self) -> Optional[TrainState]:
        return self._state

    @state.setter
    def state(self, value: TrainState) -> None:
        self._state = value

    @property
    def step(self) -> int:
        if self._state is None:
            return 0
        return int(self._state.step)

    @property
    def ema_params(self):
        """The parameter-EMA tree maintained by
        ``Optimizer(ema_decay=...)``, or None when EMA is off (see
        :func:`rocket_tpu.core.optimizer.params_ema`)."""
        if self._state is None:
            return None
        from rocket_tpu.core.optimizer import find_params_ema

        return find_params_ema(self._state.opt_state)

    @property
    def sharding_plan(self):
        """The :class:`~rocket_tpu.parallel.sharding.ShardingPlan` resolved
        at materialization (None before)."""
        return getattr(self, "_sharding_plan", None)

    def memory_plan(self) -> Optional[dict]:
        """Per-device byte accounting of the materialized state under its
        sharding plan (``{'param_bytes', 'opt_bytes', 'other_bytes',
        'total_bytes', 'host_opt_bytes'}`` — see
        :func:`rocket_tpu.engine.state.memory_plan`, and the ZeRO stage
        decision table in ``docs/performance.md`` for the per-stage
        formulas).  ``Runtime(zero_offload=True)`` moves the opt bytes to
        ``host_opt_bytes``.  None before materialization."""
        plan = getattr(self, "_sharding_plan", None)
        abstract = getattr(self, "_abstract_state", None)
        if plan is None or abstract is None:
            return None
        from rocket_tpu.engine.state import memory_plan

        return memory_plan(
            abstract, plan.state_specs, plan.mesh,
            zero_offload=bool(getattr(self._runtime, "zero_offload", False)),
        )

    def state_dict(self) -> Attributes:
        if self._state is None:
            return Attributes()
        return Attributes(state=self._state)

    def load_state_dict(self, state: Attributes) -> None:
        # Array state restores through _restore_state (needs shardings); a
        # direct host-side pytree (single-host tests) is also accepted.
        if state and "state" in state:
            self._state = state["state"]
            self._sync_micro_idx()

    def _sync_micro_idx(self) -> None:
        """Re-derive the host-side accumulation-window position from the
        restored TrainState so a resume that lands mid-window re-enters the
        window where it left off (``state.micro`` is the saved counterpart
        of ``_micro_idx``: +1 per micro step, reset to 0 at each sync)."""
        # Fused mode: the docstring contract is "a mid-window resume
        # restarts the window" — drop any pre-restore buffered batches or
        # the next boundary would train the restored params on stale data.
        self._window_buffer = []
        if self._state is not None and self._state.micro is not None:
            self._micro_idx = int(self._state.micro) % self._accum
        else:
            self._micro_idx = 0


def _null_tx():
    import optax

    return optax.identity()
