"""Runtime — the per-run execution context every capsule binds to.

This is the TPU-native replacement for the ``accelerate.Accelerator`` object
that the reference injects into every capsule (``rocket/core/capsule.py:
256-273``, created in ``launcher.py:185-193``).  It owns:

- the :class:`jax.sharding.Mesh` (device topology — replaces accelerate's
  implicit DDP process group),
- the mixed-precision :class:`~rocket_tpu.engine.precision.Policy`
  (replaces autocast/grad-scaler),
- gradient-accumulation configuration (replaces ``accumulate()`` /
  ``sync_gradients``),
- the **checkpoint registry** — ordered list of stateful capsules whose
  pytree states ride every snapshot (replaces ``register_for_checkpointing``
  + ``_custom_objects``, ``capsule.py:135-174``),
- **dedupe registries** so the same model/optimizer/dataset object mounted in
  two pipeline branches (train + eval) is only set up once (replaces
  accelerate's ``_models``/``_optimizers``/``_dataloaders`` scans, e.g.
  ``module.py:87-99``),
- tracker backends (replaces ``accelerator.get_tracker``/``init_trackers``),
- project directory state (set by the Launcher).

Unlike the Accelerator it performs **no wrapping**: models stay pure
functions, state stays an explicit pytree, and all device work happens in
jitted steps built by :mod:`rocket_tpu.engine.step`.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Union

import jax
from jax.sharding import Mesh, NamedSharding

from rocket_tpu.engine.precision import Policy
from rocket_tpu.parallel import multihost
from rocket_tpu.parallel.mesh import DATA_AXES, MeshSpec, data_parallel_mesh
from rocket_tpu.parallel.sharding import (
    DEFAULT_PARTITION_RULES,
    DEFAULT_RULES,
    ZERO_STAGES,
    PartitionRules,
    ShardingRules,
    ZeroIncompatibleError,
    batch_sharding,
    replicated,
)


class Runtime:
    def __init__(
        self,
        mesh: Union[None, Mesh, MeshSpec] = None,
        mixed_precision: str = "no",
        gradient_accumulation_steps: int = 1,
        rules: ShardingRules = DEFAULT_RULES,
        seed: int = 0,
        tracing: bool = False,
        trace_capacity: int = 4096,
        donate_train_state: Optional[bool] = None,
        partition_rules: Optional[PartitionRules] = None,
        zero_stage: int = 0,
        zero_offload: bool = False,
    ) -> None:
        if mesh is None:
            mesh = data_parallel_mesh()
        elif isinstance(mesh, MeshSpec):
            mesh = mesh.build()
        self._mesh: Mesh = mesh
        self.policy = (
            mixed_precision
            if isinstance(mixed_precision, Policy)
            else Policy.from_string(mixed_precision)
        )
        if gradient_accumulation_steps < 1:
            raise ValueError("gradient_accumulation_steps must be >= 1")
        self.gradient_accumulation_steps = int(gradient_accumulation_steps)
        self.rules = rules
        # Path-based rule engine (parallel.sharding.PartitionRules): the
        # single table the trainer's state shardings, the manifest stamp
        # and check_reshard all resolve from.  Defaults to the zoo-covering
        # DEFAULT_PARTITION_RULES retargeted to this run's logical-axis
        # table.
        self.partition_rules = (
            partition_rules
            if partition_rules is not None
            else DEFAULT_PARTITION_RULES.with_axes(rules)
        )
        # ZeRO stage (arXiv 2004.13336): 0 = replicated optimizer state,
        # 1 = optimizer state + weight update sharded over the data axis,
        # 2 = + gradients reduce-scattered into the shard owner,
        # 3 = + params stored sharded with all-gather-on-demand.  Every
        # stage keeps the training trajectory bit-equal to unsharded.
        if zero_stage not in ZERO_STAGES:
            raise ValueError(
                f"zero_stage must be one of {ZERO_STAGES}, got {zero_stage!r}"
            )
        self.zero_stage = int(zero_stage)
        # Host-RAM offload of shard-owner optimizer state (double-buffered
        # H2D prefetch one step ahead; engine.offload.ZeroOffloader).  Only
        # meaningful when the opt state is actually sharded.
        if zero_offload and self.zero_stage < 1:
            raise ZeroIncompatibleError(
                "zero_offload", self.zero_stage,
                "set zero_stage >= 1 so the optimizer state has a shard "
                "owner to offload",
                detail="offload stashes each shard owner's opt-state "
                "partition in host RAM; with replicated opt state there "
                "is no partition to own",
            )
        self.zero_offload = bool(zero_offload)
        self.seed = int(seed)
        # Host-side structured tracing (observe.trace): arming here turns
        # on the Dispatcher's per-capsule lifecycle spans, the serve loop's
        # per-request spans, and the Launcher's flight-recorder install.
        # Lazy import — observe pulls in core capsules, runtime must not.
        self.tracing = bool(tracing)
        if self.tracing:
            from rocket_tpu.observe.trace import arm

            arm(trace_capacity)

        self._checkpointables: List[Any] = []
        self._ckpt_counter = 0
        self._unique: Dict[str, List[Any]] = {}
        self._trackers: Dict[str, Any] = {}
        self.project_dir: Optional[str] = None
        self.logging_dir: Optional[str] = None
        # Run-level stop vote (preemption, divergence abort): the Launcher's
        # epoch loop checks it between cycles, so a vote cast where no
        # ``attrs.looper`` exists still stops the run (ISSUE 2 satellite).
        self.stop_training = False
        self.stop_reason: Optional[str] = None
        # Set by DivergenceSentinel(policy="skip") at setup; Module reads it
        # when building the jitted steps (engine.step skip_nonfinite guard).
        self.skip_nonfinite_updates = False
        # Run-level escape hatch for train-state buffer donation: Modules
        # that were not given an explicit ``donate=`` resolve it from here
        # at step-build time (engine.step donate_argnums).  Told nothing
        # (None), the runtime says True.
        self.donate_train_state = (
            True if donate_train_state is None else bool(donate_train_state)
        )
        # Pending resume request (set by Launcher.resume): Attributes with
        # ``path`` and ``load_capsules``.  Capsules with lazily-materialized
        # array state (Module) consume it at materialization time; host-scalar
        # states are restored by Launcher._resume right after setup.
        self.resume_spec: Optional[Any] = None

    # -- topology -----------------------------------------------------------

    @property
    def mesh(self) -> Mesh:
        return self._mesh

    @property
    def device_count(self) -> int:
        return self._mesh.devices.size

    @property
    def process_index(self) -> int:
        return jax.process_index()

    @property
    def process_count(self) -> int:
        return jax.process_count()

    @property
    def is_main_process(self) -> bool:
        return jax.process_index() == 0

    @property
    def data_parallel_size(self) -> int:
        """Number of data-parallel shards (product of the batch axes)."""
        shape = self._mesh.shape
        size = 1
        for axis in DATA_AXES:
            size *= shape.get(axis, 1)
        return size

    def wait_for_everyone(self, tag: str = "barrier") -> None:
        multihost.sync_global_devices(tag)

    @property
    def tracer(self):
        """The process-global :class:`~rocket_tpu.observe.trace.Tracer`
        (enabled iff ``tracing`` armed it — or someone armed it directly)."""
        from rocket_tpu.observe.trace import get_tracer

        return get_tracer()

    def request_stop(self, reason: str = "") -> None:
        """Vote to end the run at the next epoch boundary (preemption,
        divergence abort).  Sticky for the rest of the launch."""
        self.stop_training = True
        self.stop_reason = reason or self.stop_reason

    # -- shardings ----------------------------------------------------------

    def batch_sharding(self, ndim: int = 1, seq_dim: Optional[int] = None) -> NamedSharding:
        return batch_sharding(self._mesh, ndim=ndim, seq_dim=seq_dim)

    def replicated(self) -> NamedSharding:
        return replicated(self._mesh)

    # -- checkpoint registry (LIFO, reference capsule.py:135-174) ------------

    def register_for_checkpointing(self, capsule: Any) -> str:
        """Register a stateful capsule; returns its stable checkpoint key
        (``<classname>_<index>`` — deterministic because setup order is the
        priority-sorted tree order)."""
        if capsule in self._checkpointables:
            raise RuntimeError(
                f"{type(capsule).__name__} is already registered for "
                f"checkpointing — mount each stateful capsule once."
            )
        # Monotonic counter — indexes are never reused even after a
        # deregister, so two live capsules can never collide on a key.
        key = f"{type(capsule).__name__.lower()}_{self._ckpt_counter}"
        self._ckpt_counter += 1
        self._checkpointables.append(capsule)
        return key

    def deregister_checkpointable(self, capsule: Any) -> None:
        """Remove a capsule from the registry by identity.

        The reference deregisters by LIFO pop against accelerate's
        ``_custom_objects`` because its checkpoint format matches states by
        LIST POSITION (``capsule.py:165-174``).  Ours matches by stable
        string key, so destroy order cannot corrupt a checkpoint — and
        capsules legitimately shared across pipeline branches (one Module in
        the train and eval looper) make strict LIFO impossible.
        """
        for i, existing in enumerate(self._checkpointables):
            if existing is capsule:
                del self._checkpointables[i]
                return
        raise RuntimeError(
            f"{type(capsule).__name__} is not in the checkpoint registry — "
            f"double destroy?"
        )

    @property
    def checkpointables(self) -> List[Any]:
        return list(self._checkpointables)

    # -- dedupe registries (reference module.py:87-99 etc.) ------------------

    def register_unique(self, kind: str, obj: Any) -> bool:
        """Register ``obj`` under ``kind``; returns True if it was new,
        False if the identical object is already mounted elsewhere (the
        caller should then share instead of re-preparing)."""
        bucket = self._unique.setdefault(kind, [])
        for existing in bucket:
            if existing is obj:
                return False
        bucket.append(obj)
        return True

    def deregister_unique(self, kind: str, obj: Any) -> None:
        bucket = self._unique.get(kind, [])
        for i, existing in enumerate(bucket):
            if existing is obj:
                del bucket[i]
                return

    # -- trackers ------------------------------------------------------------

    def get_tracker(self, name: str) -> Optional[Any]:
        return self._trackers.get(name)

    def register_tracker(self, name: str, backend: Any) -> None:
        self._trackers[name] = backend

    @property
    def trackers(self) -> Dict[str, Any]:
        return dict(self._trackers)

    def end_training(self) -> None:
        """Flush/close tracker backends (reference ``end_training``,
        ``launcher.py:313``)."""
        for backend in self._trackers.values():
            close = getattr(backend, "close", None) or getattr(
                backend, "finish", None
            )
            if close is not None:
                try:
                    close()
                except Exception:  # never let tracker teardown kill the run
                    pass
        self._trackers.clear()
