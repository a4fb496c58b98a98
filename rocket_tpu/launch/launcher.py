"""Launcher — the root of the capsule tree; owns the run.

Capability parity: reference ``rocket/core/launcher.py:37-448``:

- versioned experiment dirs ``<root>/<tag>/v0,v1,…`` resolved once and
  broadcast to every host (``launcher.py:125-150``), mkdir on the main
  process + barrier (``:152-161``);
- creates the execution context at setup and injects it into the whole tree
  (Accelerator there → :class:`~rocket_tpu.runtime.Runtime` here,
  ``:185-193``);
- the epoch loop: ``attrs.launcher.epoch_idx`` then ``set → launch → reset``
  on every child per epoch (``:278-286``);
- resume: full (weights + capsule states) or weights-only, with the
  identical-topology guard (``:319-375``); epoch loop restarts at the
  restored ``epoch_idx`` (``:278``);
- teardown in reverse order + process-group shutdown (``:293-317``).

TPU-first: process bring-up is ``jax.distributed`` (one process per host —
the TPU runtime pre-wires ICI; ``notebook_launcher``'s fork-N-workers model
does not exist on TPU pods, so ``launch()`` is the single entry point);
mixed precision is a dtype policy, not autocast; and checkpoint restore is
sharded Orbax, not pickled ``load_state``.
"""

from __future__ import annotations

import os
from typing import Any, Iterable, Optional, Union

from rocket_tpu.core.attributes import Attributes
from rocket_tpu.core.capsule import Capsule
from rocket_tpu.core.dispatcher import Dispatcher
from rocket_tpu.observe.trace import get_startup
from rocket_tpu.parallel import multihost
from rocket_tpu.runtime import Runtime


class Launcher(Dispatcher):
    """Parameters
    ----------
    capsules:
        Top-level children — typically Loopers (train, eval).
    tag:
        Experiment name; enables the versioned project dir. ``None`` = no
        project dir (and Checkpointer/Tracker that need one will complain,
        reference ``checkpoint.py:75-81``).
    num_epochs:
        Epoch-loop length (reference ``launcher.py:101``).
    mesh:
        ``jax.sharding.Mesh`` / ``MeshSpec`` / ``None`` (all devices on the
        data axis — the reference's DDP topology).
    mixed_precision / gradient_accumulation_steps / seed:
        Runtime policy knobs (reference ``launcher.py:100-101``).
    project_root:
        Parent of experiment dirs (default ``./experiments``).
    goodput:
        Arm the goodput + retrace ledgers for the run (default True —
        the disarmed-equivalent cost is one branch per dispatch; armed,
        it adds no jit trace and no blocking read,
        ``tests/test_overhead_counts.py::TestGoodputGuard``).  The bucket table
        is logged at launch end and persisted as ``<project>/goodput.json``.
    metrics_port:
        Opt-in: serve the Prometheus-text ``/metrics`` endpoint on this
        port for the duration of the run (``0`` = OS-assigned; ``None``
        = no endpoint).
    """

    def __init__(
        self,
        capsules: Iterable[Capsule] = (),
        tag: Optional[str] = None,
        num_epochs: int = 1,
        mesh: Any = None,
        mixed_precision: str = "no",
        gradient_accumulation_steps: int = 1,
        seed: int = 0,
        tracing: bool = False,
        project_root: str = "experiments",
        runtime: Optional[Runtime] = None,
        statefull: bool = True,
        priority: int = 1000,
        logger: Optional[Any] = None,
        goodput: bool = True,
        metrics_port: Optional[int] = None,
        zero_stage: int = 0,
        zero_offload: bool = False,
    ) -> None:
        super().__init__(
            capsules=capsules, statefull=statefull, priority=priority, logger=logger
        )
        self._tag = tag
        self._zero_stage = int(zero_stage)
        self._zero_offload = bool(zero_offload)
        self._num_epochs = int(num_epochs)
        self._mesh = mesh
        self._mixed_precision = mixed_precision
        self._grad_accum = int(gradient_accumulation_steps)
        self._seed = int(seed)
        self._tracing = bool(tracing)
        self._project_root = project_root
        self._external_runtime = runtime
        self._epoch_idx = 0
        self._resume_path: Optional[str] = None
        self._resume_load_capsules = True
        self._goodput = bool(goodput)
        self._metrics_port = metrics_port
        self._metrics_server: Optional[Any] = None

    # -- project dirs --------------------------------------------------------

    def _resolve_project_dir(self) -> Optional[str]:
        """Next free ``<root>/<tag>/v{N}``, agreed across hosts (reference
        ``launcher.py:125-150``)."""
        if self._tag is None:
            return None
        base = os.path.join(self._project_root, self._tag)
        version = 0
        if os.path.isdir(base):
            versions = [
                int(name[1:])
                for name in os.listdir(base)
                if name.startswith("v") and name[1:].isdigit()
            ]
            version = max(versions) + 1 if versions else 0
        path = os.path.join(base, f"v{version}")
        # All hosts must agree on the dir (clocks/list races) — host 0 decides.
        path = multihost.broadcast_object(path)
        return path

    def _create_project_dir(self, runtime: Runtime) -> None:
        """mkdir on main + barrier (reference ``launcher.py:152-161``)."""
        if runtime.project_dir is None:
            return
        if runtime.is_main_process:
            os.makedirs(runtime.project_dir, exist_ok=True)
            os.makedirs(runtime.logging_dir, exist_ok=True)
        runtime.wait_for_everyone("project-dir")

    # -- lifecycle -----------------------------------------------------------

    def setup(self, attrs: Optional[Attributes] = None) -> None:
        startup = get_startup()
        with startup.phase("startup/runtime"):
            self._setup_runtime()
        # Children's set-up: Module.materialize and module/build_steps do
        # nearly all of it.
        with startup.phase("startup/build"):
            super().setup(attrs)

    def _setup_runtime(self) -> None:
        multihost.initialize()
        runtime = self._external_runtime or Runtime(
            mesh=self._mesh,
            mixed_precision=self._mixed_precision,
            gradient_accumulation_steps=self._grad_accum,
            seed=self._seed,
            tracing=self._tracing,
            zero_stage=self._zero_stage,
            zero_offload=self._zero_offload,
        )
        runtime.project_dir = self._resolve_project_dir()
        if runtime.project_dir is not None:
            runtime.logging_dir = os.path.join(runtime.project_dir, "logs")
        # A re-launch (same process, possibly same external runtime) starts
        # with a clean stop vote — stop_training is per-run, not per-process.
        runtime.stop_training = False
        runtime.stop_reason = None
        self.bind(runtime)
        self._create_project_dir(runtime)
        # Warm-start tier (ISSUE 15): arm the per-host persistent
        # compile cache before anything traces — a relaunch then pays
        # disk retrieval instead of XLA compilation for every executable
        # a previous run built.  The directory is
        # $JAX_COMPILATION_CACHE_DIR or the in-checkout default; one that
        # cannot be created fails the launch.
        from rocket_tpu.tune import compile_cache

        # (arming installs the hit/miss and trace/compile/retrieval
        # listeners the start-up line and the exports read)
        self._logger.info("persistent compile cache: %s",
                          compile_cache.enable_compile_cache())
        if getattr(runtime, "tracing", False):
            self._arm_flight_recorder(runtime)
        if self._goodput:
            self._arm_goodput()
        if self._resume_path is not None:
            resolved = self._resolve_resume_path(runtime)
            if resolved is not None:
                runtime.resume_spec = Attributes(
                    path=resolved,
                    load_capsules=self._resume_load_capsules,
                )

    def _arm_flight_recorder(self, runtime: Runtime) -> None:
        """Tracing armed: stamp the cross-host merge anchor at a barrier
        (every host anchors the same instant, up to barrier skew — the
        alignment point ``merge_traces`` uses) and install the process
        flight recorder writing to ``<project>/logs/flightrec`` (ISSUE 4).
        Lazy imports: launch must not pull observe in for untraced runs."""
        from rocket_tpu.observe import recorder as flightrec
        from rocket_tpu.observe.trace import arm

        tracer = arm()  # external Runtime with tracing=True set post-init
        runtime.wait_for_everyone("trace-anchor")
        tracer.set_anchor()
        base = runtime.logging_dir or os.path.join(
            self._project_root, "logs"
        )
        rec = flightrec.FlightRecorder(
            tracer, out_dir=os.path.join(base, "flightrec"),
            logger=self._logger,
        )
        flightrec.install(rec)
        self._logger.info(
            "tracing armed: flight recorder -> %s", rec.out_dir
        )

    def _arm_goodput(self) -> None:
        """Open the run's goodput window and arm the retrace sentinel
        (ISSUE 9).  Safe without tracing: the sentinel only dumps when a
        flight recorder is installed, and the goodput buckets are plain
        host arithmetic.  The goodput snapshot also rides along in every
        flight dump via the recorder's dump-writer hook.  Lazy imports,
        same discipline as ``_arm_flight_recorder``."""
        from rocket_tpu.observe import ledger as ledger_mod
        from rocket_tpu.observe import recorder as flightrec

        ledger_mod.arm_ledgers()
        flightrec.add_dump_writer(ledger_mod.goodput_dump_writer)
        if self._metrics_port is not None:
            from rocket_tpu.observe.export import MetricsServer

            self._metrics_server = MetricsServer(
                port=int(self._metrics_port)
            ).start()
            self._logger.info(
                "metrics endpoint: http://127.0.0.1:%d/metrics",
                self._metrics_server.port,
            )

    def _resolve_resume_path(self, runtime: Runtime) -> Optional[str]:
        """Turn the armed resume request into a VERIFIED snapshot path.

        ``"auto"`` scans the tag's versioned project dirs for the newest
        snapshot that passes :func:`~rocket_tpu.persist.integrity.verify`
        (none found = fresh start, the restart-the-same-command contract).
        An explicit path is verified too; a broken one is quarantined and
        the newest valid sibling takes over — restore falls back instead of
        crashing on a half-written snapshot.  Host 0 decides (it owns the
        quarantine renames); everyone adopts its answer.
        """
        from rocket_tpu.persist import integrity

        path = self._resume_path
        resolved: Optional[str] = None
        failed = False
        if runtime.is_main_process:
            if path == "auto":
                if self._tag is None:
                    raise RuntimeError(
                        "resume('auto') needs a project dir — give the "
                        "Launcher a tag"
                    )
                base = os.path.join(self._project_root, self._tag)
                resolved = integrity.latest_valid(base)
                if resolved is None:
                    self._logger.info(
                        "resume('auto'): no valid snapshot under %s — "
                        "starting fresh", base,
                    )
            else:
                resolved = integrity.resolve_restore_path(path)
                failed = resolved is None
        resolved, failed = multihost.broadcast_object((resolved, failed))
        if failed:
            raise RuntimeError(
                f"resume: no valid snapshot at {path} and no verified "
                f"fallback beside it (quarantined dirs are *.corrupt)"
            )
        if resolved is not None and resolved != path:
            self._logger.warning("resume: restoring from %s", resolved)
        return resolved

    def destroy(self, attrs: Optional[Attributes] = None) -> None:
        super().destroy(attrs)
        if self._runtime is not None:
            self._runtime.end_training()
        from rocket_tpu.persist.orbax_io import default_io

        default_io().wait()  # drain any in-flight async checkpoint

    # -- resume --------------------------------------------------------------

    def resume(self, path: str, load_capsules: bool = True) -> "Launcher":
        """Arm a checkpoint restore for the next ``launch()`` (reference
        ``launcher.py:377-408``). ``load_capsules=False`` = weights only."""
        self._resume_path = str(path)
        self._resume_load_capsules = bool(load_capsules)
        return self

    def _resume(self, attrs: Attributes) -> None:
        """Restore host-side capsule states right after setup (reference
        ``launcher.py:319-375``).  Array states (Module) restore lazily at
        materialization via ``runtime.resume_spec`` — sharded, direct to
        mesh."""
        if self._resume_path is None:
            return
        spec = getattr(self._runtime, "resume_spec", None)
        if spec is None:
            return  # resume('auto') with nothing on disk — fresh start
        from rocket_tpu.observe.ledger import get_goodput
        from rocket_tpu.persist import integrity
        from rocket_tpu.persist.orbax_io import default_io

        # Restore time is checkpoint-bucket time; a restart that replays
        # steps additionally reports into preemption_loss via
        # GoodputLedger.note_preemption_loss (the replay estimate lives
        # with whoever knows the step cadence, not here).
        with get_goodput().timed("checkpoint"):
            self._resume_inner(spec, integrity, default_io())

    def _resume_inner(self, spec: Any, integrity: Any, io: Any) -> None:
        # The VERIFIED path from _resolve_resume_path — not the raw request
        # ('auto', or a corrupt dir that fell back to a sibling).
        path = str(spec.path)
        # Elastic restore (ISSUE 8): a mesh-stamped snapshot may restore
        # onto a different topology — the Modules derive CURRENT-mesh
        # target shardings at materialization and orbax reshards in
        # transit; the topology guard below relaxes to a logged
        # transition.  Legacy (unstamped) snapshots keep the strict guard.
        self._saved_mesh = integrity.manifest_mesh(path)
        self._log_mesh_transition(self._saved_mesh, path)
        available = set(io.keys(path))
        if not self._resume_load_capsules:
            # Weights-only: leave resume_spec armed for Modules, skip the
            # host states (reference ``launcher.py:349-359``) — but the
            # topology guard applies to BOTH resume paths (reference
            # ``launcher.py:370-375``): arrays saved by a different
            # process count are still an elastic resume.  Peek at the
            # saved launcher state without adopting its epoch counter.
            if self._ckpt_key is not None and self._ckpt_key in available:
                saved = Attributes(io.restore_item(path, self._ckpt_key))
                self._check_resume_topology(
                    saved.get("num_procs"), ", weights-only included"
                )
            self._logger.info("weights-only resume from %s", path)
            return
        for capsule in self._runtime.checkpointables:
            key = capsule._ckpt_key
            if key is None or getattr(capsule, "lazy_state", False):
                continue  # lazy array state restores at materialization
            if key not in available:
                raise RuntimeError(
                    f"checkpoint {path} has no item {key!r} — was it saved "
                    f"from a different capsule tree? (reference guard, "
                    f"launcher.py:364-369)"
                )
            state = io.restore_item(path, key)
            capsule.load_state_dict(Attributes(state))
        self._check_resume_topology(self._saved_num_procs)
        self._logger.info(
            "resumed from %s at epoch %d", path, self._epoch_idx
        )

    def _check_resume_topology(
        self, saved_procs: Optional[int], qualifier: str = ""
    ) -> None:
        """Topology guard, shared by both resume paths (reference
        ``launcher.py:370-375``).

        Mesh-stamped snapshots (manifest schema >= 2, ISSUE 8) carry
        enough layout metadata to reshard on restore, so a process-count
        change is an *elastic* resume: logged, not fatal — the real
        legality check is per-leaf in ``integrity.check_reshard`` at
        restore time.  Legacy snapshots (no ``mesh`` section) keep the
        strict guard: without the saved layout we cannot prove the
        reshard is sound.
        """
        if (
            saved_procs is None
            or int(saved_procs) == self._runtime.process_count
        ):
            return
        if self._saved_mesh is not None:
            self._logger.warning(
                "elastic resume%s: checkpoint written by %d processes "
                "(%d devices, axes %s), this run has %d processes — "
                "arrays reshard onto the current mesh at restore",
                qualifier,
                int(saved_procs),
                self._saved_mesh.get("device_count", -1),
                self._saved_mesh.get("axes", {}),
                self._runtime.process_count,
            )
            return
        raise RuntimeError(
            f"resume topology mismatch: checkpoint was written by "
            f"{int(saved_procs)} processes, this run has "
            f"{self._runtime.process_count}. Elastic resume is not "
            f"supported{qualifier} for snapshots without a manifest "
            f"mesh section (re-save with this version to stamp one; "
            f"reference launcher.py:370-375)."
        )

    def _log_mesh_transition(
        self, mesh_meta: Optional[dict], path: str
    ) -> None:
        """Announce a cross-mesh restore (saved axes != current mesh) so
        an elastic transition is visible in the run log."""
        if mesh_meta is None:
            return
        mesh = getattr(self._runtime, "mesh", None)
        if mesh is None:
            return
        current = {str(k): int(v) for k, v in dict(mesh.shape).items()}
        saved = {
            str(k): int(v) for k, v in (mesh_meta.get("axes") or {}).items()
        }
        if saved and saved != current:
            self._logger.warning(
                "elastic restore from %s: saved mesh %s (%s devices) -> "
                "current mesh %s (%s devices)",
                path,
                saved,
                mesh_meta.get("device_count", "?"),
                current,
                mesh.devices.size,
            )

    # -- the run -------------------------------------------------------------

    def launch(self, attrs: Optional[Attributes] = None) -> None:
        """The whole program (reference ``launcher.py:256-291``).

        Notebook sugar (reference ``@notebook``, ``launcher.py:202-247``):
        inside a Jupyter kernel, a plain ``launch()`` that requests more
        processes than exist (``attrs.launcher.num_procs``) reroutes
        itself through :func:`~rocket_tpu.launch.notebook.notebook_launch`
        — each forked worker rendezvouses and re-enters ``launch``.
        """
        attrs = attrs if attrs is not None else Attributes()
        requested = (
            attrs.launcher.num_procs if attrs.launcher is not None else None
        )
        if requested is not None and int(requested) > 1:
            from rocket_tpu.launch import notebook

            # NB: the guard must not call process_count() — that would
            # initialize a jax backend in the notebook parent, which the
            # forked workers would inherit broken.  A worker re-entering
            # launch() is recognized by multihost.is_initialized().
            if notebook.in_notebook() and not multihost.is_initialized():
                n = int(requested)
                self._logger.info(
                    "notebook detected: rerouting launch through "
                    "notebook_launch(num_processes=%d)", n,
                )
                # Workers rebuild attrs.launcher post-rendezvous (where
                # multihost is initialized, so this branch cannot
                # re-trigger).  Hand them a COPY without the launcher
                # request: notebook_launch can raise, and a retried
                # launch(attrs) must still see the caller's num_procs.
                worker_attrs = Attributes(attrs)
                del worker_attrs.launcher
                notebook.notebook_launch(
                    self.launch, args=(worker_attrs,), num_processes=n
                )
                return
        attrs.launcher = Attributes(
            num_procs=multihost.process_count(),
            num_nodes=multihost.process_count(),  # one process per TPU host
            epoch_idx=0,
        )
        self.setup(attrs)
        try:
            self._resume(attrs)
            stopped = False
            for epoch in range(self._epoch_idx, self._num_epochs):
                # Run-level stop vote (preemption snapshot written, sentinel
                # abort): honored BETWEEN cycles too, where no attrs.looper
                # exists to carry a terminate vote — without this check a
                # SIGTERM landing between cycles would start the next epoch
                # and blow the grace window (ISSUE 2 satellite).
                if self._runtime.stop_training:
                    stopped = True
                    break
                self._epoch_idx = epoch
                attrs.launcher.epoch_idx = epoch
                for capsule in self._capsules:
                    self._event(capsule, "set", attrs)
                    self._event(capsule, "launch", attrs)
                    self._event(capsule, "reset", attrs)
                    if self._runtime.stop_training:
                        break  # skip sibling cycles; exit within the grace window
            if self._runtime.stop_training:
                stopped = True
                self._logger.warning(
                    "run stopped early at epoch %d: %s",
                    self._epoch_idx, self._runtime.stop_reason or "stop vote",
                )
            if not stopped:
                self._epoch_idx = self._num_epochs
        except Exception:
            # Unhandled launch exception: the flight recorder's last-N
            # window IS the post-mortem — dump before teardown can run
            # (destroy may raise again or block on checkpoint drain).
            self._dump_flight_recorder("exception")
            raise
        finally:
            del attrs.launcher
            self._finish_goodput()
            self.destroy(attrs)

    def _finish_goodput(self) -> None:
        """Close the goodput window, persist ``<project>/goodput.json``
        (main process), log the bucket table, stop the metrics endpoint.
        Never raises — run teardown must proceed regardless."""
        if not self._goodput:
            return
        try:
            from rocket_tpu.observe.ledger import disarm_ledgers, get_goodput

            goodput = get_goodput()
            disarm_ledgers()  # freezes the window; snapshot stays valid
            runtime = self._runtime
            if (
                runtime is not None
                and runtime.project_dir is not None
                and runtime.is_main_process
            ):
                path = os.path.join(runtime.project_dir, "goodput.json")
                goodput.save(path)
                self._logger.info("goodput ledger -> %s", path)
            for line in goodput.table().splitlines():
                self._logger.info("%s", line)
        except Exception:
            self._logger.warning("goodput finalization failed",
                                 exc_info=True)
        finally:
            if self._metrics_server is not None:
                try:
                    self._metrics_server.stop()
                except Exception:
                    pass
                self._metrics_server = None

    def _dump_flight_recorder(self, reason: str) -> None:
        from rocket_tpu.observe.recorder import active_recorder

        rec = active_recorder()
        if rec is None:
            return
        try:
            rec.dump(reason)
        except Exception:  # a failing dump must not mask the real error
            self._logger.warning("flight recorder dump failed", exc_info=True)

    # -- state ---------------------------------------------------------------

    _saved_num_procs: Optional[int] = None
    # The resumed snapshot's manifest "mesh" section (None = legacy
    # snapshot, strict topology guard).
    _saved_mesh: Optional[dict] = None

    def state_dict(self) -> Attributes:
        # The running epoch: resume re-enters it, and the Dataset's
        # batch_idx fast-forwards to the intra-epoch position (reference
        # ``launcher.py:410-425`` + ``dataset.py:205-210``).
        return Attributes(
            epoch_idx=self._epoch_idx,
            num_procs=multihost.process_count(),
            num_nodes=multihost.process_count(),
        )

    def load_state_dict(self, state: Attributes) -> None:
        if not state:
            return
        # Schema-tolerant: a checkpoint from an older schema warns and
        # defaults instead of KeyError-ing the whole resume (ISSUE 2
        # satellite).  num_procs=None simply skips the topology guard.
        epoch = state.get("epoch_idx")
        if epoch is None:
            self._logger.warning(
                "checkpoint has no 'epoch_idx' (older schema?) — resuming "
                "at epoch 0"
            )
            epoch = 0
        self._epoch_idx = int(epoch)
        procs = state.get("num_procs")
        if procs is None:
            self._logger.warning(
                "checkpoint has no 'num_procs' — skipping the resume "
                "topology guard"
            )
        self._saved_num_procs = int(procs) if procs is not None else None
