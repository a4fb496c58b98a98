"""ZeRO stages 2/3 + host-offloaded optimizer state (ISSUE 20).

Covers the Module-level integration of the extended sharding engine:
the typed ``ZeroIncompatibleError`` matrix (each genuinely incompatible
combination names its remedy), ``memory_plan()``'s host-tier accounting
under ``zero_offload``, the :class:`~rocket_tpu.engine.offload
.ZeroOffloader` round trip (bitwise exact, overlap-armed vs serialized,
``offload_wait`` goodput booking), bit-equality of an offloaded run
against the same run without offload, and the zero-new-jit-traces
contract of the offload path (``jax.device_get``/``device_put`` are not
jit sites).

Spec-level stage-2/3 coverage (zero_compose trees, zoo lint, oracle
bit-equality) lives in tests/test_sharding_rules.py; elastic restore
across stage transitions in tests/test_elastic.py.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import rocket_tpu as rt
from rocket_tpu.engine.offload import ZeroOffloader
from rocket_tpu.engine.state import TrainState, memory_plan
from rocket_tpu.models.objectives import cross_entropy
from rocket_tpu.observe.ledger import GoodputLedger, get_goodput
from rocket_tpu.parallel.mesh import MeshSpec
from rocket_tpu.parallel.sharding import (
    ZERO_STAGES,
    ZeroIncompatibleError,
    specs_for_state,
)

from test_pipeline import MLP, synthetic_classification


def _module(runtime, fuse=False):
    model = rt.Module(
        MLP(),
        capsules=[
            rt.Loss(cross_entropy(labels_key="label"), name="ce"),
            rt.Optimizer(learning_rate=2e-2),
        ],
        fuse_accumulation=fuse,
    )
    model.bind(runtime)
    model.setup()
    return model


def _run_steps(runtime, steps_n=6, batch_size=64):
    """Drive a Module directly for ``steps_n`` sync steps; returns the
    model and the per-step loss list."""
    data = synthetic_classification(n=256)
    model = _module(runtime)
    losses = []
    for i in range(steps_n):
        lo = (i * batch_size) % 256
        batch = {
            "x": jnp.asarray(data["x"][lo:lo + batch_size]),
            "label": jnp.asarray(data["label"][lo:lo + batch_size]),
        }
        attrs = rt.Attributes(
            batch=batch,
            looper=rt.Attributes(grad_enabled=True, state=rt.Attributes()),
        )
        model.launch(attrs)
        losses.append(float(attrs.step_logs["loss"]))
    return model, losses


# -- typed incompatibility matrix --------------------------------------------


class TestIncompatibilityMatrix:
    """Satellite 1: every refused combination raises ONE typed error
    carrying the feature, the stage, and the remedy — asserted on the
    error's fields, not a bare message match."""

    def test_runtime_accepts_all_stages(self, devices):
        for stage in ZERO_STAGES:
            runtime = rt.Runtime(
                mesh=MeshSpec(data=8).build(devices), zero_stage=stage
            )
            assert runtime.zero_stage == stage

    def test_runtime_rejects_unknown_stage(self, devices):
        with pytest.raises(ValueError, match="zero_stage"):
            rt.Runtime(mesh=MeshSpec(data=8).build(devices), zero_stage=4)

    def test_offload_requires_sharded_opt_state(self, devices):
        with pytest.raises(ZeroIncompatibleError) as exc_info:
            rt.Runtime(
                mesh=MeshSpec(data=8).build(devices), zero_offload=True
            )
        err = exc_info.value
        assert err.feature == "zero_offload"
        assert err.zero_stage == 0
        assert "zero_stage >= 1" in err.remedy
        assert "Remedy" in str(err)

    @pytest.mark.parametrize("stage", [1, 2, 3])
    def test_fuse_accumulation_refused_per_stage(self, devices, stage):
        runtime = rt.Runtime(
            mesh=MeshSpec(data=8).build(devices),
            gradient_accumulation_steps=2,
            zero_stage=stage,
        )
        model = _module(runtime, fuse=True)
        data = synthetic_classification(n=64)
        batch = {
            "x": jnp.asarray(data["x"]),
            "label": jnp.asarray(data["label"]),
        }
        with pytest.raises(ZeroIncompatibleError) as exc_info:
            model.materialize(batch)
        err = exc_info.value
        assert err.feature == "fuse_accumulation"
        assert err.zero_stage == stage
        assert "micro/sync" in err.remedy

    def test_fuse_accumulation_fine_at_stage0(self, devices):
        runtime = rt.Runtime(
            mesh=MeshSpec(data=8).build(devices),
            gradient_accumulation_steps=2,
        )
        model = _module(runtime, fuse=True)
        data = synthetic_classification(n=64)
        model.materialize({
            "x": jnp.asarray(data["x"]),
            "label": jnp.asarray(data["label"]),
        })
        assert "window" in model._steps

    def test_error_is_a_value_error(self):
        # callers that guarded the old bare ValueError keep working
        assert issubclass(ZeroIncompatibleError, ValueError)


# -- memory accounting --------------------------------------------------------


class TestOffloadMemoryPlan:
    def _plan(self, devices, zero_stage):
        mesh = MeshSpec(data=8).build(devices)
        params = {"w": jnp.zeros((64, 128)), "b": jnp.zeros((64,))}
        tx = optax.adamw(1e-2)
        abstract = jax.eval_shape(lambda: TrainState.create(params, tx))
        pspecs = {"w": P(), "b": P()}
        plan = specs_for_state(
            mesh, abstract, param_specs=pspecs, zero_stage=zero_stage)
        return abstract, plan, mesh

    def test_offload_moves_opt_bytes_to_host_tier(self, devices):
        abstract, plan, mesh = self._plan(devices, zero_stage=1)
        on_dev = memory_plan(abstract, plan.state_specs, mesh)
        off = memory_plan(
            abstract, plan.state_specs, mesh, zero_offload=True)
        assert on_dev["opt_bytes"] > 0
        assert on_dev["host_opt_bytes"] == 0
        assert off["opt_bytes"] == 0
        assert off["host_opt_bytes"] == on_dev["opt_bytes"]
        assert off["total_bytes"] == (
            on_dev["total_bytes"] - on_dev["opt_bytes"]
        )
        assert off["param_bytes"] == on_dev["param_bytes"]

    def test_module_memory_plan_reflects_runtime_offload(self, devices):
        runtime = rt.Runtime(
            mesh=MeshSpec(data=8).build(devices),
            zero_stage=1, zero_offload=True,
        )
        model, _ = _run_steps(runtime, steps_n=1)
        mem = model.memory_plan()
        assert mem["opt_bytes"] == 0
        assert mem["host_opt_bytes"] > 0
        model.destroy()


# -- the offloader ------------------------------------------------------------


class TestZeroOffloader:
    def _tree(self, devices, n=1024):
        mesh = MeshSpec(data=8).build(devices)
        sh = NamedSharding(mesh, P())
        key = jax.random.PRNGKey(3)
        tree = {
            "mu": jax.device_put(
                jax.random.normal(key, (n,), jnp.float32), sh),
            "nu": jax.device_put(
                jax.random.uniform(key, (n,), jnp.float32), sh),
        }
        shardings = {"mu": sh, "nu": sh}
        return tree, shardings

    def test_goodput_ledger_has_offload_wait_bucket(self):
        assert "offload_wait" in GoodputLedger.BUCKETS
        assert "offload_wait" in GoodputLedger.NESTED

    def test_fetch_without_stash_returns_fallback(self, devices):
        tree, shardings = self._tree(devices)
        off = ZeroOffloader(shardings)
        try:
            assert off.fetch(tree) is tree
            assert off.rounds == 0
        finally:
            off.close()

    @pytest.mark.parametrize("synchronous", [False, True])
    def test_round_trip_is_bitwise_exact(self, devices, synchronous):
        tree, shardings = self._tree(devices)
        off = ZeroOffloader(shardings, synchronous=synchronous)
        try:
            off.stash(tree)
            out = off.fetch(None)
            assert out is not None and out is not tree
            for a, b in zip(jax.tree_util.tree_leaves(tree),
                            jax.tree_util.tree_leaves(out)):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b))
            assert out["mu"].sharding == shardings["mu"]
            assert off.rounds == 1
        finally:
            off.close()

    def test_double_stash_refused(self, devices):
        tree, shardings = self._tree(devices)
        off = ZeroOffloader(shardings)
        try:
            off.stash(tree)
            with pytest.raises(RuntimeError, match="in flight"):
                off.stash(tree)
        finally:
            off.close()

    def test_wait_booked_into_offload_wait_bucket(self, devices):
        tree, shardings = self._tree(devices)
        goodput = get_goodput()
        goodput.start_run()
        try:
            off = ZeroOffloader(shardings, synchronous=True)
            off.stash(tree)
            off.fetch(None)
            off.close()
            assert goodput._buckets["offload_wait"] > 0.0
        finally:
            goodput.end_run()
            goodput.armed = False

    def test_armed_prefetch_runs_beside_the_caller(self, devices):
        """THE overlap acceptance, in counts (it was "armed step wall <
        synchronous step wall" around a sleep): the armed round trip runs
        to its end on the worker thread while the caller does something
        else — both transfers are recorded before ``fetch`` is called, by
        another thread — where the synchronous one has moved nothing by
        then and moves everything inside ``fetch``, on the caller's."""
        import threading

        from rocket_tpu.observe.trace import arm, disarm, get_tracer

        tree, shardings = self._tree(devices, n=4 << 20)  # 2 x 16 MB
        me = threading.get_ident()

        def transfers():
            return [(e[1], e[4]) for e in get_tracer().events()
                    if e[1] in ("offload/d2h", "offload/h2d")]

        arm()
        try:
            get_tracer().clear()
            sync = ZeroOffloader(shardings, synchronous=True)
            sync.stash(tree)
            assert transfers() == []        # nothing moves until fetch
            sync.fetch(None)
            sync.close()
            assert transfers() == [("offload/d2h", me), ("offload/h2d", me)]

            get_tracer().clear()
            armed = ZeroOffloader(shardings)
            try:
                armed.stash(tree)
                # the caller's "compute": anything but fetch.  The clock
                # only bounds the wait.
                deadline = time.monotonic() + 60.0
                while armed._ready.empty() and time.monotonic() < deadline:
                    time.sleep(0.005)
                before_fetch = transfers()
                armed.fetch(None)
            finally:
                armed.close()
            assert [name for name, _ in before_fetch] \
                == ["offload/d2h", "offload/h2d"]
            assert all(tid != me for _, tid in before_fetch)
            assert transfers() == before_fetch  # fetch moved nothing more
        finally:
            disarm()
            get_tracer().clear()


# -- module integration -------------------------------------------------------


class TestModuleOffload:
    @pytest.mark.parametrize("stage", [1, 3])
    def test_offload_bitwise_equals_no_offload(self, devices, stage):
        """The host round trip is a pure memcpy pair: training with
        zero_offload must match the same sharded run without it bit for
        bit (losses, params, opt state)."""
        runtime = rt.Runtime(
            mesh=MeshSpec(data=8).build(devices), zero_stage=stage)
        model_a, losses_a = _run_steps(runtime, steps_n=6)
        runtime_b = rt.Runtime(
            mesh=MeshSpec(data=8).build(devices),
            zero_stage=stage, zero_offload=True,
        )
        model_b, losses_b = _run_steps(runtime_b, steps_n=6)
        assert losses_a == losses_b
        for a, b in zip(
            jax.tree_util.tree_leaves(jax.device_get(model_a.state.params)),
            jax.tree_util.tree_leaves(jax.device_get(model_b.state.params)),
        ):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(
            jax.tree_util.tree_leaves(
                jax.device_get(model_a.state.opt_state)),
            jax.tree_util.tree_leaves(
                jax.device_get(model_b.state.opt_state)),
        ):
            np.testing.assert_array_equal(a, b)
        # the offloader actually ran round trips (one per joined boundary)
        assert model_b._offloader is not None
        assert model_b._offloader.rounds >= 4
        model_a.destroy()
        model_b.destroy()
        assert model_b._offloader is None

    def test_offload_zero_new_traces_per_step(self, devices):
        """device_get/device_put are not jit sites: after the 2-step
        warmup (first output normalizes shardings) the sync step's trace
        count must not grow, offload armed or not."""
        def trace_counts(zero_offload):
            runtime = rt.Runtime(
                mesh=MeshSpec(data=8).build(devices),
                zero_stage=2, zero_offload=zero_offload,
            )
            model, _ = _run_steps(runtime, steps_n=2)
            warm = model._steps["sync"]._cache_size()
            model_steps = model
            data = synthetic_classification(n=256)
            for i in range(5):
                lo = (i * 64) % 256
                attrs = rt.Attributes(
                    batch={
                        "x": jnp.asarray(data["x"][lo:lo + 64]),
                        "label": jnp.asarray(data["label"][lo:lo + 64]),
                    },
                    looper=rt.Attributes(
                        grad_enabled=True, state=rt.Attributes()),
                )
                model_steps.launch(attrs)
            final = model._steps["sync"]._cache_size()
            model.destroy()
            return warm, final

        base_warm, base_final = trace_counts(zero_offload=False)
        off_warm, off_final = trace_counts(zero_offload=True)
        assert off_final == off_warm, "offload retraces per step"
        # The prefetch's H2D re-pin lands opt state back on the PLAN's
        # shardings every step, so the offloaded loop can only ever see
        # fewer signatures than the baseline (whose first output pays
        # one XLA sharding-normalization retrace) — never more.
        assert off_final <= base_final, (
            f"offload traced {off_final}x vs baseline {base_final}x"
        )
