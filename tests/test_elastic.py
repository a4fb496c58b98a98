"""Elastic restore + preemption-grade persistence (ISSUE 8 acceptance).

The trajectory that must hold end-to-end: kill a 4-device run mid-epoch,
``resume("auto")`` on 2 devices, kill again, resume on all 8 — and the
stitched loss trajectory plus final params match the uninterrupted run.
Alongside it:

- the manifest's ``mesh`` section records the saving topology and
  :func:`~rocket_tpu.persist.integrity.check_reshard` raises a typed
  :class:`~rocket_tpu.persist.integrity.TopologyMismatch` (leaf path +
  remedy) for illegal cross-mesh restores;
- the emergency tier bounds hard-preemption loss to ≤1 step when the
  durable cadence is stale;
- snapshot election orders on (iter, mtime), not directory name;
- ``tree_shardings`` errors name the offending leaf;
- the SIGTERM handler chain layers deterministically (recorder dump →
  emergency flush → previous handler) and is re-entrancy-safe.
"""

import json
import os
import signal

import numpy as np
import pytest

import rocket_tpu as rt
from rocket_tpu.models.objectives import cross_entropy
from rocket_tpu.parallel.mesh import MeshSpec
from rocket_tpu.parallel.sharding import ShardingRules, tree_shardings
from rocket_tpu.persist import emergency, integrity
from rocket_tpu.persist.integrity import TopologyMismatch
from rocket_tpu.testing import (
    HardPreemptionInjector,
    SigtermInjector,
    SimulatedKill,
)

from test_pipeline import MLP, synthetic_classification
from test_resilience import LossRecorder

pytestmark = [pytest.mark.resilience, pytest.mark.elastic]


def _mesh(n):
    import jax

    return MeshSpec(data=n).build(jax.devices()[:n])


def _tree(tmp_path, data, *, tag, epochs, mesh=None, extra=(),
          save_every=100, emergency_every=None, resume=None, seed=0,
          zero_stage=0):
    """The chaos tree of test_resilience, parameterized by mesh: 256
    samples / batch 64 = 4 iterations per epoch on any device count."""
    model = rt.Module(
        MLP(),
        capsules=[
            rt.Loss(cross_entropy(labels_key="label"), name="ce"),
            rt.Optimizer(learning_rate=2e-2),
        ],
    )
    recorder = LossRecorder()
    looper = rt.Looper(
        capsules=[
            rt.Dataset(rt.ArraySource(data), batch_size=64, shuffle=True,
                       seed=7),
            model,
            *extra,
            recorder,
            rt.Checkpointer(save_every=save_every,
                            emergency_every=emergency_every),
        ],
        progress=False,
    )
    launcher = rt.Launcher(
        capsules=[looper], tag=tag, num_epochs=epochs, mesh=mesh,
        project_root=str(tmp_path), seed=seed, zero_stage=zero_stage,
    )
    if resume is not None:
        launcher.resume(resume)
    return launcher, model, recorder


def _flat(params):
    import jax

    return np.concatenate([
        np.ravel(np.asarray(x)) for x in jax.tree_util.tree_leaves(params)
    ])


# -- the acceptance trajectory: 4 devices -> kill -> 2 -> kill -> 8 ----------


def test_kill_on_4_resume_on_2_then_8_matches_uninterrupted(tmp_path,
                                                            devices):
    """THE elastic acceptance test: SIGTERM a 4-device run mid-epoch,
    resume("auto") the same tag on 2 devices, SIGTERM again, finish on all
    8 — stitched losses and final params match the uninterrupted run."""
    data = synthetic_classification(n=256)

    launcher_a, model_a, rec_a = _tree(tmp_path, data, tag="eref", epochs=2)
    launcher_a.launch()
    assert len(rec_a.losses) == 8

    # Stage 1: 4 devices, preempted at iteration 2 of epoch 0.
    launcher_b, model_b, rec_b = _tree(
        tmp_path, data, tag="elastic", epochs=2, mesh=_mesh(4),
        extra=[SigtermInjector(at_iter=2)],
    )
    launcher_b.launch()
    assert len(rec_b.losses) == 3
    snap = tmp_path / "elastic" / "v0" / "weights" / "000002"
    assert snap.is_dir()
    # the snapshot is stamped with its saving topology
    mesh_meta = integrity.manifest_mesh(str(snap))
    assert mesh_meta is not None
    assert mesh_meta["device_count"] == 4
    assert mesh_meta["axes"]["data"] == 4
    assert any(name == "batch" for name, _ in mesh_meta["rules"])

    # Stage 2: shrink to 2 devices, preempted again.  A resumed mid-epoch
    # cycle runs one extra no-step iteration when the dataset exhausts
    # (loop.py clears step_logs for it), and that call still ticks the
    # injector — so at_iter=2 lands on global step 4, after steps 3-4.
    launcher_c, model_c, rec_c = _tree(
        tmp_path, data, tag="elastic", epochs=2, mesh=_mesh(2),
        extra=[SigtermInjector(at_iter=2)], resume="auto",
    )
    launcher_c.launch()
    assert len(rec_c.losses) == 2  # global iters 3, 4
    snap_c = tmp_path / "elastic" / "v1" / "weights" / "000005"
    assert snap_c.is_dir()
    assert integrity.manifest_mesh(str(snap_c))["device_count"] == 2

    # Stage 3: grow to all 8 devices, run to completion (global 5, 6, 7).
    launcher_d, model_d, rec_d = _tree(
        tmp_path, data, tag="elastic", epochs=2, mesh=_mesh(8),
        resume="auto",
    )
    launcher_d.launch()
    assert len(rec_d.losses) == 3

    stitched = rec_b.losses + rec_c.losses + rec_d.losses
    assert len(stitched) == 8
    np.testing.assert_allclose(stitched, rec_a.losses, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        _flat(model_d.state.params), _flat(model_a.state.params),
        rtol=1e-5, atol=1e-6,
    )


def test_weights_only_resume_across_meshes(tmp_path, devices):
    """Weights saved on 4 devices seed a fresh 8-device run (and the
    other direction) without tripping the legacy topology guard."""
    data = synthetic_classification(n=256)
    launcher, model, _ = _tree(tmp_path, data, tag="wo", epochs=1,
                               mesh=_mesh(4), save_every=4)
    launcher.launch()
    snap = str(tmp_path / "wo" / "v0" / "weights" / "000003")

    launcher2, model2, rec2 = _tree(tmp_path, data, tag="wo", epochs=1,
                                    mesh=_mesh(8))
    launcher2.resume(snap, load_capsules=False)
    launcher2.launch()
    assert len(rec2.losses) == 4  # fresh run, full epoch
    # step counter fresh (weights-only), but weights came from the snapshot
    assert int(model2.state.step) == 4


# -- emergency tier: ≤1 step lost on a hard preemption -----------------------


def test_hard_preemption_emergency_bounds_loss_to_one_step(tmp_path,
                                                           devices):
    """With the durable cadence deliberately stale (save_every=100) and
    the emergency tier armed, a HARD preemption (no grace window) at
    iteration 5 leaves an emergency snapshot of iteration 4 — resume loses
    exactly the killed step, not the whole run."""
    data = synthetic_classification(n=256)

    launcher_a, model_a, rec_a = _tree(tmp_path, data, tag="href", epochs=2)
    launcher_a.launch()

    launcher_b, model_b, rec_b = _tree(
        tmp_path, data, tag="hard", epochs=2, emergency_every=1,
        extra=[HardPreemptionInjector(at_iter=5)],
    )
    with pytest.raises(SimulatedKill):
        launcher_b.launch()
    # The recorder (priority 400) runs before the injector (150), so iter
    # 5's step ran and its loss was recorded — but its update is lost: the
    # Checkpointer (100) never got to capture it, leaving iter 4 as the
    # freshest emergency snapshot.
    assert len(rec_b.losses) == 6
    edir = tmp_path / "hard" / "v0" / "emergency"
    snaps = sorted(edir.iterdir())
    assert [s.name for s in snaps] == ["000004"]
    assert (snaps[0] / integrity.EMERGENCY_MARKER).is_file()
    ok, reason = integrity.verify(str(snaps[0]))
    assert ok, reason
    # no durable grace-window snapshot was written (cadence 100 never hit)
    assert not (tmp_path / "hard" / "v0" / "weights").exists()

    # resume("auto") elects the emergency snapshot and replays from there:
    # global iters 5, 6, 7 remain.
    launcher_c, model_c, rec_c = _tree(tmp_path, data, tag="hard", epochs=2,
                                       resume="auto")
    launcher_c.launch()
    assert len(rec_c.losses) == 3  # exactly one step was lost and replayed
    # the killed step is replayed exactly once, bit-for-bit deterministic
    np.testing.assert_allclose(rec_b.losses[5], rec_c.losses[0], rtol=1e-6)
    stitched = rec_b.losses[:5] + rec_c.losses
    np.testing.assert_allclose(stitched, rec_a.losses, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(
        _flat(model_c.state.params), _flat(model_a.state.params),
        rtol=1e-5, atol=1e-7,
    )


def test_durable_snapshot_newer_than_emergency_wins(tmp_path, devices):
    """The (iter, mtime) election prefers whichever tier is NEWER: a
    polite preemption's grace-window durable save outranks the staled
    emergency flush of an earlier iteration."""
    data = synthetic_classification(n=256)
    launcher, _, _ = _tree(
        tmp_path, data, tag="newer", epochs=2, emergency_every=1,
        save_every=2, extra=[SigtermInjector(at_iter=2)],
    )
    launcher.launch()
    root = str(tmp_path / "newer")
    best = integrity.latest_valid(root, do_quarantine=False)
    # the grace-window durable snapshot (iter 2) wins; any emergency
    # capture was discarded/superseded by it
    assert best is not None and "weights" in best
    assert best.endswith("000002")


# -- manifest mesh section + check_reshard -----------------------------------


def _manifest_for(arrays, mesh, rules=None, **kw):
    return integrity.build_manifest(
        {"module_0": {"state": arrays}}, mesh=mesh, rules=rules, **kw
    )


def test_manifest_mesh_section_schema(tmp_path, devices):
    import jax

    mesh = _mesh(4)
    manifest = _manifest_for(
        {"w": np.zeros((8, 4), np.float32)}, mesh, ShardingRules(),
        iter_idx=3,
    )
    assert manifest["schema"] == integrity.SCHEMA_VERSION
    section = manifest["mesh"]
    assert section["device_count"] == 4
    assert section["axes"] == {"data": 4, "pipe": 1, "fsdp": 1,
                               "expert": 1, "seq": 1, "tensor": 1}
    rules = dict((name, axes) for name, axes in section["rules"])
    assert rules["embed"] == "fsdp"
    # per-leaf records carry the saved spec slot (None for host leaves)
    rec = manifest["items"]["module_0"]["structure"][0]
    assert "spec" in rec
    # the whole thing must survive a JSON round-trip (manifest.json)
    assert json.loads(json.dumps(manifest)) == manifest


def test_check_reshard_shape_mismatch_is_model_change(devices):
    import jax

    mesh = _mesh(2)
    manifest = _manifest_for({"w": np.zeros((8, 4), np.float32)}, mesh)
    target = {"state": {"w": jax.ShapeDtypeStruct(
        (16, 4), np.float32,
        sharding=jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()),
    )}}
    with pytest.raises(TopologyMismatch, match=r"w.*model change"):
        integrity.check_reshard(manifest, {"module_0": target})


class _TargetLeaf:
    """A hand-built restore target's leaf: the three attributes
    ``check_reshard`` reads.  ``jax.ShapeDtypeStruct`` cannot stand in for
    the two illegal cases below — since it validates its sharding at
    construction it refuses both before ``check_reshard`` is asked."""

    def __init__(self, shape, dtype, sharding):
        self.shape, self.dtype, self.sharding = shape, dtype, sharding


def test_check_reshard_missing_axis_names_leaf_and_remedy(devices):
    import jax

    mesh = _mesh(2)
    manifest = _manifest_for({"w": np.zeros((8, 4), np.float32)}, mesh)

    class FakeSharding:
        """A sharding whose spec names an axis its mesh lacks — the state
        a hand-built restore target can reach (NamedSharding validates at
        construction, so fake the duck type)."""

        def __init__(self, mesh, spec):
            self.mesh, self.spec = mesh, spec

    leaf = _TargetLeaf((8, 4), np.float32, FakeSharding(
        mesh, jax.sharding.PartitionSpec("bogus")))
    with pytest.raises(TopologyMismatch, match=r"w.*'bogus'.*size 1 is"):
        integrity.check_reshard(manifest, {"module_0": {"state": {"w": leaf}}})


def test_check_reshard_rank_overflow(devices):
    import jax

    mesh = _mesh(2)
    manifest = _manifest_for({"w": np.zeros((8,), np.float32)}, mesh)
    # a spec the mesh accepts, with one entry more than the leaf has
    # dimensions
    leaf = _TargetLeaf((8,), np.float32, jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(None, "data")))
    with pytest.raises(TopologyMismatch, match=r"w.*rank-1"):
        integrity.check_reshard(manifest, {"module_0": {"state": {"w": leaf}}})


def test_check_reshard_uneven_division_is_legal(devices):
    """GSPMD pads ragged shards: dim 6 over a 4-way axis must NOT raise."""
    import jax

    mesh = _mesh(4)
    manifest = _manifest_for({"w": np.zeros((6, 4), np.float32)}, mesh)
    target = {"state": {"w": jax.ShapeDtypeStruct(
        (6, 4), np.float32,
        sharding=jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("data", None)),
    )}}
    integrity.check_reshard(manifest, {"module_0": target})  # no raise


# -- (iter, mtime) snapshot election -----------------------------------------


def _fake_snapshot(path, iter_idx, mtime=None):
    """A minimal committed snapshot dir that passes shallow verify."""
    os.makedirs(os.path.join(path, "module_0"), exist_ok=True)
    manifest = integrity.build_manifest(
        {"module_0": {"w": np.zeros((2,), np.float32)}}, iter_idx=iter_idx,
    )
    integrity.write_manifest(path, manifest)
    integrity.write_commit_marker(path)
    if mtime is not None:
        os.utime(path, (mtime, mtime))


def test_latest_valid_orders_on_iter_not_dirname(tmp_path):
    """Regression (ISSUE 8 satellite): a backdated directory NAME must not
    outrank a snapshot whose manifest records a later iteration."""
    root = str(tmp_path / "proj")
    newer = os.path.join(root, "weights", "000002")   # small name, iter 50
    older = os.path.join(root, "weights", "000100")   # big name, iter 5
    _fake_snapshot(newer, iter_idx=50)
    _fake_snapshot(older, iter_idx=5)
    assert integrity.latest_valid(root, do_quarantine=False) == newer


def test_latest_valid_breaks_iter_ties_on_mtime(tmp_path):
    """Same iteration in both tiers: the later WRITE wins."""
    import time

    root = str(tmp_path / "proj")
    durable = os.path.join(root, "weights", "000004")
    flushed = os.path.join(root, "emergency", "000004")
    now = time.time()
    _fake_snapshot(durable, iter_idx=4, mtime=now - 60)
    _fake_snapshot(flushed, iter_idx=4, mtime=now)
    assert integrity.latest_valid(root, do_quarantine=False) == flushed
    # flip the clock: the durable one becomes the later write
    os.utime(durable, (now + 60, now + 60))
    assert integrity.latest_valid(root, do_quarantine=False) == durable


def test_resolve_restore_path_fallback_orders_on_iter(tmp_path):
    """The explicit-path fallback scan uses the same (iter, mtime) key."""
    root = str(tmp_path / "proj")
    broken = os.path.join(root, "weights", "000200")
    newer = os.path.join(root, "weights", "000002")   # iter 50
    older = os.path.join(root, "weights", "000100")   # iter 5
    _fake_snapshot(broken, iter_idx=200)
    _fake_snapshot(newer, iter_idx=50)
    _fake_snapshot(older, iter_idx=5)
    os.remove(os.path.join(broken, integrity.COMMIT_MARKER))
    assert integrity.resolve_restore_path(broken) == newer


# -- tree_shardings error paths ----------------------------------------------


def test_tree_shardings_missing_mesh_axis_names_leaf(devices):
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    tree = {"layer": {"kernel": P("data"), "bias": P("tensor")}}
    with pytest.raises(ValueError, match=r"bias.*'tensor'.*size 1 is free"):
        tree_shardings(mesh, tree)


def test_tree_shardings_unknown_logical_axis_names_leaf(devices):
    tree = {"blk": {"w": ("embed",), "v": ("no_such_axis",)}}
    with pytest.raises(KeyError, match=r"v.*no_such_axis"):
        tree_shardings(_mesh(2), tree)


def test_tree_shardings_rank_mismatch_names_leaf(devices):
    from jax.sharding import PartitionSpec as P

    mesh = _mesh(2)
    tree = {"emb": {"table": P(None, "data")}}
    shapes = {"emb": {"table": (16,)}}
    with pytest.raises(ValueError, match=r"table.*rank 1"):
        tree_shardings(mesh, tree, shapes=shapes)
    # matching rank passes and yields NamedShardings
    out = tree_shardings(mesh, tree, shapes={"emb": {"table": (16, 4)}})
    assert out["emb"]["table"].mesh is mesh


# -- SIGTERM handler layering ------------------------------------------------


class _Chain:
    """Arms recorder + emergency tier + a recording previous handler
    around the checkpoint orchestrator, and cleans all of it up."""

    def __init__(self, tmp_path):
        self.tmp_path = tmp_path
        self.order = []

    def __enter__(self):
        from rocket_tpu.observe import recorder as flightrec
        from rocket_tpu.persist import checkpoint as cp

        self.flightrec, self.cp = flightrec, cp
        rec = flightrec.FlightRecorder(out_dir=str(self.tmp_path / "fr"))
        dump = rec.dump
        rec.dump = lambda reason="manual": (
            self.order.append("dump"), dump(reason))[1]
        flightrec.install(rec, sigterm=False)
        self.rec = rec

        tier = emergency.EmergencyTier(str(self.tmp_path / "proj"))
        flush = tier.flush
        tier.flush = lambda reason="preemption": (
            self.order.append("flush"), flush(reason))[1]
        emergency.activate(tier)
        self.tier = tier

        self._saved_prev = dict(cp._PREV_HANDLER)
        cp._PREV_HANDLER["handler"] = self._prev
        return self

    def _prev(self, signum, frame):
        self.order.append("prev")

    def stage(self, iter_idx=7):
        self.tier.capture(
            {"module_0": {"w": np.ones((2,), np.float32)}},
            iter_idx=iter_idx,
        )

    def __exit__(self, *exc):
        self.flightrec.uninstall()
        emergency.deactivate(self.tier)
        self.cp._PREV_HANDLER.clear()
        self.cp._PREV_HANDLER.update(self._saved_prev)
        self.cp._preempted.clear()


def test_sigterm_chain_order_dump_flush_prev(tmp_path, devices):
    """Satellite: one SIGTERM delivery runs recorder dump FIRST, emergency
    flush SECOND, the previous handler LAST."""
    from rocket_tpu.persist import checkpoint as cp

    with _Chain(tmp_path) as chain:
        chain.stage()
        cp._on_sigterm(signal.SIGTERM, None)
        assert chain.order == ["dump", "flush", "prev"]
        assert cp._preempted.is_set()
        assert chain.tier.flushes == 1
        assert (tmp_path / "proj" / "emergency" / "000007").is_dir()


def test_sigterm_reentrant_delivery_flushes_once(tmp_path, devices):
    """A second SIGTERM landing while the first handler chain is still
    running (prev handler re-raises) must not dump or flush again."""
    from rocket_tpu.persist import checkpoint as cp

    with _Chain(tmp_path) as chain:
        chain.stage()
        prev = chain._prev

        def reentrant(signum, frame):
            prev(signum, frame)
            if chain.order.count("prev") == 1:
                cp._on_sigterm(signum, frame)  # the second delivery

        cp._PREV_HANDLER["handler"] = reentrant
        cp._on_sigterm(signal.SIGTERM, None)
        assert chain.order == ["dump", "flush", "prev"]
        assert chain.tier.flushes == 1
        assert chain.tier.captures == 1


def test_sigterm_chain_with_recorder_handler_installed_first(tmp_path,
                                                             devices):
    """Install order recorder-first: the checkpoint orchestrator chains
    INTO the recorder's own handler — still exactly one dump."""
    from rocket_tpu.observe import recorder as flightrec
    from rocket_tpu.persist import checkpoint as cp

    with _Chain(tmp_path) as chain:
        chain.stage()
        # the recorder's own handler is the "previous" one in the chain
        cp._PREV_HANDLER["handler"] = flightrec._on_sigterm
        saved = dict(flightrec._PREV_SIGTERM)
        flightrec._PREV_SIGTERM["handler"] = chain._prev
        try:
            cp._on_sigterm(signal.SIGTERM, None)
        finally:
            flightrec._PREV_SIGTERM.clear()
            flightrec._PREV_SIGTERM.update(saved)
        # recorder's handler ran but did NOT dump a second time
        assert chain.order == ["dump", "flush", "prev"]


def test_second_flush_without_new_capture_is_noop(tmp_path, devices):
    tier = emergency.EmergencyTier(str(tmp_path / "p"))
    tier.capture({"m": {"w": np.zeros((2,), np.float32)}}, iter_idx=1)
    assert tier.flush("first") is not None
    assert tier.flush("second") is None  # nothing staged: idempotent
    assert tier.flushes == 1


# -- ZeRO-1 snapshots across data-axis sizes ---------------------------------


def test_zero1_snapshot_reshards_onto_larger_data_axis(tmp_path, devices):
    """A ``zero_stage=1`` run preempted on a 4-way data axis resumes on
    an 8-way axis: the restored optimizer mirrors must RE-PARTITION over
    the new data axis (8-way, not 4-way, and certainly not replicated),
    and the stitched trajectory still matches an uninterrupted unsharded
    reference — ZeRO is a placement change, never a numerics change."""
    import jax

    data = synthetic_classification(n=256)

    def _opt_mirror_specs(model):
        """PartitionSpecs of the Dense_0 kernel's optimizer mirrors."""
        out = []
        for leaf in jax.tree_util.tree_leaves(model.state.opt_state):
            if getattr(leaf, "shape", None) == (16, 32):
                out.append(leaf.sharding.spec)
        return out

    launcher_a, model_a, rec_a = _tree(tmp_path, data, tag="zref", epochs=1)
    launcher_a.launch()
    assert len(rec_a.losses) == 4

    # Stage 1: zero_stage=1 on 4 devices, preempted at iteration 2.
    launcher_b, model_b, rec_b = _tree(
        tmp_path, data, tag="zelastic", epochs=1, mesh=_mesh(4),
        zero_stage=1, extra=[SigtermInjector(at_iter=2)],
    )
    launcher_b.launch()
    assert len(rec_b.losses) == 3
    specs_b = _opt_mirror_specs(model_b)
    assert specs_b and all("data" in str(s) for s in specs_b), specs_b
    snap = tmp_path / "zelastic" / "v0" / "weights" / "000002"
    assert snap.is_dir()
    assert integrity.manifest_mesh(str(snap))["axes"]["data"] == 4

    # Stage 2: resume on all 8 devices, still zero_stage=1.
    launcher_c, model_c, rec_c = _tree(
        tmp_path, data, tag="zelastic", epochs=1, mesh=_mesh(8),
        zero_stage=1, resume="auto",
    )
    launcher_c.launch()
    assert len(rec_c.losses) == 1

    specs_c = _opt_mirror_specs(model_c)
    assert specs_c, "no optimizer mirrors found"
    for spec in specs_c:
        assert "data" in str(spec), (
            f"restored optimizer mirror replicated ({spec}) — the reshard "
            f"must re-partition over the new data axis"
        )
    # 8-way for real: each device holds 1/8 of the (16, 32) mirror
    mirror = next(
        leaf for leaf in jax.tree_util.tree_leaves(model_c.state.opt_state)
        if getattr(leaf, "shape", None) == (16, 32)
    )
    shard_shapes = {s.data.shape for s in mirror.addressable_shards}
    assert shard_shapes == {(2, 32)}, shard_shapes

    stitched = rec_b.losses + rec_c.losses
    np.testing.assert_allclose(stitched, rec_a.losses, rtol=1e-5, atol=1e-6)


# -- ZeRO stage transitions across restores ----------------------------------


def test_zero_stage1_snapshot_resumes_at_stage3_on_new_mesh(tmp_path,
                                                            devices):
    """A stage-1 snapshot (4-way data axis) resumes into a stage-3 run on
    8 devices: params re-partition into the zero storage domain, the
    manifest carries the saving stage, and the stitched trajectory still
    matches the uninterrupted unsharded reference — a ZeRO stage change
    across a restore is a placement change, never a numerics change."""
    import jax

    data = synthetic_classification(n=256)

    launcher_a, model_a, rec_a = _tree(tmp_path, data, tag="ztref", epochs=1)
    launcher_a.launch()
    assert len(rec_a.losses) == 4

    launcher_b, model_b, rec_b = _tree(
        tmp_path, data, tag="ztrans13", epochs=1, mesh=_mesh(4),
        zero_stage=1, extra=[SigtermInjector(at_iter=2)],
    )
    launcher_b.launch()
    assert len(rec_b.losses) == 3
    snap = tmp_path / "ztrans13" / "v0" / "weights" / "000002"
    assert snap.is_dir()
    meta = integrity.manifest_mesh(str(snap))
    assert meta["axes"]["data"] == 4
    assert meta["zero_stage"] == 1  # manifests stamp the saving stage

    launcher_c, model_c, rec_c = _tree(
        tmp_path, data, tag="ztrans13", epochs=1, mesh=_mesh(8),
        zero_stage=3, resume="auto",
    )
    launcher_c.launch()
    assert len(rec_c.losses) == 1

    # stage-3 storage domain for real: the restored Dense_0 kernel is
    # data-sliced across all 8 devices, not replicated
    kernel = next(
        leaf for leaf in jax.tree_util.tree_leaves(model_c.state.params)
        if getattr(leaf, "shape", None) == (16, 32)
    )
    assert "data" in str(kernel.sharding.spec), kernel.sharding.spec
    assert {s.data.shape for s in kernel.addressable_shards} == {(2, 32)}

    stitched = rec_b.losses + rec_c.losses
    np.testing.assert_allclose(stitched, rec_a.losses, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        _flat(model_c.state.params), _flat(model_a.state.params),
        rtol=1e-5, atol=1e-6,
    )


def test_zero_stage3_snapshot_resumes_at_stage0_on_new_mesh(tmp_path,
                                                            devices):
    """The inverse transition: a stage-3 run (params stored sharded on a
    4-way axis) is preempted and resumed as a plain unsharded stage-0 run
    on 2 devices — everything gathers back to replicated and the
    trajectory stitches against the uninterrupted reference."""
    import jax

    data = synthetic_classification(n=256)

    launcher_a, model_a, rec_a = _tree(tmp_path, data, tag="ztref0", epochs=1)
    launcher_a.launch()
    assert len(rec_a.losses) == 4

    launcher_b, model_b, rec_b = _tree(
        tmp_path, data, tag="ztrans30", epochs=1, mesh=_mesh(4),
        zero_stage=3, extra=[SigtermInjector(at_iter=2)],
    )
    launcher_b.launch()
    assert len(rec_b.losses) == 3
    snap = tmp_path / "ztrans30" / "v0" / "weights" / "000002"
    assert integrity.manifest_mesh(str(snap))["zero_stage"] == 3

    launcher_c, model_c, rec_c = _tree(
        tmp_path, data, tag="ztrans30", epochs=1, mesh=_mesh(2),
        zero_stage=0, resume="auto",
    )
    launcher_c.launch()
    assert len(rec_c.losses) == 1

    # back to stage 0: params and optimizer mirrors fully replicated
    for leaf in jax.tree_util.tree_leaves(model_c.state.params):
        assert "data" not in str(leaf.sharding.spec), leaf.sharding.spec
    for leaf in jax.tree_util.tree_leaves(model_c.state.opt_state):
        if hasattr(leaf, "sharding"):
            assert "data" not in str(leaf.sharding.spec), leaf.sharding.spec

    stitched = rec_b.losses + rec_c.losses
    np.testing.assert_allclose(stitched, rec_a.losses, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        _flat(model_c.state.params), _flat(model_a.state.params),
        rtol=1e-5, atol=1e-6,
    )
