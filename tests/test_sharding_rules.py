"""Rule-based sharding engine tests (rocket_tpu.parallel.sharding).

Covers the PartitionRules regex engine (first-match precedence, anchoring,
scalar replication, unmatched-leaf errors), the manifest round-trip through
persist.integrity, the retired suffix-match heuristic's ambiguity (as a
regression against the structural-mirror engine), model-zoo rule coverage
(regex-derived specs must equal annotation-derived specs leaf-for-leaf),
zero_compose unit semantics, and bit-equality of ``zero_stage=1`` training
against the unsharded optimizer path for Adam and Muon (± EMA).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from rocket_tpu.engine import Objective, TrainState, build_train_step
from rocket_tpu.engine.ema import params_ema
from rocket_tpu.engine.muon import muon
from rocket_tpu.parallel.mesh import MeshSpec
from rocket_tpu.parallel.sharding import (
    DEFAULT_PARTITION_RULES,
    DEFAULT_RULES,
    PartitionRules,
    ShardingRules,
    UnmatchedLeafError,
    canonical_path,
    specs_for_state,
    zero_compose,
)
from rocket_tpu.persist import integrity


def _mesh(**axes):
    spec = MeshSpec(**axes)
    n = 1
    for v in axes.values():
        n *= v
    return spec.build(jax.devices()[:n])


# -- rule semantics -----------------------------------------------------------


class TestRuleSemantics:
    def test_first_match_wins(self):
        """An earlier, more specific rule beats a later catch-all."""
        rules = PartitionRules(rules=(
            (r"attn/q/kernel$", ("embed", "heads")),
            (r"kernel$", (None, None)),
        ))
        assert rules.spec_for("block_0/attn/q/kernel", (16, 16)) == \
            P("fsdp", "tensor")
        # the catch-all still handles everything else
        assert rules.spec_for("block_0/mlp/up/kernel", (16, 32)) == P(None, None)

    def test_order_flip_changes_outcome(self):
        """Same rules, reversed order: the catch-all now shadows."""
        rules = PartitionRules(rules=(
            (r"kernel$", (None, None)),
            (r"attn/q/kernel$", ("embed", "heads")),
        ))
        assert rules.spec_for("block_0/attn/q/kernel", (16, 16)) == P(None, None)

    def test_anchoring_head_does_not_match_overhead(self):
        """`(^|/)head/` must not fire inside a longer name."""
        hit = DEFAULT_PARTITION_RULES.match("model/overhead/kernel")
        assert hit is None or "head/" not in hit[0] or "(^|/)head" not in hit[0]
        # the real head still matches at both root and nested positions
        assert DEFAULT_PARTITION_RULES.match("head/kernel") is not None
        assert DEFAULT_PARTITION_RULES.match("decoder/head/kernel") is not None

    def test_scalar_leaf_forced_replicated(self):
        """Scalars and size-1 leaves bypass matching entirely."""
        rules = PartitionRules(rules=((r"scale$", ("embed",)),))
        assert rules.spec_for("temp/scale", ()) == P()
        assert rules.spec_for("temp/scale", (1,)) == P()
        assert rules.spec_for("temp/scale", (8,)) == P("fsdp")

    def test_unmatched_leaf_error_names_exact_path(self):
        tree = {"block_3": {"weird": {"thing": jnp.zeros((4, 4))}}}
        with pytest.raises(UnmatchedLeafError, match=r"block_3/weird/thing"):
            PartitionRules(rules=()).specs_for_tree(tree)

    def test_partitioned_value_suffix_stripped(self):
        """flax nn.Partitioned boxes add a trailing /value path component."""
        assert DEFAULT_PARTITION_RULES.match("b0/attn/q/kernel/value") == \
            DEFAULT_PARTITION_RULES.match("b0/attn/q/kernel")

    def test_trailing_dims_right_aligned(self):
        """A rule names TRAILING dims; leading dims pad None — one rule
        covers the scan-stacked (layers-first) variant of a kernel."""
        rules = PartitionRules(rules=((r"kernel$", ("embed", "mlp")),))
        assert rules.spec_for("mlp/up/kernel", (16, 32)) == P("fsdp", "tensor")
        assert rules.spec_for("blocks/mlp/up/kernel", (4, 16, 32)) == \
            P(None, "fsdp", "tensor")

    def test_rule_longer_than_leaf_rank_raises(self):
        rules = PartitionRules(rules=((r"kernel$", ("embed", "mlp")),))
        with pytest.raises(ValueError):
            rules.spec_for("mlp/up/kernel", (16,))

    def test_none_logical_spec_replicates(self):
        rules = PartitionRules(rules=((r"Conv_0/kernel$", None),))
        assert rules.spec_for("Conv_0/kernel", (3, 3, 8, 16)) == P()

    def test_with_axes_remaps_logical_names(self):
        rules = PartitionRules(rules=((r"kernel$", ("embed", "heads")),))
        remapped = rules.with_axes(DEFAULT_RULES.replace(embed="tensor"))
        assert remapped.spec_for("q/kernel", (8, 8)) == P("tensor", "tensor")
        # original is unchanged (frozen dataclass)
        assert rules.spec_for("q/kernel", (8, 8)) == P("fsdp", "tensor")


# -- manifest round-trip ------------------------------------------------------


class TestManifestRoundTrip:
    def test_partition_rules_survive_manifest_json(self):
        mesh = _mesh(data=2, fsdp=2, tensor=2)
        manifest = integrity.build_manifest(
            {"module_0": {"state": {"w": np.zeros((8, 4), np.float32)}}},
            mesh=mesh, rules=DEFAULT_PARTITION_RULES,
        )
        section = json.loads(json.dumps(manifest))["mesh"]
        # legacy logical-axis table is still stamped in the old format
        legacy = dict((name, axes) for name, axes in section["rules"])
        assert legacy["embed"] == "fsdp"
        # the regex table rides alongside
        rebuilt = PartitionRules.from_manifest(section)
        assert rebuilt.to_table() == DEFAULT_PARTITION_RULES.to_table()
        assert rebuilt.table() == DEFAULT_PARTITION_RULES.table()

    def test_rebuilt_rules_produce_identical_specs(self):
        mesh = _mesh(data=2, fsdp=2, tensor=2)
        manifest = integrity.build_manifest(
            {}, mesh=mesh, rules=DEFAULT_PARTITION_RULES,
        )
        rebuilt = PartitionRules.from_manifest(
            json.loads(json.dumps(manifest))["mesh"]
        )
        tree = {
            "embed": {"embedding": jnp.zeros((64, 16))},
            "block_0": {"attn": {"q": {"kernel": jnp.zeros((16, 16))}}},
            "head": {"kernel": jnp.zeros((16, 64))},
        }
        assert rebuilt.specs_for_tree(tree) == \
            DEFAULT_PARTITION_RULES.specs_for_tree(tree)

    def test_zero_stage_stamp_round_trips(self):
        """Manifests stamp the ZeRO stage the run was sharded at; legacy
        manifests (no kwarg) omit the key entirely so old snapshots keep
        the strict stage-less restore path."""
        mesh = _mesh(data=2, fsdp=2, tensor=2)
        stamped = integrity.build_manifest(
            {}, mesh=mesh, rules=DEFAULT_PARTITION_RULES, zero_stage=3)
        assert json.loads(json.dumps(stamped))["mesh"]["zero_stage"] == 3
        legacy = integrity.build_manifest(
            {}, mesh=mesh, rules=DEFAULT_PARTITION_RULES)
        assert "zero_stage" not in json.loads(json.dumps(legacy))["mesh"]

    def test_check_reshard_accepts_rule_derived_targets(self):
        """check_reshard and the trainer resolve from the same table: a
        target tree shardend via PartitionRules passes the restore gate."""
        mesh = _mesh(data=2, fsdp=2, tensor=2)
        arrays = {"head": {"kernel": np.zeros((16, 64), np.float32)}}
        manifest = integrity.build_manifest(
            {"module_0": {"state": arrays}},
            mesh=mesh, rules=DEFAULT_PARTITION_RULES,
        )
        rebuilt = PartitionRules.from_manifest(manifest["mesh"])
        specs = rebuilt.specs_for_tree(arrays)
        targets = jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s)
            ),
            arrays, specs,
        )
        integrity.check_reshard(manifest, {"module_0": {"state": targets}})


# -- suffix-match heuristic regression ----------------------------------------


class TestSuffixRegression:
    def test_container_named_mu_does_not_confuse_mirrors(self):
        """The retired engine/adapter suffix heuristic matched optimizer
        leaves to params by longest path suffix.  A param container
        literally named ``mu`` made Adam's mu-moment of ``proj/kernel``
        (state path ``...mu/proj/kernel``) collide with the *param*
        ``mu/proj/kernel``.  The structural-mirror engine maps positionally
        and must give each moment its own param's spec."""
        mesh = _mesh(data=2, fsdp=2, tensor=2)
        params = {
            "mu": {"proj": {"kernel": jnp.zeros((8, 16))}},
            "proj": {"kernel": jnp.zeros((8, 16))},
        }
        rules = PartitionRules(rules=(
            (r"^mu/proj/kernel$", ("embed", None)),
            (r"^proj/kernel$", (None, "heads")),
        ))
        tx = optax.adam(1e-2)
        abstract = jax.eval_shape(lambda: TrainState.create(params, tx))
        plan = specs_for_state(mesh, abstract, rules=rules)
        mu = plan.state_specs.opt_state[0].mu
        nu = plan.state_specs.opt_state[0].nu
        assert mu == plan.state_specs.params
        assert nu == plan.state_specs.params
        assert mu["mu"]["proj"]["kernel"] == P("fsdp", None)
        assert mu["proj"]["kernel"] == P(None, "tensor")


# -- model-zoo coverage lint --------------------------------------------------


def _zoo_configs():
    from rocket_tpu.models.lenet import LeNet
    from rocket_tpu.models.resnet import resnet18
    from rocket_tpu.models.seq2seq import EncoderDecoder, Seq2SeqConfig
    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
    from rocket_tpu.models.vit import ViT, ViTConfig

    B = 2
    tok = {"tokens": jnp.zeros((B, 8), jnp.int32)}
    img32 = {"image": jnp.zeros((B, 32, 32, 3), jnp.float32)}
    return {
        "transformer": (TransformerLM(TransformerConfig(
            vocab_size=64, hidden=16, n_layers=2, n_heads=2, ffn_dim=32,
            max_seq=8, use_bias=True, lora_rank=4, tie_embeddings=False,
            positions="learned")), tok),
        "transformer-scan": (TransformerLM(TransformerConfig(
            vocab_size=64, hidden=16, n_layers=2, n_heads=2, ffn_dim=32,
            max_seq=8, scan_layers=True, fused_qkv=True,
            tie_embeddings=True)), tok),
        "transformer-int8": (TransformerLM(TransformerConfig(
            vocab_size=64, hidden=16, n_layers=2, n_heads=2, ffn_dim=32,
            max_seq=8, weights_int8=True, tie_embeddings=True)), tok),
        "moe": (TransformerLM(TransformerConfig(
            vocab_size=64, hidden=16, n_layers=2, n_heads=2, ffn_dim=32,
            max_seq=8, n_experts=4, moe_top_k=2, use_bias=True)), tok),
        "transformer-pipelined": (TransformerLM(TransformerConfig(
            vocab_size=64, hidden=16, n_layers=4, n_heads=2, ffn_dim=32,
            max_seq=8, use_bias=True, tie_embeddings=True,
            pipeline_microbatches=2, pipeline_schedule="interleaved",
            pipeline_chunks=2)), tok),
        "vit": (ViT(ViTConfig.tiny()), img32),
        "resnet": (resnet18(num_classes=10), img32),
        "seq2seq": (EncoderDecoder(Seq2SeqConfig(
            vocab_size=64, hidden=16, n_encoder_layers=1, n_decoder_layers=1,
            n_heads=2, ffn_dim=32, max_seq=8)), {
                "inputs": jnp.zeros((B, 8), jnp.int32),
                "targets": jnp.zeros((B, 8), jnp.int32)}),
        "lenet": (LeNet(), {"image": jnp.zeros((B, 28, 28, 1), jnp.float32)}),
    }


@pytest.mark.parametrize("name", [
    "transformer", "transformer-scan", "transformer-int8", "moe",
    "transformer-pipelined", "vit", "resnet", "seq2seq", "lenet",
])
def test_zoo_default_rules_match_annotations(name):
    """CI lint: every model-zoo config gets a fully-matched spec tree from
    DEFAULT_PARTITION_RULES, identical leaf-for-leaf to the specs derived
    from the model's own nn.with_partitioning annotations."""
    from rocket_tpu.engine.adapter import FlaxModel

    model, batch = _zoo_configs()[name]
    adapter = FlaxModel(model)
    params, mutable = jax.eval_shape(
        lambda: adapter.init_variables(jax.random.PRNGKey(0), batch)
    )
    ann = adapter.partition_specs(params, DEFAULT_RULES)
    reg = DEFAULT_PARTITION_RULES.specs_for_tree(params)  # must not raise

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    is_spec = lambda x: isinstance(x, P)
    ann_leaves = jax.tree_util.tree_leaves(ann, is_leaf=is_spec)
    reg_leaves = jax.tree_util.tree_leaves(reg, is_leaf=is_spec)
    assert len(flat) == len(ann_leaves) == len(reg_leaves)
    mismatches = [
        f"{canonical_path(path)} shape={tuple(leaf.shape)}: "
        f"annotation={sa} rules={sr}"
        for (path, leaf), sa, sr in zip(flat, ann_leaves, reg_leaves)
        # size-1 leaves are forced replicated by the engine; the
        # annotation value is irrelevant for them
        if int(np.prod(leaf.shape)) > 1 and sa != sr
    ]
    assert not mismatches, "\n".join(mismatches)
    # mutable collections (e.g. BatchNorm stats) must also be coverable
    for path, leaf in jax.tree_util.tree_flatten_with_path(mutable)[0]:
        p = canonical_path(path)
        if int(np.prod(leaf.shape)) > 1:
            assert DEFAULT_PARTITION_RULES.match(p) is not None, (
                f"mutable leaf {p} (shape {tuple(leaf.shape)}) unmatched"
            )


def _data_eligible(spec, shape, mesh):
    """Independent recomputation of zero_compose's fold condition: True iff
    the data axis can divide some dim of the leaf given its base spec."""
    shape = tuple(shape)
    if int(np.prod(shape)) <= 1:
        return False
    axes = dict(mesh.shape)
    if axes.get("data", 1) <= 1:
        return False
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for dim, entry in zip(shape, entries):
        names = (
            () if entry is None
            else (entry,) if isinstance(entry, str) else tuple(entry)
        )
        if "data" in names:
            return True
        factor = axes["data"] * int(np.prod([axes[n] for n in names] or [1]))
        if dim % factor == 0:
            return True
    return False


@pytest.mark.parametrize("name", [
    "transformer", "transformer-scan", "transformer-int8", "moe",
    "transformer-pipelined", "vit", "resnet", "seq2seq", "lenet",
])
def test_zoo_rules_resolve_zero_stage_2_and_3_leaves(name):
    """CI lint for ZeRO stages 2/3: every zoo config's rule-derived table
    must produce a plan whose grad-accum (stage 2) and param-storage
    (stage 3) trees equal the leafwise zero_compose of the base specs —
    and every leaf the data axis *can* divide must actually carry it.  No
    silent fall-through to replicated.  (AdamW has no matrix-update
    exemptions, so nothing is legitimately left at base here except
    genuinely indivisible leaves.)"""
    model, batch = _zoo_configs()[name]
    mesh = _mesh(data=2, fsdp=2, tensor=2)
    from rocket_tpu.engine.adapter import FlaxModel

    adapter = FlaxModel(model)
    params, _ = jax.eval_shape(
        lambda: adapter.init_variables(jax.random.PRNGKey(0), batch)
    )
    pspecs = DEFAULT_PARTITION_RULES.specs_for_tree(params)
    abstract = jax.eval_shape(lambda: TrainState.create(
        params, optax.adamw(1e-3), gradient_accumulation_steps=2))

    is_spec = lambda x: isinstance(x, P)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    base_leaves = [
        P() if s is None else s
        for s in jax.tree_util.tree_leaves(pspecs, is_leaf=is_spec)
    ]
    expected = [
        zero_compose(s, tuple(leaf.shape), mesh)
        for (_, leaf), s in zip(flat, base_leaves)
    ]

    plan2 = specs_for_state(
        mesh, abstract, param_specs=pspecs, zero_stage=2,
        make_shardings=False)
    plan3 = specs_for_state(
        mesh, abstract, param_specs=pspecs, zero_stage=3,
        make_shardings=False)

    got_accum = jax.tree_util.tree_leaves(
        plan2.state_specs.grad_accum, is_leaf=is_spec)
    got_params = jax.tree_util.tree_leaves(
        plan3.state_specs.params, is_leaf=is_spec)
    assert len(got_accum) == len(got_params) == len(expected)

    mismatches = []
    for (path, leaf), base, want, ga, p3 in zip(
            flat, base_leaves, expected, got_accum, got_params):
        where = f"{canonical_path(path)} shape={tuple(leaf.shape)}"
        if ga != want:
            mismatches.append(f"{where}: stage-2 grad_accum {ga} != {want}")
        if p3 != want:
            mismatches.append(f"{where}: stage-3 params {p3} != {want}")
        # eligibility cross-check: a divisible leaf must gain the axis
        eligible = _data_eligible(base, leaf.shape, mesh)
        gained = any(
            "data" in ((e,) if isinstance(e, str) else tuple(e or ()))
            for e in want
        )
        if eligible != gained:
            mismatches.append(
                f"{where}: base={base} composed={want} "
                f"eligible={eligible} but gained={gained}"
            )
    assert not mismatches, "\n".join(mismatches)
    # stage 2 leaves the forward/backward param domain untouched
    assert jax.tree_util.tree_leaves(
        plan2.state_specs.params, is_leaf=is_spec) == base_leaves


# -- zero_compose -------------------------------------------------------------


class TestZeroCompose:
    def test_folds_data_into_first_divisible_dim(self):
        mesh = _mesh(data=4, tensor=2)
        assert zero_compose(P(None, "tensor"), (64, 128), mesh) == \
            P(("data",), "tensor")

    def test_composes_with_existing_axis_on_same_dim(self):
        mesh = _mesh(data=4, tensor=2)
        # dim 0 carries tensor(2); folding data(4) needs 8 | 64 — ok
        assert zero_compose(P("tensor", None), (64, 128), mesh) == \
            P(("tensor", "data"), None)

    def test_skips_to_next_dim_when_first_indivisible(self):
        mesh = _mesh(data=4, tensor=2)
        assert zero_compose(P(), (6, 64), mesh) == P(None, ("data",))

    def test_scalar_and_size1_pass_through(self):
        mesh = _mesh(data=4)
        assert zero_compose(P(), (), mesh) == P()
        assert zero_compose(P(), (1,), mesh) == P()

    def test_already_data_sharded_unchanged(self):
        mesh = _mesh(data=4)
        assert zero_compose(P("data"), (64,), mesh) == P("data")

    def test_no_divisible_dim_stays_base(self):
        mesh = _mesh(data=4)
        assert zero_compose(P(), (6, 10), mesh) == P(None, None)

    def test_data_axis_size_one_is_noop(self):
        mesh = _mesh(data=1, tensor=2)
        assert zero_compose(P(None, "tensor"), (64, 128), mesh) == \
            P(None, "tensor")


# -- specs_for_state plan shape -----------------------------------------------


class TestSpecsForState:
    def _state(self, tx, accum=1):
        params = {
            "w1": jnp.zeros((64, 128)),
            "w2": jnp.zeros((128, 64)),
            "b": jnp.zeros((64,)),
        }
        return jax.eval_shape(lambda: TrainState.create(
            params, tx, gradient_accumulation_steps=accum))

    _pspecs = {"w1": P(None, "tensor"), "w2": P("tensor", None), "b": P()}

    def test_zero_stage0_mirrors_param_specs(self):
        mesh = _mesh(data=4, tensor=2)
        plan = specs_for_state(
            mesh, self._state(optax.adam(1e-2)), param_specs=self._pspecs)
        assert plan.state_specs.opt_state[0].mu == plan.state_specs.params
        assert plan.state_specs.step == P()
        assert plan.zero_param_shardings == plan.param_shardings

    def test_zero_stage1_repartitions_adam_moments(self):
        mesh = _mesh(data=4, tensor=2)
        plan = specs_for_state(
            mesh, self._state(optax.adam(1e-2)),
            param_specs=self._pspecs, zero_stage=1)
        mu = plan.state_specs.opt_state[0].mu
        assert mu["w1"] == P(("data",), "tensor")
        assert mu["w2"] == P(("tensor", "data"), None)
        assert mu["b"] == P(("data",))
        # params themselves stay at base for forward/backward
        assert plan.state_specs.params == self._pspecs

    def test_zero_stage1_grad_accum_stays_base(self):
        """Accumulation buffers add elementwise-exactly at base sharding;
        they are NOT zero-composed (only optimizer mirrors are)."""
        mesh = _mesh(data=4, tensor=2)
        plan = specs_for_state(
            mesh, self._state(optax.adam(1e-2), accum=2),
            param_specs=self._pspecs, zero_stage=1)
        assert plan.state_specs.grad_accum == self._pspecs
        assert plan.state_specs.micro == P()

    def test_zero_stage2_grad_accum_zero_composed(self):
        """Stage 2 moves the accumulation buffers into the zero domain —
        gradients reduce-scatter straight into the shard owner."""
        mesh = _mesh(data=4, tensor=2)
        plan = specs_for_state(
            mesh, self._state(optax.adam(1e-2), accum=2),
            param_specs=self._pspecs, zero_stage=2)
        ga = plan.state_specs.grad_accum
        assert ga["w1"] == P(("data",), "tensor")
        assert ga["w2"] == P(("tensor", "data"), None)
        assert ga["b"] == P(("data",))
        # forward/backward domain is untouched at stage 2
        assert plan.state_specs.params == self._pspecs

    def test_zero_stage3_params_storage_zero_composed(self):
        """Stage 3 stores the params themselves on the zero shard; the
        compute specs keep the base layout (the step gathers on demand)."""
        mesh = _mesh(data=4, tensor=2)
        plan = specs_for_state(
            mesh, self._state(optax.adam(1e-2)),
            param_specs=self._pspecs, zero_stage=3)
        stored = plan.state_specs.params
        assert stored["w1"] == P(("data",), "tensor")
        assert stored["w2"] == P(("tensor", "data"), None)
        assert stored["b"] == P(("data",))
        assert plan.param_specs == self._pspecs
        # optimizer mirrors live in the same domain as the storage
        assert plan.state_specs.opt_state[0].mu == stored

    def test_zero_stage3_muon_rank2_params_stay_base(self):
        """Muon's matrix-update exemption extends to the storage domain:
        rank-2 params are never data-sliced, only the rank-1 bias is."""
        mesh = _mesh(data=4, tensor=2)
        plan = specs_for_state(
            mesh, self._state(muon(1e-2)),
            param_specs=self._pspecs, zero_stage=3)
        stored = plan.state_specs.params
        assert stored["w1"] == P(None, "tensor")
        assert stored["w2"] == P("tensor", None)
        assert stored["b"] == P(("data",))

    def test_invalid_zero_stage_rejected(self):
        mesh = _mesh(data=4, tensor=2)
        with pytest.raises(ValueError, match="zero_stage"):
            specs_for_state(
                mesh, self._state(optax.adam(1e-2)),
                param_specs=self._pspecs, zero_stage=4)

    def test_make_shardings_false_prices_hypothetical_mesh(self):
        """Spec arithmetic must run against a mesh this host doesn't have
        (a pod shape's memory plan): any object with a ``.shape``
        mapping works when NamedSharding construction is skipped."""
        class PodMesh:
            shape = {"data": 64, "tensor": 1}

        plan = specs_for_state(
            PodMesh(), self._state(optax.adam(1e-2)),
            param_specs=self._pspecs, zero_stage=3, make_shardings=False)
        assert plan.param_shardings is None
        assert plan.zero_param_shardings is None
        assert plan.state_shardings is None
        assert plan.state_specs.params["w1"] == P(("data",), "tensor")
        assert plan.state_specs.opt_state[0].mu["b"] == P(("data",))

    def test_muon_rank2_exempt_from_zero(self):
        """Newton-Schulz orthogonalization reduces over the full matrix:
        rank-2 params (and their momenta) must keep base sharding."""
        mesh = _mesh(data=4, tensor=2)
        plan = specs_for_state(
            mesh, self._state(muon(1e-2)),
            param_specs=self._pspecs, zero_stage=1)
        leaves = {
            canonical_path(p): s for p, s in
            jax.tree_util.tree_flatten_with_path(
                plan.state_specs.opt_state,
                is_leaf=lambda x: isinstance(x, P))[0]
        }
        momenta = {k: v for k, v in leaves.items() if "momentum" in k}
        assert any(v == P(None, "tensor") for v in momenta.values())
        assert any(v == P("tensor", None) for v in momenta.values())
        # the rank-1 bias momentum is still zero-composed
        assert any(v == P(("data",)) for v in momenta.values())


# -- zero_stage=1 bit-equality ------------------------------------------------


def _bit_eq_setup():
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    params = {
        "w1": jax.random.normal(k1, (64, 128), jnp.float32),
        "w2": jax.random.normal(k2, (128, 64), jnp.float32) * 0.1,
        "b": jnp.zeros((64,), jnp.float32),
    }
    pspecs = {"w1": P(None, "tensor"), "w2": P("tensor", None), "b": P()}

    def apply_fn(p, mutable, rng, batch, train):
        out = dict(batch)
        h = jnp.tanh(batch["x"] @ p["w1"])
        out["pred"] = h @ p["w2"] + p["b"]
        return out, mutable

    def loss(batch):
        return jnp.mean((batch["pred"] - batch["y"]) ** 2)

    return params, pspecs, apply_fn, loss


def _run_zero(tx, zero_stage, steps_n=6, accum=1):
    """Train `steps_n` steps on a data=4 × tensor=2 mesh through the repo's
    own machinery (specs_for_state + build_train_step).  ``accum > 1``
    drives the micro/sync cadence (``steps_n`` counts micro batches)."""
    mesh = _mesh(data=4, tensor=2)
    params, pspecs, apply_fn, loss = _bit_eq_setup()
    abstract = jax.eval_shape(lambda: TrainState.create(
        params, tx, gradient_accumulation_steps=accum))
    plan = specs_for_state(
        mesh, abstract, param_specs=pspecs, zero_stage=zero_stage)
    state = TrainState.create(params, tx, gradient_accumulation_steps=accum)
    state = jax.device_put(state, plan.state_shardings)
    step_fns = build_train_step(
        apply_fn, [Objective("mse", loss)], tx,
        gradient_accumulation_steps=accum,
        shard_plan=plan if zero_stage else None,
    )
    batch_sh = NamedSharding(mesh, P("data"))
    rng = np.random.default_rng(0)
    losses = []
    for i in range(steps_n):
        batch = {
            "x": jax.device_put(
                jnp.asarray(rng.normal(size=(8, 64)), jnp.float32), batch_sh),
            "y": jax.device_put(
                jnp.asarray(rng.normal(size=(8, 64)), jnp.float32), batch_sh),
        }
        fn = step_fns["sync"] if (i + 1) % accum == 0 else step_fns["micro"]
        state, logs = fn(state, batch)
        losses.append(float(logs["loss"]))
    return losses, jax.device_get(state.params), jax.device_get(state.opt_state)


def _tx_variants():
    return {
        "adam": optax.adamw(1e-2),
        "muon": muon(1e-2),
        "adam+ema": optax.chain(optax.adamw(1e-2), params_ema(0.99)),
        "muon+ema": optax.chain(muon(1e-2), params_ema(0.99)),
    }


_ORACLES = {}


def _oracle(variant, accum=1):
    """Memoized unsharded (zero_stage=0) trajectory per optimizer variant —
    the oracle every sharded stage is compared against bitwise."""
    key = (variant, accum)
    if key not in _ORACLES:
        _ORACLES[key] = _run_zero(
            _tx_variants()[variant], zero_stage=0, accum=accum)
    return _ORACLES[key]


def _assert_bit_equal(ref, got):
    l0, p0, o0 = ref
    l1, p1, o1 = got
    assert l0 == l1
    for a, b in zip(jax.tree_util.tree_leaves(p0),
                    jax.tree_util.tree_leaves(p1)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree_util.tree_leaves(o0),
                    jax.tree_util.tree_leaves(o1)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("variant", ["adam", "muon", "adam+ema", "muon+ema"])
def test_zero_stage1_bitwise_equals_unsharded(variant):
    """ZeRO-1 must not change the training trajectory AT ALL: per-step
    losses, final params, and final optimizer state are compared bitwise
    against the unsharded optimizer path on the same mesh."""
    _assert_bit_equal(
        _oracle(variant),
        _run_zero(_tx_variants()[variant], zero_stage=1),
    )


@pytest.mark.parametrize("stage", [2, 3])
@pytest.mark.parametrize("variant", ["adam", "muon", "adam+ema", "muon+ema"])
def test_zero_stage23_bitwise_equals_unsharded(stage, variant):
    """Stages 2 (grads reduce-scattered into the shard owner) and 3
    (params stored sharded, gathered on demand) are pure layout moves:
    the trajectory must stay bitwise identical to the unsharded path."""
    _assert_bit_equal(
        _oracle(variant),
        _run_zero(_tx_variants()[variant], zero_stage=stage),
    )


@pytest.mark.parametrize("stage", [2, 3])
@pytest.mark.parametrize("variant", ["adam", "muon"])
def test_zero_stage23_bitwise_with_grad_accum(stage, variant):
    """Gradient accumulation under stages 2/3: micro-sums happen on the
    zero shard (elementwise, exact) — still bitwise vs the unsharded
    accumulating oracle."""
    _assert_bit_equal(
        _oracle(variant, accum=2),
        _run_zero(_tx_variants()[variant], zero_stage=stage, accum=2),
    )
