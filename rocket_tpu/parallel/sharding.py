"""Named-sharding helpers, logical-axis rules, and the rule-based engine
that resolves one coherent placement for a full TrainState.

This is where the reference's implicit "replicate the model, shard the batch"
DDP contract (``rocket/core/module.py:106``, ``dataset.py:175-180``) becomes
explicit, composable GSPMD shardings.  Two layers of naming:

1. **Logical axes** — models annotate parameters with *logical* axis names
   (``'embed'``, ``'mlp'``, ``'heads'``, …); a :class:`ShardingRules` table
   maps logical names to mesh axes, so the same model code runs replicated
   on one chip or tensor/fsdp-sharded on a pod — only the rules change.
2. **Path rules** — :class:`PartitionRules` maps *leaf paths* (regexes over
   ``'block_0/attn/q/kernel'``-style canonical paths) to logical-spec
   tuples, so trees that carry **no** annotations — optax optimizer state,
   grad-accum buffers, mutable collections, externally-defined models —
   resolve through the same vocabulary.

:func:`specs_for_state` combines both into a :class:`ShardingPlan`: the
single source of truth consumed by ``core/module.py`` (materialization),
the ``engine/step.py`` train step (ZeRO constraints), ``persist/integrity``
(manifest stamps + ``check_reshard`` restore targets) and
``Module.memory_plan()`` (per-device byte accounting).  Optimizer-state
subtrees that are *structural mirrors* of the params (Adam ``mu``/``nu``,
Muon momenta, EMA shadows) inherit the param specs positionally — this
retires the old path-suffix heuristic that silently mis-placed state when
two params shared a suffix and shape.

Rule semantics (each under test in ``tests/test_sharding_rules.py``):
first-match-wins precedence; ``re.search`` so patterns anchor themselves
(``$``, ``(^|/)`` — ``head/kernel`` must not match ``overhead/kernel``);
scalar/size-1 leaves replicate before any rule is consulted; a rule names
the *trailing* dims (right-aligned, so one ``("embed", "mlp")`` rule covers
a rank-2 kernel and its scan-stacked rank-3 variant); a trailing ``/value``
component (flax ``nn.Partitioned`` box) is stripped; an unmatched leaf
raises :class:`UnmatchedLeafError` naming the exact path — never a silent
replication.  :data:`DEFAULT_PARTITION_RULES` covers the whole model zoo
(transformer incl. LoRA / int8 / fused-QKV / scan, MoE, ViT, ResNet,
seq2seq, LeNet); a tier-1 lint asserts the regex-derived specs equal the
annotation-derived specs leaf-for-leaf for every config.

**ZeRO stage 1** (``Runtime(zero_stage=1)`` / ``Launcher(zero_stage=1)``,
arXiv 2004.13336 "Automatic Cross-Replica Sharding of Weight Update in
Data-Parallel Training"): optimizer state and the weight update
re-partition over the ``data`` axis (:func:`zero_compose` folds ``data``
into the first evenly-divisible dim, composing with — not replacing — any
existing fsdp/tensor sharding); the optax update runs on the shard and
only the updated params are all-gathered, all inside the jitted step.  The
constraint chain in ``engine/step.py`` keeps the trajectory **bit-equal**
to the unsharded path (Adam and Muon, ± EMA)::

    grads      -> pin to base param shardings   # backward stays identical
    grads      -> pin to zero shardings         # slice to the update shard
    params_in  -> pin to zero shardings
    tx.update + apply_updates                   # run entirely on the shard
    new_params -> pin to zero shardings         # keep the FMA on-shard
    new_params -> pin to base shardings         # the all-gather
    new_opt    -> pin to zero opt shardings     # moments stay sharded

Muon's rank-2 params are exempt (Newton-Schulz orthogonalization reduces
over the full matrix); grad-accum buffers stay at base sharding (the
micro-sum must be elementwise-exact); ZeRO stages are incompatible
with ``fuse_accumulation`` windows (:class:`ZeroIncompatibleError`).
At Llama-2-7B full-finetune with
Adam on a pure 8-way ``data`` mesh this turns 25.1 GB of replicated
moments into 3.1 GB per device — 40.3 GB of step arguments (provably over
a 32 GB v4 chip) down to 15.7 GB (AOT-compiles within the envelope); the
worked example lives in ``docs/performance.md`` and is pinned by
``tests/test_ladder_shapes.py::test_llama2_7b_full_finetune_zero1_fits_v4_hbm``
and ``tests/test_overhead_counts.py::TestZeroGuard``.

**ZeRO stages 2 and 3** extend the same composition through the rest of
the state:

- ``zero_stage=2`` additionally moves the *gradient accumulation
  buffers* into the zero domain and pins fresh gradients straight to it
  inside the step — GSPMD then lowers the data-axis gradient reduction
  as a **reduce-scatter into the shard owner** instead of an all-reduce
  followed by a local slice (half the comm volume, no full-gradient
  replica materialized).  The micro-window sum stays elementwise on the
  shard, so accumulation remains exact.
- ``zero_stage=3`` additionally shards the **parameters themselves**:
  ``state_specs.params`` (the storage/donation domain) becomes the
  zero-composed spec tree and the step **all-gathers params on demand**
  at the top of the forward (one ``with_sharding_constraint`` to the
  base compute domain), so the full parameter replica exists only
  transiently inside the step — this is the FSDP shape of the paper.

Every stage keeps the trajectory bit-equal to the unsharded oracle (the
same constraint-chain discipline; ``tests/test_sharding_rules.py``
covers adam/muon ± ema ± gradient accumulation at every stage), and the
Muon rank-2 exemption applies to all three stages.  Per-chip state cost:
``P + O`` at stage 0/1 (``O/N`` at 1), ``P + O/N`` plus ``A/N``
accumulation at stage 2, and ``P/N + O/N`` at stage 3 — the decision
table with comm volumes lives in ``docs/performance.md``.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from rocket_tpu.parallel.mesh import DATA_AXES

P = PartitionSpec

MeshAxes = Union[None, str, Tuple[str, ...]]


def named_sharding(mesh: Mesh, *spec: MeshAxes) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(
    mesh: Mesh, ndim: int = 1, seq_dim: Optional[int] = None
) -> NamedSharding:
    """Sharding for a batch of rank ``ndim``: leading dim over the data axes
    (``data`` × ``fsdp``), optional sequence dim over ``seq`` (for
    sequence/context parallelism), rest replicated."""
    spec: list = [DATA_AXES] + [None] * (ndim - 1)
    if seq_dim is not None:
        if not -ndim <= seq_dim < ndim:
            raise ValueError(f"seq_dim {seq_dim} out of range for rank {ndim}")
        seq_dim = seq_dim % ndim
        if seq_dim == 0:
            raise ValueError("seq_dim must not be the batch dim")
        spec[seq_dim] = "seq"
    return NamedSharding(mesh, P(*spec))


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Logical-axis-name → mesh-axis mapping.

    Defaults implement the standard transformer recipe (scaling-book):
    batch over data axes, embed/residual sharded over ``fsdp`` (ZeRO-style),
    heads/mlp over ``tensor``, sequence over ``seq``, experts over
    ``expert``, pipeline stages over ``pipe``.
    """

    rules: Tuple[Tuple[str, MeshAxes], ...] = (
        ("batch", DATA_AXES),
        ("sequence", "seq"),
        ("embed", "fsdp"),
        ("heads", "tensor"),
        ("kv", None),
        ("mlp", "tensor"),
        ("vocab", "tensor"),
        ("expert", "expert"),
        ("stage", "pipe"),
        ("norm", None),
        ("layers", None),  # scan-stacked layer dim (never sharded)
        # Activation-only axes: the residual stream's feature dim must NOT
        # reuse the parameter 'embed' -> 'fsdp' mapping (the batch dim
        # already occupies 'fsdp'; ZeRO shards params, not activations).
        ("act_embed", None),
    )

    def table(self) -> Dict[str, MeshAxes]:
        return dict(self.rules)

    def spec(self, *logical_axes: Optional[str]) -> PartitionSpec:
        """Translate logical axis names to a PartitionSpec."""
        table = self.table()
        out = []
        for name in logical_axes:
            if name is None:
                out.append(None)
            elif name in table:
                out.append(table[name])
            else:
                raise KeyError(f"unknown logical axis {name!r}; add a rule")
        return P(*out)

    def sharding(self, mesh: Mesh, *logical_axes: Optional[str]) -> NamedSharding:
        return NamedSharding(mesh, self.spec(*logical_axes))

    def replace(self, **updates: MeshAxes) -> "ShardingRules":
        table = self.table()
        table.update(updates)
        return ShardingRules(rules=tuple(table.items()))


DEFAULT_RULES = ShardingRules()


def tree_shardings(
    mesh: Mesh,
    tree: Any,
    rules: ShardingRules = DEFAULT_RULES,
    shapes: Any = None,
) -> Any:
    """Map a pytree of logical-axis tuples (as produced by
    ``nn.with_partitioning`` metadata / ``nn.get_partition_spec``) to a pytree
    of NamedShardings.

    Every error names the offending leaf's tree path — a bad annotation in
    a 400-leaf model must say *which* leaf, not just *what* (an opaque
    ``KeyError: 'mlp'`` cost a debugging afternoon once).  ``shapes`` is an
    optional matching pytree of array shapes (tuples); when given, a spec
    with more entries than the leaf has dims is rejected here rather than
    as a GSPMD lowering error later.
    """
    mesh_axes = set(str(name) for name in mesh.shape)
    is_leaf = lambda x: x is None or isinstance(x, (tuple, list, PartitionSpec))

    def leaf_to_sharding(path: Any, leaf: Any, shape: Any = None) -> Any:
        where = jax.tree_util.keystr(path) or "<root>"
        if isinstance(leaf, PartitionSpec):
            spec = leaf
        elif leaf is None:
            spec = P()
        elif isinstance(leaf, (tuple, list)):
            try:
                spec = rules.spec(*leaf)
            except KeyError as exc:
                raise KeyError(f"leaf {where}: {exc.args[0]}") from None
        else:
            raise TypeError(
                f"leaf {where}: cannot interpret sharding annotation {leaf!r}"
            )
        for entry in spec:
            for axis in entry if isinstance(entry, (tuple, list)) else (entry,):
                if axis is not None and str(axis) not in mesh_axes:
                    raise ValueError(
                        f"leaf {where}: PartitionSpec {spec} names mesh axis "
                        f"{axis!r} absent from mesh axes "
                        f"{tuple(dict(mesh.shape))} — build the mesh with "
                        f"that axis (size 1 is free) or remap the logical "
                        f"axis in ShardingRules"
                    )
        if shape is not None and len(spec) > len(tuple(shape)):
            raise ValueError(
                f"leaf {where}: PartitionSpec {spec} has {len(spec)} entries "
                f"but the array is rank {len(tuple(shape))} "
                f"(shape {tuple(shape)})"
            )
        return NamedSharding(mesh, spec)

    if shapes is not None:
        return jax.tree_util.tree_map_with_path(
            leaf_to_sharding, tree, shapes, is_leaf=is_leaf
        )
    return jax.tree_util.tree_map_with_path(
        leaf_to_sharding, tree, is_leaf=is_leaf
    )


def shard_like(tree: Any, shardings: Any) -> Any:
    """Constrain/lay out every leaf of ``tree`` per ``shardings``
    (device_put for concrete arrays)."""
    return jax.device_put(tree, shardings)


# ---------------------------------------------------------------------------
# Rule engine: regex-over-leaf-path partition rules.
#
# The annotation path (``nn.with_partitioning`` -> ``ShardingRules``) covers
# params the model author labelled; :class:`PartitionRules` covers everything
# by *path* — params, optimizer mirrors, mutable collections — from one
# ordered rule table, first match wins.  This is the single source the
# trainer (``core.module``), the manifest stamp (``persist.integrity``) and
# ``check_reshard`` all consume.
# ---------------------------------------------------------------------------

# A rule's logical spec names the TRAILING dims of the leaf (right-aligned);
# leading dims pad with None.  One ('embed',) rule therefore covers the
# rank-2 unrolled kernel AND its rank-3 scan-stacked twin.  ``None`` as the
# whole spec means fully replicated.
LogicalSpec = Optional[Tuple[Optional[str], ...]]


def canonical_path(path: Any) -> str:
    """'/'-joined leaf path, container-agnostic (mirrors
    ``persist.integrity._canon_path``): dict keys, NamedTuple fields and
    sequence indices all canonicalize to their bare names."""
    parts = []
    for key in path:
        for attr in ("name", "key", "idx"):
            value = getattr(key, attr, None)
            if value is not None:
                parts.append(str(value))
                break
        else:
            parts.append(str(key))
    return "/".join(parts)


class UnmatchedLeafError(ValueError):
    """A leaf no rule matches — names the exact leaf path."""


# Stages implemented by the rule engine (arXiv 2004.13336): 0 = off,
# 1 = optimizer state, 2 = + gradients (reduce-scatter), 3 = + params
# (all-gather-on-demand / FSDP).
ZERO_STAGES = (0, 1, 2, 3)


class ZeroIncompatibleError(ValueError):
    """A ZeRO stage/offload setting combined with a feature it cannot
    support.  One typed error per genuinely incompatible combination —
    carries the offending ``feature``, the ``zero_stage``, and the
    ``remedy`` (also baked into the message) instead of a bare string.
    """

    def __init__(self, feature: str, zero_stage: int, remedy: str,
                 detail: str = "") -> None:
        self.feature = feature
        self.zero_stage = int(zero_stage)
        self.remedy = remedy
        msg = (
            f"zero_stage={int(zero_stage)} is not supported with "
            f"{feature}"
        )
        if detail:
            msg += f" — {detail}"
        msg += f". Remedy: {remedy}."
        super().__init__(msg)


def _leaf_size(shape: Sequence[int]) -> int:
    return int(math.prod(tuple(shape))) if shape is not None else 1


@dataclasses.dataclass(frozen=True)
class PartitionRules:
    """Ordered ``(regex, logical-spec)`` rules over '/'-joined leaf paths.

    Matching is ``re.search`` with first-match-wins precedence — anchor with
    ``$`` (and ``(^|/)`` where a bare name could be a substring of another).
    Logical names resolve through ``axes`` (a :class:`ShardingRules` table),
    so retargeting a whole rule set to a different mesh layout is
    ``rules.with_axes(...)``, not a rewrite.

    Scalar and size-1 leaves are forced replicated before any rule is
    consulted; a leaf that no rule matches raises
    :class:`UnmatchedLeafError` naming the exact path.
    """

    rules: Tuple[Tuple[str, LogicalSpec], ...]
    axes: ShardingRules = dataclasses.field(default_factory=lambda: DEFAULT_RULES)

    def match(self, path: str) -> Optional[Tuple[str, LogicalSpec]]:
        """First ``(pattern, logical-spec)`` whose regex matches ``path``.

        A trailing ``/value`` component (the ``flax.linen.Partitioned``
        box around annotated params and their optimizer mirrors) is
        stripped first so rules name the param, not the box."""
        if path.endswith("/value"):
            path = path[: -len("/value")]
        for pattern, logical in self.rules:
            if re.search(pattern, path):
                return pattern, logical
        return None

    def spec_for(self, path: str, shape: Sequence[int]) -> PartitionSpec:
        """Resolve one leaf: scalar/size-1 -> replicated; else first
        matching rule, right-aligned onto the leaf's trailing dims."""
        shape = tuple(shape)
        if _leaf_size(shape) <= 1:
            return P()
        hit = self.match(path)
        if hit is None:
            raise UnmatchedLeafError(
                f"no partition rule matches leaf '{path}' (shape {shape}); "
                f"add a (regex, logical-spec) rule to PartitionRules"
            )
        pattern, logical = hit
        if logical is None:
            return P()
        if len(logical) > len(shape):
            raise ValueError(
                f"leaf '{path}': rule {pattern!r} names {len(logical)} "
                f"trailing dims but the array is rank {len(shape)} "
                f"(shape {shape})"
            )
        resolved = self.axes.spec(*logical)
        entries = [None] * (len(shape) - len(logical)) + list(resolved)
        return P(*entries)

    def specs_for_tree(self, tree: Any) -> Any:
        """PartitionSpec pytree for a pytree of (abstract) arrays; raises
        :class:`UnmatchedLeafError` on the first uncovered leaf."""
        def resolve(path, leaf):
            return self.spec_for(canonical_path(path), jax.numpy.shape(leaf))

        return jax.tree_util.tree_map_with_path(resolve, tree)

    def with_axes(self, axes: ShardingRules) -> "PartitionRules":
        return dataclasses.replace(self, axes=axes)

    # -- manifest round-trip ------------------------------------------------
    def table(self) -> Dict[str, MeshAxes]:
        """The logical-axis table (delegates to ``axes``) — keeps the legacy
        manifest ``rules`` stamp format stable."""
        return self.axes.table()

    def to_table(self) -> List[List[Any]]:
        """JSON-able ``[[pattern, logical-or-null], ...]`` (order preserved)."""
        return [
            [pattern, None if logical is None else list(logical)]
            for pattern, logical in self.rules
        ]

    @classmethod
    def from_table(
        cls,
        table: Sequence[Sequence[Any]],
        axes: Optional[ShardingRules] = None,
    ) -> "PartitionRules":
        rules = tuple(
            (str(pattern), None if logical is None else tuple(logical))
            for pattern, logical in table
        )
        return cls(rules=rules, axes=axes if axes is not None else DEFAULT_RULES)

    @classmethod
    def from_manifest(cls, mesh_section: Dict[str, Any]) -> "PartitionRules":
        """Rebuild from a manifest's mesh section (the inverse of the
        ``persist.integrity`` stamp): ``partition_rules`` carries the regex
        table, ``rules`` the logical-axis table."""
        axes_table = mesh_section.get("rules")
        axes = DEFAULT_RULES
        if axes_table:
            axes = ShardingRules(rules=tuple(
                (name, tuple(ax) if isinstance(ax, list) else ax)
                for name, ax in axes_table
            ))
        return cls.from_table(mesh_section["partition_rules"], axes=axes)


# The default rule vocabulary covers every model-zoo family (transformer —
# unrolled, scanned, fused-qkv, int8, LoRA —, vit, resnet, moe, seq2seq,
# lenet) with no per-model spec tables; a tier-1 lint asserts these rules
# reproduce the annotation-derived specs exactly.  Order matters: specific
# sub-leaf rules (lora/bias/scale) come before their kernel's rule only
# where patterns overlap; catch-alls for unannotated vision stacks go last.
DEFAULT_PARTITION_RULES = PartitionRules(rules=(
    # pipeline-stacked blocks (PipelinedBlocks, incl. the interleaved
    # per-stage chunked layout): every param carries a leading layer dim
    # scattered over 'stage', so these rows mirror the per-layer rules below
    # with an explicit leading 'stage' axis.  They must precede the generic
    # rows — patterns are searched and first match wins.  The interleaved
    # schedule permutes *rows* of this same layout at dispatch time
    # (``interleave_order``); checkpoints and manifests stay canonical, so
    # one rule set covers every schedule.
    (r"(^|/)pipeline/blocks/.*attn/(q|k|v|qkv)/(kernel|kernel_q)$", ("stage", "embed", "heads")),
    (r"(^|/)pipeline/blocks/.*attn/(q|k|v|qkv)/(bias|kernel_scale)$", ("stage", "heads")),
    (r"(^|/)pipeline/blocks/.*attn/o/(kernel|kernel_q)$", ("stage", "heads", "embed")),
    (r"(^|/)pipeline/blocks/.*attn/o/(bias|kernel_scale)$", ("stage", "embed")),
    (r"(^|/)pipeline/blocks/.*mlp/(gate|up)/(kernel|kernel_q)$", ("stage", "embed", "mlp")),
    (r"(^|/)pipeline/blocks/.*mlp/(gate|up)/(bias|kernel_scale)$", ("stage", "mlp")),
    (r"(^|/)pipeline/blocks/.*mlp/down/(kernel|kernel_q)$", ("stage", "mlp", "embed")),
    (r"(^|/)pipeline/blocks/.*mlp/down/(bias|kernel_scale)$", ("stage", "embed")),
    (r"(^|/)pipeline/blocks/.*(RMSNorm_\d+|LayerNorm_\d+)/scale$", ("stage", "norm")),
    (r"(^|/)pipeline/blocks/.*LayerNorm_\d+/bias$", ("stage", None)),
    # attention projections (matches attn/, self_attn/, cross_attn/)
    (r"attn/(q|k|v|qkv)/(kernel|kernel_q)$", ("embed", "heads")),
    (r"attn/(q|k|v|qkv)/(bias|kernel_scale)$", ("heads",)),
    (r"attn/(q|k|v|qkv)/lora_a$", ("embed", None)),
    (r"attn/(q|k|v|qkv)/lora_b$", (None, "heads")),
    (r"attn/o/(kernel|kernel_q)$", ("heads", "embed")),
    (r"attn/o/(bias|kernel_scale)$", ("embed",)),
    (r"attn/o/lora_a$", ("heads", None)),
    (r"attn/o/lora_b$", (None, "embed")),
    # dense mlp
    (r"mlp/(gate|up)/(kernel|kernel_q)$", ("embed", "mlp")),
    (r"mlp/(gate|up)/(bias|kernel_scale)$", ("mlp",)),
    (r"mlp/(gate|up)/lora_a$", ("embed", None)),
    (r"mlp/(gate|up)/lora_b$", (None, "mlp")),
    (r"mlp/down/(kernel|kernel_q)$", ("mlp", "embed")),
    (r"mlp/down/(bias|kernel_scale)$", ("embed",)),
    (r"mlp/down/lora_a$", ("mlp", None)),
    (r"mlp/down/lora_b$", (None, "embed")),
    # mixture-of-experts
    (r"moe/router$", ("embed", "expert")),
    (r"moe/w_up$", ("expert", "embed", "mlp")),
    (r"moe/w_down$", ("expert", "mlp", "embed")),
    (r"moe/b_up$", ("expert", "mlp")),
    # embedding / unembedding
    (r"embed/embedding(_q)?$", ("vocab", "embed")),
    (r"embed/embedding_scale$", ("vocab",)),
    (r"(^|/)head/(kernel|kernel_q)$", ("embed", "vocab")),
    (r"(^|/)head/(bias|kernel_scale)$", ("vocab",)),
    # learned positions / ViT patchify + cls (right-aligned 'embed' covers
    # the rank-2 (S, D) table and the rank-3/4 (1, S, D) / (P, P, C, D))
    (r"pos_embedding$", ("embed",)),
    (r"(^|/)cls$", ("embed",)),
    (r"patchify/(kernel|bias)$", ("embed",)),
    # norms (RMSNorm scale is annotated 'norm'; LayerNorm bias is not)
    (r"(RMSNorm_\d+|LayerNorm_\d+)/scale$", ("norm",)),
    (r"LayerNorm_\d+/bias$", None),
    # unannotated vision stacks (resnet/lenet) + plain flax defaults:
    # replicated, matching their annotation-free partition specs
    (r"(^|/)Conv_\d+/(kernel|bias)$", None),
    (r"(^|/)BatchNorm_\d+/(scale|bias|mean|var)$", None),
    (r"(^|/)Dense_\d+/(kernel|bias)$", None),
))


# ---------------------------------------------------------------------------
# ZeRO stage 1 (arXiv 2004.13336): optimizer state + the weight update are
# sharded across the data axis; the updated params are all-gathered inside
# the step.  ``zero_compose`` folds the data axis into the first dim whose
# size the combined factor divides, composing with (not replacing) whatever
# fsdp/tensor spec the leaf already has.
# ---------------------------------------------------------------------------


def zero_compose(
    spec: PartitionSpec,
    shape: Sequence[int],
    mesh: Mesh,
    axis: str = "data",
) -> PartitionSpec:
    """Fold ``axis`` into ``spec`` on the first evenly-divisible dim.

    Scalars/size-1 leaves, leaves already sharded over ``axis`` and meshes
    where ``axis`` has size 1 pass through unchanged; a leaf no dim of
    which divides stays at its base spec (still correct, just not
    ZeRO-sharded — the step's constraints are then no-ops for it)."""
    shape = tuple(shape)
    if _leaf_size(shape) <= 1 or dict(mesh.shape).get(axis, 1) <= 1:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for i, entry in enumerate(entries):
        names = (
            () if entry is None
            else (entry,) if isinstance(entry, str) else tuple(entry)
        )
        if axis in names:
            return P(*entries)
        factor = dict(mesh.shape)[axis] * int(
            math.prod([dict(mesh.shape)[n] for n in names] or [1])
        )
        if shape[i] % factor == 0:
            entries[i] = (axis,) if entry is None else tuple(names) + (axis,)
            return P(*entries)
    return P(*entries)


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    """One coherent sharding resolution for a full TrainState.

    ``state_specs``/``state_shardings`` mirror the TrainState structure;
    ``param_specs`` is the base (non-ZeRO) *compute* spec tree the
    forward/backward runs under; ``zero_param_shardings`` is the
    data-composed domain the optimizer update runs in when
    ``zero_stage >= 1`` (equal to ``param_shardings`` otherwise).  At
    ``zero_stage=3`` the params' *storage* domain
    (``state_specs.params`` / ``state_shardings.params``) is the zero
    domain too — the step all-gathers to ``param_shardings`` on demand
    and never stores the gathered replica."""

    mesh: Mesh
    rules: PartitionRules
    zero_stage: int
    param_specs: Any
    state_specs: Any
    param_shardings: Any
    zero_param_shardings: Any
    state_shardings: Any

    @property
    def opt_shardings(self) -> Any:
        return self.state_shardings.opt_state


def _is_spec_leaf(x: Any) -> bool:
    return isinstance(x, PartitionSpec)


def _zero_exempt_mask(abstract_state: Any, params_flat: Any) -> List[bool]:
    """Params whose updates are matrix-valued (Muon's Newton-Schulz runs
    norm + matmuls over the FULL matrix) must keep their entire state
    chain on the base sharding domain — slicing them over ``data`` would
    regroup the NS reductions and break bit-equality.  Detected by the
    presence of a MuonState anywhere in the optimizer state; Muon
    orthogonalizes every rank-2 leaf it sees, so every rank-2 param is
    exempt."""
    try:
        from rocket_tpu.engine.muon import MuonState
    except Exception:  # pragma: no cover - muon is part of the tree
        return [False] * len(params_flat)

    found = False

    def visit(node):
        nonlocal found
        if isinstance(node, MuonState):
            found = True
        return node

    jax.tree_util.tree_map(
        visit, abstract_state.opt_state,
        is_leaf=lambda n: isinstance(n, MuonState),
    )
    if not found:
        return [False] * len(params_flat)
    return [
        len(getattr(leaf, "shape", ())) == 2 for _, leaf in params_flat
    ]


def specs_for_state(
    mesh: Mesh,
    abstract_state: Any,
    rules: PartitionRules = DEFAULT_PARTITION_RULES,
    param_specs: Any = None,
    zero_stage: int = 0,
    make_shardings: bool = True,
) -> ShardingPlan:
    """Resolve shardings for every leaf of a TrainState from one rule table.

    Optimizer-state subtrees that are *structural mirrors* of the params
    (same treedef, same leaf shapes — Adam's mu/nu, Muon momenta, EMA
    shadows, grad-accum buffers) inherit the param specs positionally;
    non-mirror leaves fall back to scalar-replication, then the regex
    rules on their canonical path, then replication.  With
    ``zero_stage >= 1`` mirror leaves (minus matrix-update-exempt params)
    are re-partitioned over the ``data`` axis via :func:`zero_compose`;
    ``zero_stage >= 2`` moves the grad-accum buffers into the same zero
    domain (the window sum is elementwise on the shard, still exact);
    ``zero_stage=3`` stores the params themselves there — the step
    all-gathers them to the base compute domain on demand.

    ``param_specs`` overrides rule-derived param specs (the Module passes
    annotation-derived specs through here so existing models keep their
    exact layouts); when ``None`` the rules must cover every param leaf or
    :class:`UnmatchedLeafError` is raised naming the path.

    ``make_shardings=False`` skips :class:`~jax.sharding.NamedSharding`
    construction (the plan's ``*_shardings`` fields are ``None``) so the
    spec/byte arithmetic also runs against a *hypothetical* mesh — any
    object with a ``.shape`` mapping of axis sizes, e.g. a pod shape this
    host doesn't have.
    """
    if zero_stage not in ZERO_STAGES:
        raise ValueError(
            f"zero_stage must be one of {ZERO_STAGES}, got {zero_stage!r}"
        )
    params = abstract_state.params
    if param_specs is None:
        param_specs = rules.specs_for_tree(params)

    params_flat, params_td = jax.tree_util.tree_flatten_with_path(params)
    spec_leaves = jax.tree_util.tree_leaves(param_specs, is_leaf=_is_spec_leaf)
    spec_leaves = [P() if s is None else s for s in spec_leaves]
    if len(spec_leaves) != len(params_flat):
        raise ValueError(
            f"param_specs has {len(spec_leaves)} leaves for "
            f"{len(params_flat)} params"
        )
    param_shapes = [tuple(getattr(leaf, "shape", ())) for _, leaf in params_flat]

    exempt = _zero_exempt_mask(abstract_state, params_flat)
    if zero_stage >= 1:
        zero_leaves = [
            spec if exempt[i] else zero_compose(spec, param_shapes[i], mesh)
            for i, spec in enumerate(spec_leaves)
        ]
    else:
        zero_leaves = list(spec_leaves)

    param_spec_tree = jax.tree_util.tree_unflatten(params_td, spec_leaves)
    mirror_spec_tree = jax.tree_util.tree_unflatten(params_td, zero_leaves)

    def is_mirror(node: Any) -> bool:
        try:
            if jax.tree_util.tree_structure(node) != params_td:
                return False
        except Exception:
            return False
        leaves = jax.tree_util.tree_leaves(node)
        return all(
            tuple(getattr(leaf, "shape", ())) == shape
            for leaf, shape in zip(leaves, param_shapes)
        )

    def fallback_spec(path, leaf) -> PartitionSpec:
        shape = tuple(getattr(leaf, "shape", ()))
        if _leaf_size(shape) <= 1:
            return P()
        hit = rules.match(canonical_path(path))
        if hit is not None:
            try:
                return rules.spec_for(canonical_path(path), shape)
            except ValueError:
                return P()
        return P()

    def resolve_collection(tree: Any, mirror_specs: Any) -> Any:
        """Spec tree for ``tree``: params-shaped subtrees take
        ``mirror_specs`` wholesale; other leaves fall back per-path."""
        if tree is None:
            return None
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda n: is_mirror(n)
        )
        out = []
        for path, node in flat:
            if is_mirror(node):
                out.append(mirror_specs)
            else:
                out.append(fallback_spec(path, node))
        return jax.tree_util.tree_unflatten(treedef, out)

    state_specs = abstract_state.replace(
        step=P(),
        # Stage 3: the params' STORAGE domain is the zero shard — the step
        # all-gathers to the base compute domain on demand, so no full
        # replica persists between steps.
        params=mirror_spec_tree if zero_stage >= 3 else param_spec_tree,
        opt_state=resolve_collection(abstract_state.opt_state, mirror_spec_tree),
        rng=P(),
        mutable=resolve_collection(abstract_state.mutable, param_spec_tree),
        # Stage 2+: accumulation buffers live on the zero shard too — the
        # micro-sum is elementwise on the shard (exact) and gradients
        # reduce-scatter straight into it.
        grad_accum=resolve_collection(
            abstract_state.grad_accum,
            mirror_spec_tree if zero_stage >= 2 else param_spec_tree,
        ),
        micro=None if abstract_state.micro is None else P(),
    )

    if not make_shardings:
        return ShardingPlan(
            mesh=mesh,
            rules=rules,
            zero_stage=zero_stage,
            param_specs=param_spec_tree,
            state_specs=state_specs,
            param_shardings=None,
            zero_param_shardings=None,
            state_shardings=None,
        )

    to_sharding = lambda spec: NamedSharding(mesh, spec)
    as_shardings = lambda specs: jax.tree_util.tree_map(
        to_sharding, specs, is_leaf=_is_spec_leaf
    )
    return ShardingPlan(
        mesh=mesh,
        rules=rules,
        zero_stage=zero_stage,
        param_specs=param_spec_tree,
        state_specs=state_specs,
        param_shardings=as_shardings(param_spec_tree),
        zero_param_shardings=as_shardings(mirror_spec_tree),
        state_shardings=as_shardings(state_specs),
    )
