"""Ladder-config structural smoke tests (VERDICT r1 item 9).

The BASELINE.json ladder's big configs (Llama-2 7B, ViT-B/16, ResNet-50)
can't run for real on CI hardware, but their shapes and sharding plans can:
``jax.eval_shape`` traces the full init at zero memory cost, and the
adapter's partition-spec resolution is exactly what materialization uses —
so wrong param counts or accidentally-replicated 7B weight matrices fail
here, long before a pod run.
"""

import jax
import jax.numpy as jnp
import pytest

import rocket_tpu as rt
from rocket_tpu.engine.adapter import FlaxModel
from rocket_tpu.models.resnet import resnet50
from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
from rocket_tpu.models.vit import ViT, ViTConfig
from rocket_tpu.parallel.mesh import MeshSpec


def _abstract_plan(model, batch_spec, mesh_spec, devices):
    """(abstract_params, resolved PartitionSpecs, param_count) without
    allocating anything."""
    runtime = rt.Runtime(mesh=mesh_spec.build(devices))
    adapter = FlaxModel(model)
    adapter.configure(runtime.mesh, runtime.rules)

    def init_fn():
        batch = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), batch_spec
        )
        params, _ = adapter.init_variables(jax.random.PRNGKey(0), batch)
        return params

    abstract = jax.eval_shape(init_fn)
    specs = adapter.partition_specs(abstract, runtime.rules)
    count = sum(
        int(leaf.size) for leaf in jax.tree_util.tree_leaves(abstract)
    )
    return abstract, specs, count


def _spec_axes(specs):
    axes = set()
    for spec in jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
    ):
        for part in spec:
            if part is None:
                continue
            parts = part if isinstance(part, tuple) else (part,)
            axes.update(parts)
    return axes


def test_llama2_7b_shape_and_sharding_plan(devices):
    """7B config: correct param count and fsdp x tensor sharded big matrices
    on an 8-device mesh (the BASELINE 'Llama-2 7B LoRA (GSPMD, v4-32)'
    config, structurally)."""
    cfg = TransformerConfig.llama2_7b(scan_layers=True)
    batch_spec = {"tokens": jax.ShapeDtypeStruct((8, 4096), jnp.int32)}
    abstract, specs, count = _abstract_plan(
        TransformerLM(cfg), batch_spec, MeshSpec(fsdp=4, tensor=2), devices
    )
    assert 6.5e9 < count < 7.0e9, f"param count {count:,}"
    axes = _spec_axes(specs)
    assert "fsdp" in axes and "tensor" in axes, axes
    # every big (>= hidden^2) matrix must be sharded, not replicated
    flat_specs = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
    )
    flat_shapes = jax.tree_util.tree_leaves(abstract)
    for leaf, spec in zip(flat_shapes, flat_specs):
        if leaf.size >= cfg.hidden * cfg.hidden:
            assert any(axis is not None for axis in spec), (
                f"{leaf.shape} is replicated"
            )


def test_llama2_7b_lora_plan(devices):
    """LoRA variant: adapters exist, base count grows only by the low-rank
    terms (the 'Llama-2 7B LoRA' ladder config)."""
    cfg = TransformerConfig.llama2_7b(scan_layers=True, lora_rank=8)
    batch_spec = {"tokens": jax.ShapeDtypeStruct((4, 512), jnp.int32)}
    _, specs, count = _abstract_plan(
        TransformerLM(cfg), batch_spec, MeshSpec(fsdp=4, tensor=2), devices
    )
    assert 6.5e9 < count < 7.1e9
    paths = [
        jax.tree_util.keystr(p)
        for p, _ in jax.tree_util.tree_leaves_with_path(specs)
    ]
    assert any("lora_a" in p for p in paths) and any(
        "lora_b" in p for p in paths
    )


def test_vit_b16_shape_plan(devices):
    """ViT-B/16: ~86M params; encoder matrices carry the transformer
    sharding axes (the 'ViT-B/16 ImageNet bf16' ladder config)."""
    cfg = ViTConfig.b16()
    batch_spec = {"image": jax.ShapeDtypeStruct((8, 224, 224, 3), jnp.float32)}
    _, specs, count = _abstract_plan(
        ViT(cfg), batch_spec, MeshSpec(data=2, fsdp=2, tensor=2), devices
    )
    assert 85e6 < count < 88e6, f"param count {count:,}"
    axes = _spec_axes(specs)
    assert "tensor" in axes or "fsdp" in axes, axes


def test_resnet50_shape_plan(devices):
    """ResNet-50: ~25.6M params; CNNs are data-parallel by design (SURVEY
    §2.2 DDP contract) — params replicated, batch sharded."""
    batch_spec = {"image": jax.ShapeDtypeStruct((8, 224, 224, 3), jnp.float32)}
    _, specs, count = _abstract_plan(
        resnet50(), batch_spec, MeshSpec(data=8), devices
    )
    assert 25.0e6 < count < 26.5e6, f"param count {count:,}"
    assert _spec_axes(specs) == set()  # replicated = the documented contract


def test_gpt2_124m_fused_bench_layout_plan(devices):
    """GPT-2 124M with fused_qkv + fused_ce and a padded vocabulary
    (a layout no benchmark cell runs) at REAL scale: correct param count
    and a clean sharding plan, traced at zero memory cost."""
    cfg = TransformerConfig.gpt2_124m(
        vocab_size=50304, fused_qkv=True, fused_ce=True,
        attention_block_q=512, attention_block_k=1024,
    )
    batch_spec = {"tokens": jax.ShapeDtypeStruct((16, 1024), jnp.int32)}
    abstract, specs, count = _abstract_plan(
        TransformerLM(cfg), batch_spec, MeshSpec(data=4, tensor=2), devices
    )
    # 124M-class: tied embed (50304*768) + pos + 12 blocks
    assert 1.2e8 < count < 1.3e8, f"param count {count:,}"
    paths = [
        jax.tree_util.keystr(p)
        for p, _ in jax.tree_util.tree_leaves_with_path(specs)
    ]
    assert any("qkv" in p for p in paths), paths[:8]  # fused projection
    assert not any("'head'" in p for p in paths)      # tied — no extra head


def test_llama2_7b_full_finetune_zero1_fits_v4_hbm(devices):
    """7B FULL finetune (non-LoRA) Adam on a pure data(8) mesh: replicated
    optimizer state provably does NOT fit a v4 chip (bf16 params 13.4GB +
    bf16 Adam mu/nu 26.9GB ≈ 40GB of arguments > 32GB HBM), while
    ``zero_stage=1`` re-partitions the moments over the data axis and the
    AOT-compiled step fits.  Both plans come from the same
    :func:`specs_for_state` call — this is the ladder config ZeRO exists
    for (arXiv 2004.13336 §4: ZeRO-1 fits 7.5B on 32GB where DDP cannot).
    """
    import optax

    from rocket_tpu.engine.precision import Policy
    from rocket_tpu.engine.state import TrainState, memory_plan
    from rocket_tpu.engine.step import Objective, build_train_step
    from rocket_tpu.models.objectives import lm_cross_entropy
    from rocket_tpu.parallel.sharding import batch_sharding, specs_for_state

    B, S = 8, 1024
    cfg = TransformerConfig.llama2_7b(
        scan_layers=True, remat=True, attention="flash"
    )
    runtime = rt.Runtime(mesh=MeshSpec(data=8).build(devices))
    mesh = runtime.mesh
    policy = Policy.from_string("bf16_full")
    adapter = FlaxModel(TransformerLM(cfg))
    adapter.configure(mesh, runtime.rules)
    adapter.apply_policy(policy)
    batch_struct = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    tx = optax.adamw(1e-5)

    def init_fn():
        batch = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), batch_struct
        )
        params, mutable = adapter.init_variables(jax.random.PRNGKey(0), batch)
        params = policy.cast_to_param(params)
        return TrainState.create(
            params, tx, rng=jax.random.PRNGKey(0), mutable=mutable
        )

    abstract_state = jax.eval_shape(init_fn)
    param_specs = adapter.partition_specs(abstract_state.params, runtime.rules)
    GB = 1 << 30

    # The replicated plan: assert analytically (via the memory plan — no
    # point compiling a program we know cannot fit) that per-device
    # ARGUMENTS alone exceed the 32GB v4 envelope.
    repl = specs_for_state(
        mesh, abstract_state, param_specs=param_specs, zero_stage=0
    )
    repl_mem = memory_plan(abstract_state, repl.state_specs, mesh)
    assert repl_mem["param_bytes"] / GB > 12.0   # bf16 7B ≈ 13.4GB
    assert repl_mem["opt_bytes"] / GB > 24.0     # mu + nu ≈ 2x params
    assert repl_mem["total_bytes"] / GB > 32.0, (
        f"replicated plan only needs "
        f"{repl_mem['total_bytes'] / GB:.1f} GB/device — the ZeRO test "
        f"config no longer demonstrates anything"
    )

    # The ZeRO-1 plan from the SAME rule table: optimizer mirrors fold
    # the 8-way data axis; compile for real and check the envelope.
    plan = specs_for_state(
        mesh, abstract_state, param_specs=param_specs, zero_stage=1
    )
    zero_mem = memory_plan(abstract_state, plan.state_specs, mesh)
    assert zero_mem["opt_bytes"] <= repl_mem["opt_bytes"] / 8 + 1024
    assert zero_mem["total_bytes"] / GB < 18.0

    state_structs = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        abstract_state,
        plan.state_shardings,
    )
    batch_structs = {
        "tokens": jax.ShapeDtypeStruct(
            (B, S), jnp.int32, sharding=batch_sharding(mesh, 2)
        )
    }
    steps = build_train_step(
        adapter.apply_fn,
        [Objective("lm", lm_cross_entropy())],
        tx,
        policy=policy,
        donate=True,
        shard_plan=plan,
    )
    compiled = steps["sync"].lower(state_structs, batch_structs).compile()
    ma = compiled.memory_analysis()
    args_gb = ma.argument_size_in_bytes / GB
    temp_gb = ma.temp_size_in_bytes / GB
    assert ma.alias_size_in_bytes > 0.9 * ma.output_size_in_bytes
    # arguments: 12.6GB bf16 params + 25.1/8 ≈ 3.1GB moments ≈ 15.7GB —
    # the number the sharding plan commands, asserted un-fudged.
    assert 14.0 < args_gb < 19.0, f"arguments {args_gb:.2f} GB/device"
    # Steady state: the CPU SPMD partitioner materializes two param-sized
    # STAGING buffers that TPU GSPMD does not pay for — the identity
    # grads→base-sharding pin becomes a full reshard copy (ablating that
    # one constraint drops temps by exactly params−shard bytes), and the
    # updated-params all-gather stages into a temp instead of writing the
    # donation-aliased output buffer.  Discount both; what remains is the
    # real ZeRO-1 footprint (params + opt shard args, one grads temp,
    # activations) that the v4 envelope must cover.
    # params are data-replicated, so per-device param bytes = full params
    param_gb = zero_mem["param_bytes"] / GB
    steady_gb = args_gb + temp_gb - 2 * param_gb
    assert steady_gb < 32.0, (
        f"per-device steady state {steady_gb:.2f} GB (after discounting "
        f"2x{param_gb:.1f} GB CPU-partitioner staging copies) exceeds the "
        f"v4 HBM envelope — ZeRO-1 is supposed to make this config fit"
    )
    # and the temps themselves must stay param-scale (grads + 2 staging
    # copies + activations) — catches an accidental extra full-size copy
    assert temp_gb < 3 * param_gb + 4.0, f"temps {temp_gb:.2f} GB/device"


@pytest.mark.slow
def test_llama2_7b_lora_aot_memory_fits_v4_hbm(devices):
    """AOT-compile (not just eval_shape) the REAL 7B LoRA train step —
    flash attention, remat, scanned layers, bf16 compute — on an
    fsdp(4) x tensor(2) mesh and check the compiled per-device memory
    against a v4 chip's 32GB HBM (VERDICT r3 next #3).

    XLA's memory analysis is per-device under SPMD; with the donated
    state aliasing outputs onto arguments, steady-state per-device use is
    arguments + temps.  The CPU backend models neither TPU tile padding
    nor Mosaic scratch, so this is an ESTIMATE of the TPU footprint, not
    a bound in either direction — the assertion leaves 9GB of headroom
    against the v4 envelope for exactly that reason (ladder config
    'Llama-2 7B LoRA (GSPMD, v4-32)').
    """
    import optax

    from rocket_tpu.engine.precision import Policy
    from rocket_tpu.engine.state import TrainState
    from rocket_tpu.engine.step import Objective, build_train_step
    from rocket_tpu.engine.adapter import state_shardings
    from rocket_tpu.models.lora import freeze_non_lora
    from rocket_tpu.models.objectives import lm_cross_entropy
    from rocket_tpu.parallel.sharding import batch_sharding

    B, S = 8, 4096
    cfg = TransformerConfig.llama2_7b(
        lora_rank=8, scan_layers=True, remat=True, attention="flash"
    )
    runtime = rt.Runtime(mesh=MeshSpec(fsdp=4, tensor=2).build(devices))
    mesh = runtime.mesh
    policy = Policy.from_string("bf16")
    adapter = FlaxModel(TransformerLM(cfg))
    adapter.configure(mesh, runtime.rules)
    adapter.apply_policy(policy)
    batch_struct = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    tx = freeze_non_lora(optax.adamw(1e-4))

    def init_fn():
        batch = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), batch_struct
        )
        params, mutable = adapter.init_variables(jax.random.PRNGKey(0), batch)
        params = policy.cast_to_param(params)
        return TrainState.create(
            params, tx, rng=jax.random.PRNGKey(0), mutable=mutable
        )

    abstract_state = jax.eval_shape(init_fn)
    param_specs = adapter.partition_specs(abstract_state.params, runtime.rules)
    shardings = state_shardings(mesh, abstract_state, param_specs)
    state_structs = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        abstract_state,
        shardings,
    )
    batch_structs = {
        "tokens": jax.ShapeDtypeStruct(
            (B, S), jnp.int32, sharding=batch_sharding(mesh, 2)
        )
    }
    steps = build_train_step(
        adapter.apply_fn,
        [Objective("lm", lm_cross_entropy())],
        tx,
        policy=policy,
        donate=True,
    )
    compiled = steps["sync"].lower(state_structs, batch_structs).compile()
    ma = compiled.memory_analysis()
    GB = 1 << 30
    args_gb = ma.argument_size_in_bytes / GB
    temp_gb = ma.temp_size_in_bytes / GB
    # donation: outputs alias arguments, so they don't add
    assert ma.alias_size_in_bytes > 0.9 * ma.output_size_in_bytes
    steady_gb = args_gb + temp_gb
    # fp32 master params ~27GB sharded 8 ways -> ~3.4GB/device; LoRA-only
    # adamw moments add noise-level bytes.  Catch accidental replication.
    assert 2.5 < args_gb < 5.0, f"arguments {args_gb:.2f} GB/device"
    assert steady_gb < 30.0, (
        f"per-device steady state {steady_gb:.2f} GB exceeds the v4 HBM "
        f"envelope (32GB - headroom)"
    )


def test_mistral_7b_swa_aot_memory_fits_v4_hbm(devices):
    """AOT-compile the REAL Mistral-7B LoRA train step — GQA(8),
    sliding-window 4096 at seq 8192 (the flash kernel skips
    out-of-window blocks), scanned layers, remat, bf16 — on an
    fsdp(4) x tensor(2) mesh and check per-device memory against the
    v4 envelope, same method and caveats as the Llama-2 test above.
    This is the new-family counterpart: the window path must survive
    scan + remat + GSPMD at 7B scale, not just the unit tests."""
    import optax

    from rocket_tpu.engine.precision import Policy
    from rocket_tpu.engine.state import TrainState
    from rocket_tpu.engine.step import Objective, build_train_step
    from rocket_tpu.engine.adapter import state_shardings
    from rocket_tpu.models.lora import freeze_non_lora
    from rocket_tpu.models.objectives import lm_cross_entropy
    from rocket_tpu.parallel.sharding import batch_sharding

    B, S = 4, 8192
    cfg = TransformerConfig.mistral_7b(
        lora_rank=8, scan_layers=True, remat=True, attention="flash"
    )
    assert cfg.attention_window == 4096  # the windowed path is the point
    runtime = rt.Runtime(mesh=MeshSpec(fsdp=4, tensor=2).build(devices))
    mesh = runtime.mesh
    policy = Policy.from_string("bf16")
    adapter = FlaxModel(TransformerLM(cfg))
    adapter.configure(mesh, runtime.rules)
    adapter.apply_policy(policy)
    batch_struct = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    tx = freeze_non_lora(optax.adamw(1e-4))

    def init_fn():
        batch = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), batch_struct
        )
        params, mutable = adapter.init_variables(jax.random.PRNGKey(0), batch)
        params = policy.cast_to_param(params)
        return TrainState.create(
            params, tx, rng=jax.random.PRNGKey(0), mutable=mutable
        )

    abstract_state = jax.eval_shape(init_fn)
    param_specs = adapter.partition_specs(abstract_state.params, runtime.rules)
    shardings = state_shardings(mesh, abstract_state, param_specs)
    state_structs = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        abstract_state,
        shardings,
    )
    batch_structs = {
        "tokens": jax.ShapeDtypeStruct(
            (B, S), jnp.int32, sharding=batch_sharding(mesh, 2)
        )
    }
    steps = build_train_step(
        adapter.apply_fn,
        [Objective("lm", lm_cross_entropy())],
        tx,
        policy=policy,
        donate=True,
    )
    compiled = steps["sync"].lower(state_structs, batch_structs).compile()
    ma = compiled.memory_analysis()
    GB = 1 << 30
    args_gb = ma.argument_size_in_bytes / GB
    temp_gb = ma.temp_size_in_bytes / GB
    assert ma.alias_size_in_bytes > 0.9 * ma.output_size_in_bytes
    steady_gb = args_gb + temp_gb
    assert 2.5 < args_gb < 5.0, f"arguments {args_gb:.2f} GB/device"
    assert steady_gb < 30.0, (
        f"per-device steady state {steady_gb:.2f} GB exceeds the v4 HBM "
        f"envelope (32GB - headroom)"
    )
