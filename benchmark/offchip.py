"""Compile the cells' programs for a described TPU, with no chip attached.

libtpu's compiler runs in the sandbox, so XLA and Mosaic refuse here what
they would refuse on the chip (memory, tiling), and ``memory_analysis()``
says what one program needs.  Nothing runs: no time, no result.  Used by one
test file (inside a fixture) and by hand before chip time is spent.
"""

from __future__ import annotations

import contextlib
from typing import Dict

from benchmark import harness
from benchmark.kinds import train as train_kind


def topology_device(name: str = "v5e:2x2"):
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name=name).devices[0]


@contextlib.contextmanager
def mosaic_kernels():
    """Make the Pallas kernels lower to Mosaic although JAX's default
    backend here is the CPU (the program asks ``jax.default_backend()``)."""
    from rocket_tpu.ops import flash

    old = flash._interpret
    flash._interpret = lambda: False
    try:
        yield
    finally:
        flash._interpret = old


def _on(device, tree):
    import jax
    from jax.sharding import SingleDeviceSharding

    sharding = SingleDeviceSharding(device)
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def memory(compiled) -> Dict[str, int]:
    m = compiled.memory_analysis()
    out = {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes")}
    out["total_bytes"] = (out["argument_size_in_bytes"]
                          + out["output_size_in_bytes"]
                          + out["temp_size_in_bytes"]
                          - out["alias_size_in_bytes"])
    return out


def compile_train_step(cell: harness.Cell, device, batch: int = None):
    """The donated train step the ``train`` kind's Module would build, at
    the cell's sizes, compiled for ``device``."""
    import jax
    import jax.numpy as jnp
    import optax

    from rocket_tpu.engine.adapter import FlaxModel
    from rocket_tpu.engine.precision import Policy
    from rocket_tpu.engine.state import TrainState
    from rocket_tpu.engine.step import Objective, build_train_step
    from rocket_tpu.models.objectives import lm_cross_entropy

    mix = cell.traffic
    opt = mix["optimizer"]
    batch = int(batch or mix["batch"])
    adapter = FlaxModel(train_kind.program(cell, attention="flash"))
    policy = Policy.from_string(mix["mixed_precision"])
    tx = optax.chain(
        optax.clip_by_global_norm(opt["clip_norm"]),
        optax.adamw(optax.warmup_cosine_decay_schedule(
            opt["lr_init"], opt["lr_peak"], opt["warmup_steps"],
            opt["decay_steps"], opt["lr_end"]),
            b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
            weight_decay=opt["weight_decay"]))
    tokens = jax.ShapeDtypeStruct((batch, int(mix["seq"])), jnp.int32)
    abstract_batch = {"tokens": tokens,
                      "_valid": jax.ShapeDtypeStruct((batch,), jnp.bool_)}

    def init():
        rng = jax.random.PRNGKey(0)
        sample = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), abstract_batch)
        params, mutable = adapter.init_variables(rng, sample)
        return TrainState.create(policy.cast_to_param(params), tx, rng=rng,
                                 mutable=mutable,
                                 gradient_accumulation_steps=1)

    with mosaic_kernels():
        state = jax.eval_shape(init)
        steps = build_train_step(
            adapter.apply_fn, [Objective("lm", lm_cross_entropy())], tx,
            policy=policy, donate=True)
        step = getattr(steps["sync"], "jitted", steps["sync"])
        lowered = step.lower(_on(device, state), _on(device, abstract_batch))
        return lowered.compile()


def _serving_models(cell: harness.Cell):
    """Target and draft as ``ContinuousBatcher`` runs them: with per-row
    cache frontiers."""
    import dataclasses

    from benchmark.kinds import serving

    model, draft, params, draft_params = serving.program_models(cell)
    per_row = lambda m: type(m)(  # noqa: E731
        dataclasses.replace(m.config, decode_per_row=True))
    return per_row(model), per_row(draft), params, draft_params


GREEDY = dict(eos_token=None, sampled=False, top_k=None, top_p=None)


def _serving_state(cell: harness.Cell):
    """The program's modules, abstract parameters and the abstract round
    state of the cell's rows (caches included)."""
    import jax
    import jax.numpy as jnp

    import importlib

    # the module, not the function of that name the package re-exports
    generate = importlib.import_module("rocket_tpu.models.generate")
    model, draft, params, draft_params = _serving_models(cell)
    serving = cell.config["serving"]
    rows, total = int(serving["rows"]), int(serving["total_len"])

    def prefill(p, dp, prompt):
        return generate._spec_prefill_impl(
            model, draft, p, dp, prompt, None, 0.0,
            max_new_tokens=total - 1, **GREEDY)

    state = jax.eval_shape(prefill, params, draft_params,
                           jax.ShapeDtypeStruct((rows, 1), jnp.int32))
    return generate, model, draft, params, draft_params, state


def compile_spec_round(cell: harness.Cell, device):
    """One ``_spec_round`` over the cell's rows, compiled for ``device``."""
    generate, model, draft, params, draft_params, state = _serving_state(cell)
    n_draft = int(cell.config["serving"]["n_draft"])
    lowered = generate._spec_round.lower(
        model, draft, _on(device, params), _on(device, draft_params),
        _on(device, state), 0.0, n_draft=n_draft, **GREEDY)
    return lowered.compile()


def compile_spec_admit(cell: harness.Cell, device, prompt_len: int):
    """One ``_spec_admit`` of a prompt of ``prompt_len`` tokens."""
    import jax
    import jax.numpy as jnp

    generate, model, draft, params, draft_params, state = _serving_state(cell)
    sds = jax.ShapeDtypeStruct
    lowered = generate._spec_admit.lower(
        model, draft, _on(device, params), _on(device, draft_params),
        _on(device, state), _on(device, sds((), jnp.int32)),
        _on(device, sds((1, prompt_len), jnp.int32)),
        _on(device, sds((2,), jnp.uint32)), 0.0, **GREEDY)
    return lowered.compile()
