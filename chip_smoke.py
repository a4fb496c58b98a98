"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one run, no benchmark: it drives the two main paths through
the entry points a user calls, at the full width of GPT-2 124M, on whatever
TPU chips this process sees, and fails on the first thing that is wrong.

1. kernels — every Pallas kernel the repo ships, compiled by Mosaic (not
   interpreted) at the shapes its models use, against the plain
   ``jax.numpy`` path on the same chip;
2. trainer — ``Launcher -> Looper -> Dataset -> Module -> Tracker ->
   Checkpointer`` as ``examples/train_gpt2.py`` builds it, donation live,
   one checkpoint landing between steps, the loss going down;
3. serving — ``ServingLoop`` over a ``ContinuousBatcher`` (same model, a
   2-layer draft) answering 8 requests, one of them compared token for
   token with ``generate()``.

Refuses anything but a TPU (no CPU mode, no flag to skip that).  The last
line of stdout is the result as one JSON object; on any failure the process
exits non-zero before printing it.  Every time it prints is a smoke
observation of one run, compilation included — not a benchmark metric.

    python chip_smoke.py [--mesh data=2,tensor=2]
"""

import argparse
import gc
import glob
import json
import logging
import os
import re
import shutil
import sys
import tempfile
import time

import jax

# GPT-2 124M at the widths of its published config, 16 rows of 1024.
VOCAB, BATCH, SEQ = 50304, 16, 1024
TRAIN_STEPS, SAVE_EVERY = 12, 5
SERVE_BATCH, SERVE_TOTAL_LEN, SERVE_N_DRAFT, SERVE_NEW = 8, 256, 4, 32
SERVE_PROMPT_LENS = (32, 64, 96, 128, 32, 64, 96, 128)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--mesh", default=None, metavar="AXIS=N,...",
        help="MeshSpec of the trainer phase (default: the trivial mesh on "
             "one chip, fsdp=2 with data filling the rest on several)")
    args = parser.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{devices[0].platform!r} ({devices[0].device_kind}); "
              f"no phase ran", file=sys.stderr)
        return 2

    from importlib import metadata

    import jaxlib

    from rocket_tpu.observe import trace
    from rocket_tpu.tune import compile_cache

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:  # shipped under another name
        libtpu = "unknown"
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"chip_smoke: {device['count']} x {device['kind']}; jax "
          f"{jax.__version__}, jaxlib {jaxlib.__version__}, libtpu {libtpu}; "
          f"compile cache {compile_cache.enable_compile_cache()}", flush=True)
    # Fallback reroutes are counted on the tracer at trace time.
    tracer = trace.arm(1 << 16)

    mesh_spec = parse_mesh(args.mesh, len(devices))
    run_phase("kernels", kernel_phase, tracer)
    run_phase("trainer", trainer_phase, tracer, gpt2_config(), mesh_spec)
    run_phase("serving", serving_phase, gpt2_config(),
              gpt2_config(n_layers=2))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def run_phase(name, fn, *args) -> None:
    """Run one phase; print its wall time (compilation included), what the
    compile cache saw during it, and the chip's peak memory after it."""
    from rocket_tpu.tune import compile_cache

    before = compile_cache.snapshot()
    t0 = time.perf_counter()
    detail = fn(*args)
    wall = time.perf_counter() - t0
    after = compile_cache.snapshot()
    stats = jax.devices()[0].memory_stats() or {}
    print(f"chip_smoke[{name}]: ok in {wall:.1f}s; compile cache "
          f"requests {after['requests'] - before['requests']:.0f} hits "
          f"{after['hits'] - before['hits']:.0f}; peak HBM "
          f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB; {detail}",
          flush=True)


def parse_mesh(text, n_devices):
    from rocket_tpu.parallel.mesh import MeshSpec

    if text:
        return MeshSpec(**{k: int(v) for k, v in
                           (item.split("=") for item in text.split(","))})
    if n_devices == 1:
        return None
    # fsdp=2, data fills the rest: both the gradient reduction and the
    # parameter all-gather cross ICI.
    return MeshSpec(fsdp=2)


def gpt2_config(**kw):
    from rocket_tpu.models.transformer import TransformerConfig

    return TransformerConfig.gpt2_124m(
        vocab_size=VOCAB, attention="auto", **kw)


def fallback_counts(tracer) -> dict:
    names = ("attention/flash/fallback", "quant/int8_matmul/fallback")
    return {n: sum(1 for e in tracer.events() if e[1] == n) for n in names}


# -- phase 1: kernels ---------------------------------------------------------


def close(got, want, what, tol=3e-2) -> float:
    """Max error relative to the reference's largest magnitude, within a
    bf16 tolerance (bf16 keeps 8 bits: products round at 2**-8, and the two
    paths round in different orders)."""
    import jax.numpy as jnp

    got, want = (jnp.asarray(a, jnp.float32) for a in (got, want))
    assert got.shape == want.shape, (what, got.shape, want.shape)
    assert bool(jnp.isfinite(got).all()), f"{what}: non-finite values"
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err <= tol, f"{what}: relative error {err:.4f} > {tol}"
    return err


def flash_case(name, shape, *, causal, window=None, segments=False):
    """Flash forward and backward (dq and dkv both compile) against
    ``dot_attention`` on the same inputs."""
    import jax.numpy as jnp
    import numpy as np

    from rocket_tpu.ops.attention import dot_attention
    from rocket_tpu.ops.flash import flash_attention

    B, S, H, D = shape
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v = (jax.random.normal(key, shape, jnp.bfloat16)
               for key in (kq, kk, kv))
    weight = jax.random.normal(kw, shape, jnp.float32)
    seg = None
    if segments:
        # four packed documents of uneven length per row
        cuts = np.sort(np.random.default_rng(0).integers(
            1, S, size=(B, 3)), axis=1)
        seg = jnp.asarray(
            (np.arange(S)[None, :, None] >= cuts[:, None, :]).sum(-1),
            jnp.int32)

    def run(attention):
        def loss(q, k, v):
            out = attention(q, k, v, causal=causal, window=window,
                            segment_ids=seg)
            return jnp.sum(out.astype(jnp.float32) * weight), out

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    kernel = run(flash_attention)
    assert "tpu_custom_call" in kernel.lower(q, k, v).as_text(), (
        f"{name}: no Mosaic custom call in the lowered program — the "
        f"kernel was interpreted or rerouted")
    (_, out), grads = kernel(q, k, v)
    (_, ref_out), ref_grads = run(dot_attention)(q, k, v)
    errs = [close(out, ref_out, f"{name} out")]
    errs += [close(g, r, f"{name} d{n}")
             for g, r, n in zip(grads, ref_grads, "qkv")]
    return f"{name} {max(errs):.4f}"


def int8_case(K, N, *, nk_layout=False):
    """``int8_matmul`` at a decode-shaped M=8 against ``x @ dequant(q)``."""
    import jax.numpy as jnp

    from rocket_tpu.ops.quant import (
        dequantize_int8,
        int8_matmul,
        quantize_int8,
    )

    name = f"int8 {K}->{N}" + (" nk" if nk_layout else "")
    kx, kw = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (8, K), jnp.bfloat16)
    w = jax.random.normal(kw, (N, K) if nk_layout else (K, N), jnp.float32)
    q, scale = quantize_int8(w, axis=1 if nk_layout else 0)
    kernel = jax.jit(lambda x, q, s: int8_matmul(
        x, q, s, nk_layout=nk_layout))
    assert "tpu_custom_call" in kernel.lower(x, q, scale).as_text(), (
        f"{name}: no Mosaic custom call in the lowered program")
    deq = dequantize_int8(q, scale, axis=1 if nk_layout else 0,
                          dtype=jnp.bfloat16)
    ref = x @ (deq.T if nk_layout else deq)
    return f"{name} {close(kernel(x, q, scale), ref, name):.4f}"


def kernel_phase(tracer) -> str:
    gpt2 = (BATCH, SEQ, 12, 64)     # GPT-2 124M: 12 heads of 64
    results = [
        flash_case("flash gpt2 causal", gpt2, causal=True),
        flash_case("flash gpt2 segments", gpt2, causal=True, segments=True),
        flash_case("flash gpt2 window256", gpt2, causal=True, window=256),
        flash_case("flash vit-b16", (64, 197, 12, 64), causal=False),
        int8_case(768, 3072),
        int8_case(3072, 768),
        int8_case(768, VOCAB, nk_layout=True),   # the tied unembed
    ]
    counts = fallback_counts(tracer)
    assert not any(counts.values()), f"kernel fallbacks counted: {counts}"
    return "max rel err vs jnp: " + ", ".join(results)


# -- phase 2: trainer ---------------------------------------------------------


def trainer_phase(tracer, cfg, mesh_spec, *, batch=BATCH, seq=SEQ,
                  steps=TRAIN_STEPS, save_every=SAVE_EVERY) -> str:
    import optax

    import rocket_tpu as rt
    from rocket_tpu.data.toys import synthetic_lm_tokens
    from rocket_tpu.models.objectives import lm_cross_entropy
    from rocket_tpu.models.transformer import TransformerLM
    from rocket_tpu.persist import integrity

    class StepProbe(rt.Capsule):
        """Keeps the jitted step the Module trained with (the Module drops
        it at teardown) and the layout of a batch it was fed."""

        steps = batch = None

        def launch(self, attrs=None):
            if self.steps is None and attrs.batch is not None:
                self.steps = module._steps
                self.batch = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(
                        x.shape, x.dtype, sharding=x.sharding), attrs.batch)

    data = synthetic_lm_tokens(n_docs=batch * steps, seq_len=seq, vocab=512)
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0, peak_value=3e-4, warmup_steps=3,
        decay_steps=steps, end_value=3e-5,
    )
    module = rt.Module(
        TransformerLM(cfg),
        capsules=[
            rt.Loss(lm_cross_entropy(), name="lm"),
            rt.Optimizer(tx_factory=optax.adamw, learning_rate=3e-4,
                         grad_clip_norm=1.0, weight_decay=0.1),
            rt.Scheduler(schedule),
        ],
    )
    # Nothing is written into the tracked tree: the project lives and dies
    # under a temporary directory.
    root = tempfile.mkdtemp(prefix="chip_smoke_")
    probe = StepProbe()
    try:
        launcher = rt.Launcher(
            capsules=[rt.Looper(capsules=[
                rt.Dataset(rt.ArraySource(data), batch_size=batch,
                           shuffle=True),
                module,
                probe,
                rt.Tracker("jsonl"),
                rt.Checkpointer(save_every=save_every),
            ])],
            tag="smoke", num_epochs=1, mesh=mesh_spec,
            mixed_precision="bf16", project_root=root,
        )
        launcher.launch()

        assert module.step == steps, (module.step, steps)
        project = os.path.join(root, "smoke", "v0")
        with open(os.path.join(project, "logs", "metrics.jsonl")) as fh:
            losses = [rec["losses/lm"] for rec in map(json.loads, fh)
                      if "losses/lm" in rec]
        assert len(losses) == steps, (len(losses), steps)
        assert all(x == x and abs(x) != float("inf") for x in losses), losses
        assert losses[-1] < losses[0], losses

        # save_every < steps: each save's write drains under the donated
        # steps that follow it.  deep=True re-reads every saved leaf and
        # re-computes its crc32 — a buffer donated away mid-save shows here.
        snapshots = sorted(glob.glob(os.path.join(project, "weights", "*")))
        assert len(snapshots) == steps // save_every, snapshots
        # orbax warns once per leaf that a numpy restore has no target tree
        absl = logging.getLogger("absl")
        absl_level = absl.level
        absl.setLevel(logging.ERROR)
        for path in snapshots:
            for marker in (integrity.MANIFEST_NAME, integrity.COMMIT_MARKER):
                assert os.path.isfile(os.path.join(path, marker)), (
                    path, marker)
            ok, reason = integrity.verify(path, deep=True)
            assert ok, (path, reason)
        absl.setLevel(absl_level)

        lowered = probe.steps["sync"].lower(module.state, probe.batch)
        n_kernels = lowered.as_text().count("tpu_custom_call")
        assert n_kernels, (
            "no Mosaic custom call in the lowered train step — the flash "
            "kernels are not in the program that trained")
        counts = fallback_counts(tracer)
        assert not any(counts.values()), f"fallbacks counted: {counts}"
        # Each chip's kernel works on its own shard of the batch and the
        # heads — never on a batch all-gathered back to full size.
        mesh = module.sharding_plan.mesh.shape
        shard = (batch // (mesh["data"] * mesh["fsdp"]),
                 cfg.n_heads // mesh["tensor"])
        called = kernel_call_shapes(lowered.compile().as_text())
        assert called and all(dims[:2] == shard for dims in called), (
            f"Mosaic calls in the compiled step work on {sorted(called)}, "
            f"expected [batch, heads] = {shard} per chip")
        axes = {k: v for k, v in mesh.items() if v > 1}
        return (f"mesh {axes or 'one chip'}, {module.step} steps, loss "
                f"{losses[0]:.3f} -> {losses[-1]:.3f}, "
                f"{len(snapshots)} snapshot(s) verified, {n_kernels} Mosaic "
                f"calls in the step on {sorted(called)} per chip")
    finally:
        shutil.rmtree(root, ignore_errors=True)
        # release the trainer's state before the server takes the chip
        module.state = None
        gc.collect()


def kernel_call_shapes(hlo_text) -> set:
    """Dims of the first result of every Mosaic custom call in a compiled
    program's HLO text, e.g. ``{(4, 12, 1024, 64)}``."""
    calls = re.findall(
        r"= \(?\w+\[([\d,]+)\][^=]* custom-call\([^\n]*"
        r'custom_call_target="tpu_custom_call"', hlo_text)
    return {tuple(int(d) for d in dims.split(",")) for dims in calls}


# -- phase 3: serving ---------------------------------------------------------


def bf16_params(model, seed):
    import flax.linen as nn
    import jax.numpy as jnp

    sample = {"tokens": jnp.zeros((1, 8), jnp.int32)}
    params = nn.meta.unbox(
        jax.jit(model.init)(jax.random.PRNGKey(seed), sample)["params"])
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)


def serving_phase(cfg, draft_cfg, *, max_batch=SERVE_BATCH,
                  total_len=SERVE_TOTAL_LEN, n_draft=SERVE_N_DRAFT,
                  new_tokens=SERVE_NEW, prompt_lens=SERVE_PROMPT_LENS) -> str:
    import jax.numpy as jnp
    import numpy as np

    from rocket_tpu.models.generate import ContinuousBatcher, generate
    from rocket_tpu.models.transformer import TransformerLM
    from rocket_tpu.serve import Completed, HealthState, Request, ServingLoop

    model, draft = TransformerLM(cfg), TransformerLM(draft_cfg)
    params, draft_params = bf16_params(model, 0), bf16_params(draft, 1)

    def factory():
        return ContinuousBatcher(model, draft, params, draft_params,
                                 total_len=total_len, n_draft=n_draft)

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 50257, size=n).astype(np.int32)
               for n in prompt_lens]
    loop = ServingLoop(factory, max_batch=max_batch)
    try:
        for rid, prompt in enumerate(prompts):
            rejected = loop.submit(Request(rid=rid, prompt=prompt,
                                           max_new_tokens=new_tokens))
            assert rejected is None, rejected
        results = {res.rid: res for res in loop.run_until_idle()}
        assert sorted(results) == list(range(len(prompts))), sorted(results)
        for rid, prompt in enumerate(prompts):
            res = results[rid]
            assert isinstance(res, Completed), res
            assert not res.truncated, res
            assert res.n_tok >= len(prompt) + new_tokens, (rid, res.n_tok)
            assert (np.asarray(res.tokens[:len(prompt)]) == prompt).all(), rid
        assert loop.counters.watchdog_trips == 0, loop.counters
        assert loop.counters.failed == 0, loop.counters
        assert loop.health is HealthState.SERVING, loop.health
        rounds = loop.counters.rounds
    finally:
        loop.close()

    # one request against plain greedy decode on the same weights
    want = np.asarray(generate(
        model, params, jnp.asarray(prompts[0])[None, :], new_tokens,
        temperature=0.0))[0]
    got = np.asarray(results[0].tokens[:len(want)])
    assert (got == want).all(), (
        f"greedy tokens differ from generate() at positions "
        f"{np.flatnonzero(got != want).tolist()}: {got.tolist()} vs "
        f"{want.tolist()}")
    return (f"{len(prompts)} requests completed in {rounds} rounds, "
            f"{new_tokens} new tokens each, request 0 equal to generate()")


if __name__ == "__main__":
    sys.exit(main())
