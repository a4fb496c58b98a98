"""Benchmark: the BASELINE.json ladder's training throughput on the
available chip — ResNet-50/CIFAR, ViT-B/16, and GPT-2 124M.

Prints ONE JSON line PER CONFIG
(``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}``),
with the flagship GPT-2 line LAST (drivers that keep only the final line
get the headline metric).

Each workload runs through the framework's own jitted train step
(Module + Loss + Optimizer capsules -> donated step), bf16 compute.  Steps
are timed with the state threaded sequentially (step i+1 consumes step i's
state), so async dispatch / caching cannot fake the measurement; the final
block waits on the whole chain.

MFU accounting: GPT-2 uses the standard analytical 6*N*tokens model-FLOPs
formula; the vision configs read XLA's own cost analysis of the compiled
step (conv FLOP bookkeeping by hand is error-prone).  ``vs_baseline``: the
reference (dsenushkin/rocket) publishes NO numbers (BASELINE.json
``"published": {}``; SURVEY §6), so the ratio is against the BASELINE.json
north-star proxy: 50% model-FLOPs utilization — vs_baseline = MFU / 0.50.
"""

import argparse
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rocket_tpu as rt  # noqa: E402
from rocket_tpu.models.objectives import cross_entropy, lm_cross_entropy  # noqa: E402
from rocket_tpu.models.transformer import TransformerConfig, TransformerLM  # noqa: E402


# Device-peak tables and the GPT-2 analytical step-FLOPs formula moved
# to rocket_tpu.tune.cost_model so the autotuner's roofline seeding and
# this ladder's MFU/MBU accounting can never disagree; these wrappers
# keep the historical bench API (tests and the committed records'
# provenance reference them by these names).
from rocket_tpu.tune.cost_model import gpt2_step_flops  # noqa: E402,F401
from rocket_tpu.tune.cost_model import (  # noqa: E402
    device_peak_flops as _peak_flops,
    device_peak_hbm_bytes as _peak_hbm,
)


def _local_peak(peak_fn):
    """Published peak of the local accelerator, or ``None`` on the CPU: a
    CPU run gets no MFU/MBU at all rather than one over some chip's
    peaks.  An accelerator kind the table does not hold raises."""
    dev = jax.devices()[0]
    return None if dev.platform == "cpu" else peak_fn(dev.device_kind)


def peak_flops_per_chip():
    """bf16 peak for the local accelerator (``None`` on the CPU)."""
    return _local_peak(_peak_flops)


def peak_hbm_bytes_per_chip():
    """HBM bandwidth peak for the local accelerator (``None`` on the CPU).

    Decode is bandwidth-bound (every emitted token re-reads the weights),
    so the decode bench reports MBU — model-bandwidth utilization —
    against this, the serving-world analogue of MFU."""
    return _local_peak(_peak_hbm)


def xla_step_flops(module, batch) -> float:
    """Per-step FLOPs from XLA's cost analysis of the train step (vision
    configs: hand-counting conv FLOPs is error-prone).  Reads the analysis
    off the LOWERING where possible — a second backend compile of the
    already-jitted step costs tens of seconds on TPU."""
    step = module._steps["sync"]  # the donated jitted step Module built
    lowered = step.lower(module.state, batch)
    try:
        cost = lowered.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        return float(cost["flops"])
    except (KeyError, TypeError, NotImplementedError):
        cost = lowered.compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        return float(cost["flops"])


def run_config(name, module, batch_np, samples_per_step, n_steps, warmup,
               flops_fn):
    """Time the framework train step; return the result record."""
    runtime = rt.Runtime(mixed_precision="bf16")
    module.bind(runtime)
    module.setup()
    batches = [
        jax.device_put(b, runtime.batch_sharding(ndim=1)) for b in batch_np
    ]
    attrs = rt.Attributes(
        looper=rt.Attributes(grad_enabled=True, state=rt.Attributes())
    )
    # >=1 warmup step: materializes the lazy TrainState and keeps the
    # compile out of the timed loop.
    for i in range(max(1, warmup)):
        attrs.batch = batches[i % len(batches)]
        module.launch(attrs)
    jax.block_until_ready(module.state.params)

    t0 = time.perf_counter()
    gaps = []
    for i in range(n_steps):
        attrs.batch = batches[i % len(batches)]
        g0 = time.perf_counter()
        module.launch(attrs)  # state threads: step i+1 depends on step i
        gaps.append(time.perf_counter() - g0)
    jax.block_until_ready(module.state.params)
    elapsed = time.perf_counter() - t0

    step_time = elapsed / n_steps
    # Host dispatch gap: time the host spends enqueuing each step — the
    # window the chip sits idle between back-to-back steps.  Median, so a
    # one-off GC pause doesn't masquerade as a dispatch regression (the
    # async-loop guard in tests/test_bench_guard.py holds this down).
    dispatch_gap_ms = float(np.median(gaps)) * 1e3
    try:
        flops = flops_fn(module, batches[0])
    except Exception as exc:  # cost analysis unavailable on this backend
        flops = None
        flops_err = f"{type(exc).__name__}: {exc}"
    peak = peak_flops_per_chip()
    mfu = (flops / step_time / peak) if flops and peak else None
    record = {
        "config": name,
        "value": round(samples_per_step / step_time, 1),
        "vs_baseline": round(mfu / 0.50, 3) if mfu else None,
        "step_time_ms": round(step_time * 1e3, 2),
        "dispatch_gap_ms": round(dispatch_gap_ms, 3),
        "mfu": round(mfu, 4) if mfu else None,
        "device": jax.devices()[0].device_kind,
    }
    # Per-device memory plan from the sharding engine: what the rule-derived
    # spec tree says each device holds at steady state (params / optimizer /
    # total argument bytes).  This is the column TestZeroGuard asserts drops
    # (N-1)/N when zero_stage=1 re-partitions the optimizer mirrors.
    mem = module.memory_plan() if hasattr(module, "memory_plan") else None
    if mem:
        record["mem_param_mb"] = round(mem["param_bytes"] / 2**20, 1)
        record["mem_opt_mb"] = round(mem["opt_bytes"] / 2**20, 1)
        record["mem_total_mb"] = round(mem["total_bytes"] / 2**20, 1)
    if flops is None:
        record["flops_error"] = flops_err
    module.destroy()
    return record


def bench_resnet50(n_steps, warmup):
    from rocket_tpu.models.resnet import resnet50

    B = int(os.environ.get("BENCH_RESNET_BATCH", 256))
    # Image size knob: 32 = the CIFAR ladder config (3x3 stem, no
    # maxpool); >=128 switches to the ImageNet stem and 1000 classes.
    # CIFAR's 32x32 spatial dims shrink to 4x4 by stage 4 — a structural
    # MXU under-fill — so the 224 point separates "framework overhead"
    # from "these conv shapes cannot fill the MXU" in the 0.298-MFU
    # analysis (VERDICT r4 next #2).
    img = int(os.environ.get("BENCH_RESNET_IMAGE", 32))
    small = img < 128
    classes = 10 if small else 1000
    cfg_name = "resnet50" if img == 32 else f"resnet50-img{img}"
    flavor = "cifar" if img == 32 else (
        f"{img}px small-stem" if small else f"imagenet-shaped {img}px")
    module = rt.Module(
        resnet50(num_classes=classes, small_images=small),
        capsules=[
            rt.Loss(cross_entropy(labels_key="label"), name="ce"),
            rt.Optimizer(learning_rate=1e-3),
        ],
    )
    rng = np.random.default_rng(0)
    batches = [
        {"image": jnp.asarray(rng.normal(0.5, 0.25, size=(B, img, img, 3)),
                              jnp.float32),
         "label": jnp.asarray(rng.integers(0, classes, size=(B,)), jnp.int32)}
        for _ in range(2)
    ]
    rec = run_config(cfg_name, module, batches, B, n_steps, warmup,
                     xla_step_flops)
    rec.update({
        "metric": f"resnet50-{flavor} train throughput (1 chip, bf16, "
                  f"bs{B})",
        "unit": "samples/sec/chip",
        "flops_source": "xla cost_analysis (fwd+bwd step)",
    })
    return rec


def bench_vit_b16(n_steps, warmup):
    from rocket_tpu.models.vit import ViT, ViTConfig

    B = int(os.environ.get("BENCH_VIT_BATCH", 64))
    module = rt.Module(
        ViT(ViTConfig.b16()),
        capsules=[
            rt.Loss(cross_entropy(labels_key="label"), name="ce"),
            rt.Optimizer(learning_rate=1e-3),
        ],
    )
    rng = np.random.default_rng(0)
    batches = [
        {"image": jnp.asarray(rng.normal(0.5, 0.25, size=(B, 224, 224, 3)),
                              jnp.float32),
         "label": jnp.asarray(rng.integers(0, 1000, size=(B,)), jnp.int32)}
        for _ in range(2)
    ]
    rec = run_config("vit-b16", module, batches, B, n_steps, warmup,
                     xla_step_flops)
    rec.update({
        "metric": f"vit-b16-imagenet train throughput (1 chip, bf16, bs{B})",
        "unit": "samples/sec/chip",
        "flops_source": "xla cost_analysis (fwd+bwd step)",
    })
    return rec


# GPT-2 bench tunables (sweepable via --sweep; defaults = best known).
# vocab 50304 = 50257 padded to a multiple of 128 — the unembed matmul
# tiles the MXU cleanly (same trick as the public nanoGPT recipe); the
# extra logits are never targeted by data (ids < 50257) and their FLOPs
# ARE executed, so the analytical formula counts the padded size.
# Defaults = the best MEASURED configuration: the round-4 on-chip sweep
# (experiments/bench_runs.jsonl, 2026-07-31) measured every combination
# point and picked bs16 x blocks 512/1024 = 0.4587 MFU / 119.6k tok/s.
# The fused_qkv / fused_ce variants all measured SLOWER on the v5e chip
# (0.40-0.42) and stay off; scan_layers compiled under the auto-guard
# but ran at 0.328.
# block_q/block_k None = the LIBRARY's shape-aware defaults
# (ops.flash.auto_blocks — which now encode the same measured 512/1024
# at S=1024), so the headline bench exercises exactly what a user gets
# with no tune dict (VERDICT r4 next #5).
GPT2_TUNE = dict(batch=16, seq=1024, block_q=None, block_k=None,
                 vocab=50304, scan_layers=False, remat=False,
                 fused_qkv=False, fused_ce=False, ce_chunk=1024,
                 remat_policy="nothing", attention="auto",
                 # sliding-window attention (None = full causal); the
                 # long-seq ablation point measures the flash kernel's
                 # out-of-window block skipping on chip
                 window=None,
                 # first-moment dtype ("bf16" -> optax.adamw(mu_dtype=...)).
                 # NOTE: optax casts only mu — nu has no dtype knob and
                 # bf16 squared-grad accumulators would be lossy anyway —
                 # so of the ~7 f32 passes over 124M params (~4.3ms/step
                 # at 819GB/s) only the 2 mu passes shrink: expect
                 # ~0.6ms/step, a sub-1% MFU nudge. Unmeasured -> f32.
                 mu_dtype="f32",
                 # model dims (gpt2_124m defaults): overridable so the
                 # autotuner's CPU-proxy smoke and scaled ablations can
                 # probe through the exact same code path as the headline
                 hidden=768, n_layers=12, n_heads=12,
                 # TrainState donation (None = Module/runtime resolution,
                 # which itself consults the tune store — see
                 # rocket_tpu.tune.store.runtime_default)
                 donate=None)


def _env_tune() -> dict:
    """Optional per-run GPT-2 tune overrides from ``BENCH_GPT2_TUNE``
    (a JSON object merged over GPT2_TUNE) — lets a watcher/queue run a
    single tuned point (e.g. ``{"block_q": 1024, "block_k": 1024}`` or a
    long-seq point) without editing this file or running the full sweep.
    Explicit ``tune=`` arguments (the sweep) still take precedence."""
    raw = os.environ.get("BENCH_GPT2_TUNE")
    if not raw:
        return {}
    t = json.loads(raw)
    unknown = set(t) - set(GPT2_TUNE)
    if unknown:
        raise SystemExit(
            f"unknown BENCH_GPT2_TUNE keys {sorted(unknown)}; "
            f"valid: {sorted(GPT2_TUNE)}"
        )
    return t


def _store_tune() -> dict:
    """Defaults from a completed autotune search (``rocket_tpu.tune``):
    the best record for (gpt2, THIS device kind, THIS backend) — a tune
    measured on different silicon must not steer the headline.  Unknown
    keys (advisory knobs like prefetch/mesh) are dropped.  Best-effort:
    a broken or absent store reads as empty.  ``BENCH_NO_TUNE_STORE=1``
    disables consultation (sweep probes pass explicit ``tune=`` and are
    immune regardless)."""
    if os.environ.get("BENCH_NO_TUNE_STORE"):
        return {}
    try:
        from rocket_tpu.tune.store import best_tune

        rec = best_tune(model="gpt2",
                        device=jax.devices()[0].device_kind,
                        backend=jax.default_backend())
    except Exception:
        return {}
    if not rec:
        return {}
    return {k: v for k, v in rec.get("tune", {}).items() if k in GPT2_TUNE}


def _resolve_gpt2_tune(tune=None) -> tuple:
    """Merge precedence for the gpt2 bench tune — lowest to highest:
    ``GPT2_TUNE`` defaults < tune-store record (:func:`_store_tune`) <
    ``BENCH_GPT2_TUNE`` env < explicit ``tune=`` (the sweep / probes).
    Returns ``(merged, store_keys)`` where ``store_keys`` are the store
    keys that SURVIVED the merge (recorded for provenance)."""
    store = _store_tune()
    env = _env_tune()
    explicit = dict(tune or {})
    merged = {**GPT2_TUNE, **store, **env, **explicit}
    survived = sorted(
        k for k, v in store.items()
        if k not in env and k not in explicit and merged[k] == v
    )
    return merged, survived


def _gpt2_cfg_kwargs(t: dict) -> dict:
    """The ONE place a merged tune dict becomes ``gpt2_124m`` kwargs."""
    return dict(
        # the default slice path fails loudly past the learned-position
        # table (shape mismatch at trace time); sizing the table with the
        # benched seq is what makes long-seq ablation points runnable
        max_seq=max(1024, t["seq"]),
        scan_layers=t["scan_layers"], remat=t["remat"],
        remat_policy=t["remat_policy"], fused_qkv=t["fused_qkv"],
        fused_ce=t["fused_ce"], fused_ce_chunk=t["ce_chunk"],
        vocab_size=t["vocab"],
        hidden=t.get("hidden", 768),
        n_layers=t.get("n_layers", 12),
        n_heads=t.get("n_heads", 12),
        attention=t.get("attention", "auto"),
        attention_block_q=t["block_q"],
        attention_block_k=t["block_k"],
        attention_window=t.get("window"),
    )


def bench_gpt2(n_steps, warmup, tune=None):
    t, store_keys = _resolve_gpt2_tune(tune)
    batch, seq = t["batch"], t["seq"]
    cfg = TransformerConfig.gpt2_124m(**_gpt2_cfg_kwargs(t))
    opt_kw = {}
    mu = t.get("mu_dtype", "f32")
    if mu not in ("f32", "bf16"):
        raise ValueError(f"mu_dtype must be 'f32' or 'bf16', got {mu!r}")
    if mu == "bf16":
        opt_kw["mu_dtype"] = jnp.bfloat16  # forwarded to optax.adamw
    module = rt.Module(
        TransformerLM(cfg),
        capsules=[
            rt.Loss(lm_cross_entropy(), name="lm"),
            rt.Optimizer(learning_rate=1e-4, **opt_kw),
        ],
        donate=t.get("donate"),  # None = Module/runtime/tune resolution
    )
    rng = np.random.default_rng(0)
    batches = [
        {"tokens": jnp.asarray(
            rng.integers(0, min(50257, t["vocab"]), size=(batch, seq)),
            jnp.int32)}
        for _ in range(4)
    ]
    rec = run_config(
        "gpt2", module, batches, batch * seq, n_steps, warmup,
        lambda m, b: gpt2_step_flops(cfg, batch, seq),
    )
    rec.update({
        "metric": f"gpt2-124m train throughput (1 chip, bf16, bs{batch}x{seq})",
        "unit": "tokens/sec/chip",
        "flops_source": "analytical 6*N*tokens + attention",
        "tune": t,
        "baseline_note": "reference publishes no numbers (BASELINE.json "
                         "published={}); vs_baseline = MFU/0.50 north-star "
                         "proxy",
    })
    if store_keys:
        # provenance: these keys came from a persisted autotune record
        # (rocket_tpu.tune), not the hardcoded defaults / env / caller
        rec["tune_store_keys"] = store_keys
    return rec


def sweep_gpt2(n_steps, warmup, top_k=3):
    """Grid-sweep the GPT-2 tunables on the real chip; prints one JSON line
    per point (value AND mfu — comparable across devices), a
    ``sweep_top_k`` summary of the best ``top_k`` points, and a final
    best-point line.  Points are deduped by CANONICAL tune key
    (``rocket_tpu.tune.store.canonical_tune_key``): flash-block ``None``
    resolves through ``ops.flash.auto_blocks``, so an explicit
    512/1024-at-seq-1024 point and the library default are measured
    once, not twice.  A short decode section follows (bf16 / int8
    weights / int8 KV cache), each point carrying MBU.  Used to pick
    GPT2_TUNE."""
    from rocket_tpu.tune.store import canonical_tune_key
    grid = []
    for batch in (8, 16, 32):
        grid.append({"batch": batch})
    for bq, bk in ((128, 128), (128, 256), (256, 256), (256, 512),
                   (512, 512), (512, 1024)):
        grid.append({"block_q": bq, "block_k": bk})
    grid.append({"vocab": 50257})       # unpadded-vocab ablation
    grid.append({"fused_qkv": True})    # one wide qkv matmul ablation
    grid.append({"fused_ce": True})     # logits-free LM loss ablation
    # fused_ce frees the [B*S, vocab] logits memory — the big-batch points
    # only fit with it on.
    grid.append({"fused_ce": True, "batch": 32})
    grid.append({"fused_ce": True, "batch": 64})
    # The VERDICT r3 combination matrix: the individually-strongest
    # measured knobs (blocks 512/1024, bs16) x the round-3 kernel fixes
    # (fused_qkv, fused_ce) — the points that decide the >=50%-MFU claim.
    grid.append({"batch": 16, "block_q": 512, "block_k": 1024})
    grid.append({"fused_qkv": True, "fused_ce": True})
    grid.append({"fused_qkv": True, "fused_ce": True,
                 "batch": 16, "block_q": 512, "block_k": 1024})
    grid.append({"fused_qkv": True, "fused_ce": True,
                 "batch": 32, "block_q": 512, "block_k": 1024})
    # attention-impl ablation: plain XLA dot attention materializes the
    # [B,H,S,S] logits but lets XLA fuse/tile freely — at moderate seq it
    # can beat a hand-tiled pallas kernel on the MXU.
    grid.append({"attention": "dot"})
    grid.append({"attention": "dot", "batch": 8})
    grid.append({"batch": 12})          # refine around the bs16 optimum
    grid.append({"batch": 24})
    # long-context single-chip points (same 16k tokens/step as bs16x1024;
    # learned-position table sized up with seq — see bench_gpt2)
    grid.append({"seq": 2048, "batch": 8})
    grid.append({"seq": 8192, "batch": 2})
    grid.append({"mu_dtype": "bf16"})   # bf16 adam moments (bandwidth)
    grid.append({"scan_layers": True})  # scan ablation
    grid.append({"remat": True})        # remat ablation
    grid.append({"remat": True, "remat_policy": "dots"})
    # The grid is written against a fixed reference point, not the current
    # defaults — always include the default itself, and run each distinct
    # merged config once even when a knob's value coincides with GPT2_TUNE.
    grid.insert(0, {})
    seen_cfgs = set()
    ranked = []
    for point in grid:
        resolved = dict(GPT2_TUNE, **point)
        merged = canonical_tune_key(resolved)
        if merged in seen_cfgs:
            # e.g. an explicit block point equals the auto_blocks default:
            # record WHY instead of re-benching a mislabeled duplicate.
            print(json.dumps({"sweep_point": point, "skipped":
                              "canonical tune key already measured"}),
                  flush=True)
            continue
        seen_cfgs.add(merged)
        try:
            rec = bench_gpt2(n_steps, warmup, tune=resolved)
        except Exception as exc:
            rec = {"tune": dict(GPT2_TUNE, **point), "value": None,
                   "mfu": None, "error": f"{type(exc).__name__}: {exc}"}
        print(json.dumps({"sweep_point": point, **rec}), flush=True)
        _persist_record({"sweep_point": point, **rec})
        # Selection needs a real value and a real MFU (the gpt2
        # analytical formula provides one on every accelerator).
        if rec.get("value") and rec.get("mfu"):
            ranked.append(rec)
    ranked.sort(key=lambda r: -r["value"])
    if top_k and ranked:
        line = {"sweep_top_k": [
            {"tune": r["tune"], "value": r["value"], "mfu": r["mfu"]}
            for r in ranked[:top_k]
        ]}
        print(json.dumps(line), flush=True)
        _persist_record(line)
    if ranked:
        best = ranked[0]
        line = {"sweep_best": best["tune"], "value": best["value"],
                "mfu": best["mfu"]}
        print(json.dumps(line), flush=True)
        _persist_record(line)
    # Decode section: the serving-side knobs, each point carrying MBU
    # (bandwidth is decode's roofline the way FLOPs are training's).
    # BENCH_SWEEP_DECODE=0 skips it (train-only sweep days).
    if os.environ.get("BENCH_SWEEP_DECODE", "1") != "0":
        for point in ({}, {"int8": True}, {"kv_int8": True},
                      {"int8": True, "kv_int8": True}):
            try:
                rec = bench_gpt2_decode(n_steps, warmup, overrides=point)
            except Exception as exc:
                rec = {"value": None, "mbu": None,
                       "error": f"{type(exc).__name__}: {exc}"}
            line = {"sweep_point": {"decode": point}, **rec}
            print(json.dumps(line), flush=True)
            _persist_record(line)


def bench_gpt2_decode(n_steps, warmup, overrides=None):
    """KV-cache decode throughput (the serving-side number).

    GPT-2 124M, prompt 128 -> 128 new tokens per call, greedy-ish
    sampling at temperature 1.  Decode is HBM-bandwidth-bound — each
    emitted token re-reads the bf16 weights plus the live KV cache — so
    the record carries MBU (achieved bytes/s over peak) alongside raw
    tokens/sec.  ``max_seq`` is sized to prompt+new so the static cache
    isn't padded with dead positions the kernels would still scan.

    Knobs come from ``BENCH_DECODE_*`` env vars; ``overrides`` (keys
    ``batch``/``int8``/``kv_int8``/``mode``/``beam``/``n_draft``) wins
    over env — the sweep's decode section passes points this way.
    ``kv_int8`` turns on the per-page int8 KV cache
    (``TransformerConfig.kv_cache_int8``): the cache's HBM footprint —
    and the per-token re-read — drops ~2x, which the MBU byte model
    picks up automatically through ``decode_cache_shapes``.
    """
    from rocket_tpu.models.generate import generate

    o = dict(overrides or {})

    def knob(key, env, cast, default):
        return cast(o[key]) if key in o else cast(
            os.environ.get(env, default))

    B = knob("batch", "BENCH_DECODE_BATCH", int, 8)
    int8 = bool(knob("int8", "BENCH_DECODE_INT8", int, "0"))
    kv_int8 = bool(knob("kv_int8", "BENCH_DECODE_KV_INT8", int, "0"))
    mode = knob("mode", "BENCH_DECODE_MODE", str, "generate")
    if mode not in ("generate", "beam", "rounds"):
        raise ValueError(
            f"BENCH_DECODE_MODE must be generate|beam|rounds, got {mode!r}"
        )
    beam_k = knob("beam", "BENCH_DECODE_BEAM", int, 4)
    n_draft = knob("n_draft", "BENCH_DECODE_NDRAFT", int, 4)
    PROMPT, NEW = 128, 128
    # rounds mode: the speculative verify chunk may write up to n_draft
    # slots past the final token, so the static cache carries that slack
    max_seq = PROMPT + NEW + (n_draft if mode == "rounds" else 0)
    cfg = TransformerConfig.gpt2_124m(vocab_size=50304, max_seq=max_seq,
                                      weights_int8=int8,
                                      kv_cache_int8=kv_int8)
    model = TransformerLM(cfg)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, 50257, size=(B, PROMPT)), jnp.int32)
    init_model = model
    if int8 or kv_int8:
        # init trained-shaped f32 weights (and a vanilla-cache model for
        # shape purposes), then rewrite into the int8 layout — the same
        # flow a user quantizing a checkpoint follows.  KV-cache int8
        # does NOT change params, but init through the vanilla config
        # keeps the two paths' param trees trivially identical.
        init_model = TransformerLM(
            TransformerConfig.gpt2_124m(vocab_size=50304, max_seq=max_seq)
        )
    variables = jax.jit(init_model.init)(
        jax.random.PRNGKey(0), {"tokens": prompt}
    )
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if isinstance(a, jax.Array) and jnp.issubdtype(a.dtype, jnp.floating)
        else a,
        variables["params"],
    )
    if int8:
        from rocket_tpu.ops.quant import quantize_params

        params = jax.jit(quantize_params)(params)
        jax.block_until_ready(params)
    # drop the f32 init tree before timing: keeping it live would leave
    # f32 + bf16/int8 copies resident through the measured decode loop
    del variables

    extra = {}
    if mode == "beam":
        from rocket_tpu.models.generate import beam_search_cached

        # eos_id -1 never matches a vocab token, so every call decodes
        # the full NEW tokens and calls stay work-identical
        bs_run = jax.jit(lambda p, tok: beam_search_cached(
            model, p, tok, NEW, eos_id=-1, beam_size=beam_k)[0])

        def run_call(i):
            return bs_run(params, prompt)

        extra = {"beam_size": beam_k}
    elif mode == "rounds":
        from rocket_tpu.models.generate import ContinuousBatcher

        bat = ContinuousBatcher(model, model, params, params,
                                total_len=PROMPT + NEW, n_draft=n_draft)

        def run_call(i):
            # round-at-a-time host loop — same math as the one-dispatch
            # speculative path, but each round is its own dispatch; the
            # delta vs plain decode prices the serving loop's ability to
            # admit requests between rounds
            bat.start(prompt)
            while not bat.all_done:
                bat.step()
            return bat.state[0]

        extra = {"n_draft": n_draft}
    else:
        run = jax.jit(lambda p, tok, key: generate(
            model, p, tok, NEW, rng=key, temperature=1.0))
        key = jax.random.PRNGKey(1)

        def run_call(i):
            return run(params, prompt, jax.random.fold_in(key, i))

    out = None
    for _ in range(max(1, warmup)):
        out = run_call(0)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for i in range(n_steps):
        out = run_call(i)
        jax.block_until_ready(out)  # each call is an independent request
    elapsed = time.perf_counter() - t0
    if mode == "rounds":
        extra["rounds_per_call"] = int(bat.stats()["rounds"])

    per_call = elapsed / n_steps
    tok_per_s = B * NEW / per_call
    param_bytes = sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(params)
    )
    # per decode step: weights once + ~half the KV cache (growing frontier)
    from rocket_tpu.models.generate import decode_cache_shapes

    kv_bytes = sum(
        a.size * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(
            decode_cache_shapes(model, params, prompt)
        )
    )
    # Per decode step i the live cache holds PROMPT+i entries out of the
    # PROMPT+NEW allocation, so the mean fraction of kv_bytes read per
    # step is (PROMPT + NEW/2) / (PROMPT + NEW) — ~75% at 128+128, not
    # the 50% a bare "half the cache" model gives (ADVICE r4).  The
    # timed loop also includes the prefill forward: account its dominant
    # traffic (one full weight read + the PROMPT-token KV write) rather
    # than letting untracked prefill time deflate MBU.
    frontier = (PROMPT + NEW / 2) / (PROMPT + NEW)
    prefill_bytes = param_bytes + kv_bytes * PROMPT / (PROMPT + NEW)
    bytes_per_call = NEW * (param_bytes + kv_bytes * frontier) + prefill_bytes
    # the traffic model above assumes one decode row per request and one
    # forward per token — beam tiles the cache K-wide and speculative
    # rounds batch draft+verify, so MBU is only honest for plain decode
    hbm_peak = peak_hbm_bytes_per_chip()
    mbu = (bytes_per_call / per_call / hbm_peak
           if mode == "generate" and hbm_peak else None)
    wdt = "int8 weights" if int8 else "bf16"
    if kv_int8:
        wdt += ", int8 kv"
    cfg_name = "gpt2-decode-int8" if int8 else "gpt2-decode"
    if kv_int8:
        cfg_name += "-kvint8"
    if mode != "generate":
        cfg_name += f"-{mode}"
    mode_note = {"beam": f", cached beam k={beam_k}",
                 "rounds": f", round-granular spec n_draft={n_draft}"}
    return {
        "config": cfg_name,
        "metric": f"gpt2-124m KV-cache decode (1 chip, {wdt}, bs{B}, "
                  f"{PROMPT}+{NEW} tokens{mode_note.get(mode, '')})",
        "value": round(tok_per_s, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": None,
        "per_call_ms": round(per_call * 1e3, 2),
        "mbu": None if mbu is None else round(mbu, 4),
        **extra,
        "device": jax.devices()[0].device_kind,
        "baseline_note": "reference has no generation path at all; MBU = "
                         "achieved HBM bytes/s over peak (decode is "
                         "bandwidth-bound)",
    }


# -- pipeline schedule bench (ISSUE 13) ------------------------------------
#
# Record schema (config="pipeline", emitted by ``--only pipeline``):
#   value / unit ........ interleaved (v=2) bubble reduction vs GPipe:
#                         gpipe bubble_fraction / interleaved
#                         bubble_fraction from the lockstep proxy run
#   schedules.<name> .... one column set per schedule:
#     bubble_fraction ... MEASURED: sum of the goodput ledger's
#                         pipeline/bubble/stage<p> buckets over
#                         (bubble + busy) seconds of the lockstep run —
#                         the same buckets the fleet metrics export
#     bubble_fraction_plan / ticks_forward / ticks_total / bubble_ticks /
#     live_microbatches . analytic schedule_plan() columns
#     stage_wait_s / stage_busy_s ... per-stage lockstep seconds
#     mem_param_bytes / mem_opt_bytes / mem_other_bytes / mem_total_bytes
#                         memory_plan() per-device TrainState bytes of the
#                         pipelined proxy transformer under the
#                         DEFAULT_PARTITION_RULES specs (PR 16 accounting)
#     mem_live_activation_bytes ... live_microbatches x microbatch bytes
#                         (the 1F1B residency bound made concrete)
#   guard ............... "interleaved<gpipe: ok" or the failure text —
#                         the bench-level form of the test-suite guard
#
# The lockstep driver exists because this proxy host is effectively
# single-core: a threaded MPMD run measures OS-scheduler noise, while the
# tick-round driver prices structural idleness at each stage's own
# measured compute rate (see mpmd.run_lockstep).

PIPELINE_PROXY = dict(n_stages=2, n_micro=8, n_layers=8, width=128,
                      micro_batch=32)


def measure_pipeline_schedules(n_stages=None, n_micro=None, n_layers=None,
                               width=None, micro_batch=None,
                               schedules=(("gpipe", 1), ("1f1b", 1),
                                          ("interleaved", 2))):
    """Lockstep-run each schedule on the CPU proxy stack; bubble fractions
    are read back from the goodput ledger's per-stage buckets."""
    import jax.numpy as jnp

    from rocket_tpu.observe.ledger import get_goodput
    from rocket_tpu.parallel import mpmd

    P = n_stages or PIPELINE_PROXY["n_stages"]
    M = n_micro or PIPELINE_PROXY["n_micro"]
    L = n_layers or PIPELINE_PROXY["n_layers"]
    D = width or PIPELINE_PROXY["width"]
    B = micro_batch or PIPELINE_PROXY["micro_batch"]
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    params = {"w": jax.random.normal(ks[0], (L, D, D)) * 0.3,
              "b": jax.random.normal(ks[1], (L, D)) * 0.01}
    micros = jax.random.normal(ks[2], (M, B, D))
    target = jax.random.normal(ks[3], (B, D))

    def layer(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    def loss_fn(y):
        return jnp.mean((y - target) ** 2)

    gp = get_goodput()
    was_armed = gp.armed
    out = {}
    try:
        for sched, v in schedules:
            gp.start_run()
            res = mpmd.run_lockstep(layer, params, micros, loss_fn,
                                    n_stages=P, schedule=sched, n_chunks=v)
            gp.end_run()
            snap = gp.snapshot()
            wait = [snap.get(f"pipeline/bubble/stage{p}_s", 0.0)
                    for p in range(P)]
            busy = [r.busy_s for r in res.reports]
            denom = sum(wait) + sum(busy)
            out[sched] = {
                "n_chunks": v,
                "bubble_fraction": round(sum(wait) / denom, 4) if denom
                else 0.0,
                "bubble_fraction_plan": round(
                    res.plan["bubble_fraction"], 4),
                "ticks_forward": res.plan["ticks_forward"],
                "ticks_total": res.plan["ticks_total"],
                "bubble_ticks": res.plan["bubble_ticks"],
                "live_microbatches": res.plan["live_microbatches"],
                "stage_wait_s": [round(w, 6) for w in wait],
                "stage_busy_s": [round(b, 6) for b in busy],
            }
    finally:
        gp.armed = was_armed
    return out


def _pipeline_memory_columns(schedule, n_chunks, n_stages=2, n_micro=4):
    """memory_plan() per-device state bytes of a pipelined proxy
    transformer + the schedule's live-activation bound."""
    import optax

    from rocket_tpu.engine.adapter import FlaxModel
    from rocket_tpu.engine.state import TrainState, memory_plan
    from rocket_tpu.parallel.mesh import MeshSpec
    from rocket_tpu.parallel.pipeline import schedule_plan
    from rocket_tpu.parallel.sharding import DEFAULT_RULES, specs_for_state

    devs = jax.devices()
    P = n_stages if len(devs) >= n_stages else 1
    mesh = MeshSpec(pipe=P).build(devs[:P])
    B, S, D = 8, 64, 128
    cfg = TransformerConfig(
        vocab_size=256, hidden=D, n_layers=8, n_heads=4, ffn_dim=256,
        max_seq=S, attention="dot", pipeline_microbatches=n_micro,
        pipeline_schedule=schedule, pipeline_chunks=n_chunks,
    )
    adapter = FlaxModel(TransformerLM(cfg))
    adapter.configure(mesh, DEFAULT_RULES)
    tx = optax.adamw(1e-4)

    def init_fn():
        import jax.numpy as jnp

        batch = {"tokens": jnp.zeros((B, S), jnp.int32)}
        params, mutable = adapter.init_variables(jax.random.PRNGKey(0), batch)
        return TrainState.create(params, tx, mutable=mutable)

    abstract = jax.eval_shape(init_fn)
    param_specs = adapter.partition_specs(abstract.params, DEFAULT_RULES)
    plan = specs_for_state(mesh, abstract, param_specs=param_specs)
    mem = memory_plan(abstract, plan.state_specs, mesh)
    micro_act_bytes = (B // n_micro) * S * D * 4
    sched_plan = schedule_plan(schedule, P, n_micro, n_chunks,
                               micro_act_bytes=micro_act_bytes)
    return {
        "mem_param_bytes": mem["param_bytes"],
        "mem_opt_bytes": mem["opt_bytes"],
        "mem_other_bytes": mem["other_bytes"],
        "mem_total_bytes": mem["total_bytes"],
        "mem_live_activation_bytes": sched_plan["live_activation_bytes"],
    }


def bench_pipeline(n_steps, warmup):
    """Pipeline-schedule ladder record — see the schema comment above."""
    measured = measure_pipeline_schedules()
    for sched, cols in measured.items():
        cols.update(_pipeline_memory_columns(sched, cols["n_chunks"]))
    gp_b = measured["gpipe"]["bubble_fraction"]
    il_b = measured["interleaved"]["bubble_fraction"]
    guard = ("interleaved<gpipe: ok" if 0.0 < il_b < gp_b else
             f"interleaved bubble {il_b} !< gpipe {gp_b}")
    pp = PIPELINE_PROXY
    return {
        "config": "pipeline",
        "metric": (f"pipeline schedule bubble (CPU lockstep proxy, "
                   f"P={pp['n_stages']}, M={pp['n_micro']}, "
                   f"L={pp['n_layers']}; interleaved v=2)"),
        "value": round(gp_b / il_b, 2) if il_b > 0 else None,
        "unit": "bubble_reduction_x",
        "vs_baseline": None,
        "schedules": measured,
        "guard": guard,
        "device": jax.devices()[0].device_kind,
        "baseline_note": "reference has no pipeline parallelism; analytic "
                         "bound: (P-1)/(M+P-1) vs (P-1)/(vM+P-1)",
    }


# -- ZeRO stage ladder --------------------------------------------------------
#
# Two halves, one record:
#   mem_rows_gb         analytic memory_plan() per-device GB of a 30B-class
#                         transformer on a HYPOTHETICAL 64-way data pod
#                         (specs_for_state(make_shardings=False) — no such
#                         mesh exists on this host), per stage ± offload,
#                         each row with fits: <hbm_budget_gb>
#   step_wall_s         CPU-proxy measured sync-step walls per stage on the
#                         real local mesh (fake CPU devices) — placement
#                         cost, not TPU truth
#   offload             armed (double-buffered) vs synchronous host
#                         round-trip walls for the same opt state


def _zero_memory_rows(hbm_budget_gb):
    """memory_plan() rows for a 30B-class decoder on a 64-way data pod."""
    import optax

    from rocket_tpu.engine.state import TrainState, memory_plan
    from rocket_tpu.parallel.sharding import specs_for_state

    from jax.sharding import PartitionSpec as P

    V, H, L, F = 32000, 7168, 48, 28672
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    params = {
        "embed": {"embedding": S(V, H)},
        "blocks": {
            "attn": {"qkv": {"kernel": S(L, H, 3 * H)},
                     "o": {"kernel": S(L, H, H)}},
            "mlp": {"up": {"kernel": S(L, H, F)},
                    "down": {"kernel": S(L, F, H)}},
            "ln1": {"scale": S(L, H)},
            "ln2": {"scale": S(L, H)},
        },
        "head": {"kernel": S(H, V)},
    }
    n_params = sum(
        int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))

    class PodMesh:
        shape = {"data": 64}

    pspecs = jax.tree_util.tree_map(lambda _: P(), params)
    abstract = jax.eval_shape(
        lambda p: TrainState.create(p, optax.adamw(1e-4)), params)
    rows = {}
    for stage in (0, 1, 2, 3):
        plan = specs_for_state(
            PodMesh(), abstract, param_specs=pspecs, zero_stage=stage,
            make_shardings=False)
        for offload in ((False, True) if stage >= 1 else (False,)):
            mem = memory_plan(
                abstract, plan.state_specs, PodMesh(), zero_offload=offload)
            total_gb = round(mem["total_bytes"] / 2**30, 2)
            rows[f"stage{stage}" + ("+offload" if offload else "")] = {
                "param_gb": round(mem["param_bytes"] / 2**30, 2),
                "opt_gb": round(mem["opt_bytes"] / 2**30, 2),
                "host_opt_gb": round(mem["host_opt_bytes"] / 2**30, 2),
                "total_gb": total_gb,
                "fits": total_gb <= hbm_budget_gb,
            }
    return rows, n_params


def _zero_step_walls(n_steps, warmup):
    """Measured sync-step walls per ZeRO stage on the local (fake CPU)
    mesh, plus armed-vs-synchronous offload round-trip walls."""
    import optax

    from jax.sharding import NamedSharding, PartitionSpec as P

    from rocket_tpu.engine import Objective, TrainState, build_train_step
    from rocket_tpu.engine.offload import ZeroOffloader
    from rocket_tpu.parallel.mesh import MeshSpec
    from rocket_tpu.parallel.sharding import specs_for_state

    devs = jax.devices()
    n_data = 1
    while n_data * 2 <= len(devs):
        n_data *= 2
    mesh = MeshSpec(data=n_data).build(devs[:n_data])
    D = 512
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    # host-side numpy: each TrainState.create below must materialize FRESH
    # device buffers (the donated step deletes its input's buffers, and
    # device_put can alias an already-on-device source)
    params = {
        "w1": np.asarray(jax.random.normal(k1, (D, D), jnp.float32)) * 0.05,
        "w2": np.asarray(jax.random.normal(k2, (D, D), jnp.float32)) * 0.05,
    }
    pspecs = {"w1": P(), "w2": P()}

    def apply_fn(p, mutable, rng, batch, train):
        out = dict(batch)
        out["pred"] = jnp.tanh(batch["x"] @ p["w1"]) @ p["w2"]
        return out, mutable

    def loss(batch):
        return jnp.mean((batch["pred"] - batch["y"]) ** 2)

    tx = optax.adamw(1e-3)
    batch_sh = NamedSharding(mesh, P("data"))
    rng = np.random.default_rng(0)
    batch = {
        "x": jax.device_put(jnp.asarray(
            rng.normal(size=(n_data * 8, D)), jnp.float32), batch_sh),
        "y": jax.device_put(jnp.asarray(
            rng.normal(size=(n_data * 8, D)), jnp.float32), batch_sh),
    }

    walls = {}
    stage1 = None  # (state, step) kept for the offload comparison
    for stage in (0, 1, 2, 3):
        abstract = jax.eval_shape(lambda: TrainState.create(params, tx))
        plan = specs_for_state(
            mesh, abstract, param_specs=pspecs, zero_stage=stage)
        state = jax.device_put(
            TrainState.create(params, tx), plan.state_shardings)
        step = build_train_step(
            apply_fn, [Objective("mse", loss)], tx,
            shard_plan=plan if stage else None,
        )["sync"]
        for _ in range(warmup):
            state, _ = step(state, batch)
        jax.block_until_ready(state.params)
        t0 = time.perf_counter()
        for _ in range(n_steps):
            state, _ = step(state, batch)
        jax.block_until_ready(state.params)
        walls[f"stage{stage}"] = round(
            (time.perf_counter() - t0) / max(n_steps, 1), 6)
        if stage == 1:
            stage1 = (state, step, plan)

    # offload: armed (double-buffered, overlaps compute) vs synchronous
    # (inline round trip) driving the SAME stage-1 step loop
    offload = {}
    _, step1, plan1 = stage1
    for mode, sync in (("armed", False), ("sync", True)):
        off = ZeroOffloader(plan1.opt_shardings, synchronous=sync)
        # fresh state per mode: the step donates its input buffers
        state = jax.device_put(
            TrainState.create(params, tx), plan1.state_shardings)
        t0 = time.perf_counter()
        for _ in range(n_steps):
            state = state.replace(opt_state=off.fetch(state.opt_state))
            state, _ = step1(state, batch)
            off.stash(state.opt_state)
        state = state.replace(opt_state=off.fetch(state.opt_state))
        jax.block_until_ready(state.opt_state)
        offload[f"{mode}_wall_s"] = round(time.perf_counter() - t0, 6)
        offload[f"{mode}_host_wait_s"] = round(off.total_wait, 6)
        off.close()
    offload["devices"] = n_data
    return walls, offload


def bench_zero(n_steps, warmup):
    """ZeRO stage ladder record — see the schema comment above."""
    hbm_budget_gb = 96.0
    rows, n_params = _zero_memory_rows(hbm_budget_gb)
    walls, offload = _zero_step_walls(n_steps, warmup)
    s1, s3 = rows["stage1"], rows["stage3"]
    guard = ("stage3 fits where stage1 overflows: ok"
             if s3["fits"] and not s1["fits"] else
             f"stage1 total {s1['total_gb']}GB (fits={s1['fits']}) vs "
             f"stage3 {s3['total_gb']}GB (fits={s3['fits']})")
    return {
        "config": "zero",
        "metric": (f"ZeRO stage ladder: 30B-class "
                   f"({round(n_params / 1e9, 1)}B params) per-device "
                   f"memory plan on a hypothetical 64-way data pod + "
                   f"CPU-proxy step walls ({offload['devices']} devices)"),
        "value": round(s1["total_gb"] / s3["total_gb"], 1),
        "unit": "stage1_vs_stage3_mem_x",
        "vs_baseline": None,
        "hbm_budget_gb": hbm_budget_gb,
        "mem_rows_gb": rows,
        "step_wall_s": walls,
        "offload": offload,
        "guard": guard,
        "device": jax.devices()[0].device_kind,
        "baseline_note": "arXiv 2004.13336 table 1: stage-k per-device "
                         "state is P+P+O, P+P+O/N, P+P/N+O/N, (P+O)/N; "
                         "offload moves O to host RAM",
    }


BENCHES = {
    "resnet50": bench_resnet50,
    "vit": bench_vit_b16,
    "gpt2": bench_gpt2,
    "decode": bench_gpt2_decode,
    "pipeline": bench_pipeline,
    "zero": bench_zero,
}


def main(argv=None) -> int:
    """Run the ladder; returns the process exit code — non-zero when any
    config raised (its error record is still printed and persisted, and
    the remaining configs still run)."""
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--only", choices=sorted(BENCHES), default=None,
        help="run a single config (default: full ladder, gpt2 last)",
    )
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--warmup", type=int, default=3)
    parser.add_argument(
        "--sweep", action="store_true",
        help="grid-sweep the GPT-2 tunables instead of the ladder",
    )
    parser.add_argument(
        "--top-k", type=int, default=3,
        help="with --sweep: emit a sweep_top_k summary of the best K "
             "points (value + mfu, comparable across devices)",
    )
    parser.add_argument(
        "--profile-dir", type=str, default=None,
        help="capture a jax.profiler trace of the selected bench "
             "(--only NAME, default gpt2; setup + compile + warmup + "
             "timed loop) into this dir",
    )
    args = parser.parse_args(argv)
    if args.sweep and (args.only or args.profile_dir):
        parser.error("--sweep cannot combine with --only/--profile-dir")

    if args.sweep:
        sweep_gpt2(args.steps, args.warmup, top_k=args.top_k)
        return 0
    if args.profile_dir:
        # NOTE: the trace spans the whole bench — setup, compile,
        # warmup AND the timed loop; read the trace accordingly.
        traced = BENCHES[args.only or "gpt2"]
        with jax.profiler.trace(args.profile_dir):
            rec = traced(args.steps, args.warmup)
        print(json.dumps(rec), flush=True)
        _persist_record(dict(rec, profiled=True))
        return 0
    units = {"resnet50": "samples/sec/chip", "vit": "samples/sec/chip",
             "gpt2": "tokens/sec/chip", "decode": "tokens/sec/chip",
             "pipeline": "bubble_reduction_x"}
    # gpt2 stays LAST: the driver reads the final stdout line as the
    # headline record
    names = [args.only] if args.only else ["resnet50", "vit", "decode",
                                           "gpt2"]
    labels = {"decode": "KV-cache decode"}  # default: train throughput
    decode_int8 = bool(int(os.environ.get("BENCH_DECODE_INT8", "0")))
    failed = 0
    for name in names:
        wdt = "int8 weights" if name == "decode" and decode_int8 else "bf16"
        try:
            record = BENCHES[name](args.steps, args.warmup)
        except Exception as exc:
            # Boundary that must keep running: the other configs still
            # get measured, the traceback goes to stderr, and the exit
            # code says a config failed.
            traceback.print_exc()
            failed += 1
            record = {
                "config": name,
                "metric": f"{name} "
                          f"{labels.get(name, 'train throughput')} "
                          f"(1 chip, {wdt})",
                "value": None,
                "unit": units.get(name, "x"),
                "vs_baseline": None,
                "error": f"{type(exc).__name__}: {exc}",
            }
        print(json.dumps(record), flush=True)
        _persist_record(record)
    return 1 if failed else 0


def _persist_record(record: dict) -> None:
    """Append every ladder record to ``experiments/bench_runs.jsonl`` so
    ALL lines survive as a committed artifact even when the caller keeps
    only the final stdout line (round-3 verdict: the resnet/vit numbers
    were lost that way).  Best-effort: never fails the bench."""
    try:
        path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "experiments", "bench_runs.jsonl",
        )
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "a") as fh:
            fh.write(json.dumps({"ts": time.time(), **record}) + "\n")
    except OSError:
        pass


if __name__ == "__main__":
    sys.exit(main())
