"""Component-level attribution of the ResNet-50/CIFAR step time (VERDICT
r4 next #2: 0.298 MFU with zero analysis — give it the GPT-2 treatment).

Times each piece as its own jitted program on the bench shapes (bs256,
32x32x3, bf16) and compares against the v5e peaks, answering which
component is below its own ceiling:

- full train step (the bench reference point);
- forward only / forward+backward (where the gap opens);
- the adam update alone (pure HBM bandwidth over ~25.6M params);
- ONE bottleneck block per stage at its live shape (which stage's convs
  under-fill the MXU — CIFAR spatial dims shrink to 4x4 by stage 4);
- the stem conv alone (3->64: contraction depth 27 over a 128-deep MXU
  — a structural under-fill no tuning can fix);
- the same full step under f32 (is bf16 actually engaged end-to-end?).

FLOPs come from XLA's own cost analysis of each compiled program (conv
FLOP bookkeeping by hand is error-prone).  One JSON line per component;
persisted to ``experiments/bench_runs.jsonl`` (kind=resnet_attribution).

Run on the chip (never ran, ROADMAP S4):
``python experiments/resnet/attribution_r5.py``
(``ATTRIB_SMOKE=1`` for a tiny CPU harness check).
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np

import bench

SMOKE = bool(int(os.environ.get("ATTRIB_SMOKE", "0")))
B = 32 if SMOKE else int(os.environ.get("BENCH_RESNET_BATCH", 256))
ITERS, WARMUP = (3, 1) if SMOKE else (30, 5)
PEAK_TFLOPS = 197.0  # v5e bf16
PEAK_HBM_GBS = 819.0


def _time(fn, *args):
    out = None
    for _ in range(WARMUP):
        out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(ITERS):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _xla_flops(jitted, *args) -> float:
    cost = jitted.lower(*args).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost["flops"])


def report(name, secs, flops=None, bytes_moved=None, note=""):
    rec = {"kind": "resnet_attribution", "component": name,
           "time_ms": round(secs * 1e3, 3), "batch": B}
    if flops:
        rec["tflops_per_s"] = round(flops / secs / 1e12, 1)
        rec["mxu_frac"] = round(flops / secs / 1e12 / PEAK_TFLOPS, 3)
    if bytes_moved:
        rec["gb_per_s"] = round(bytes_moved / secs / 1e9, 1)
        rec["hbm_frac"] = round(bytes_moved / secs / 1e9 / PEAK_HBM_GBS, 3)
    if note:
        rec["note"] = note
    print(json.dumps(rec), flush=True)
    if not SMOKE:
        bench._persist_record(rec)
    return rec


def full_model_pieces():
    """Forward / fwd+bwd / optimizer on the exact bench model."""
    import optax

    from rocket_tpu.models.resnet import resnet50

    model = resnet50(num_classes=10, small_images=True,
                     dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    img = jnp.asarray(rng.normal(0.5, 0.25, size=(B, 32, 32, 3)),
                      jnp.float32)
    lbl = jnp.asarray(rng.integers(0, 10, size=(B,)), jnp.int32)
    variables = jax.jit(
        lambda r, b: model.init(r, b, train=True)
    )(jax.random.PRNGKey(0), {"image": img})
    params, stats = variables["params"], variables["batch_stats"]

    def loss_fn(params, stats, img, lbl):
        out, mut = model.apply(
            {"params": params, "batch_stats": stats},
            {"image": img}, train=True, mutable=["batch_stats"],
        )
        logits = out["logits"].astype(jnp.float32)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, lbl
        ).mean()
        return loss, mut["batch_stats"]

    fwd = jax.jit(loss_fn)
    t = _time(fwd, params, stats, img, lbl)
    report("forward only (train mode)", t, flops=_xla_flops(
        fwd, params, stats, img, lbl))

    grad = jax.jit(jax.grad(loss_fn, has_aux=True))
    t = _time(grad, params, stats, img, lbl)
    report("forward+backward", t, flops=_xla_flops(
        grad, params, stats, img, lbl))

    tx = optax.adam(1e-3)
    opt_state = tx.init(params)
    g = jax.tree_util.tree_map(jnp.ones_like, params)

    @jax.jit
    def opt_step(p, g, s):
        u, s = tx.update(g, s, p)
        return optax.apply_updates(p, u), s

    t = _time(opt_step, params, g, opt_state)
    nbytes = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(params))
    # read p,m,v,g + write p,m,v = 7 passes over the param bytes
    report("adam update", t, bytes_moved=7 * nbytes)

    # f32 ablation of the full fwd+bwd: a small gap means bf16 never
    # engaged; a ~2x+ gap means it did and the ceiling is elsewhere
    model32 = resnet50(num_classes=10, small_images=True,
                       dtype=jnp.float32)
    v32 = jax.jit(
        lambda r, b: model32.init(r, b, train=True)
    )(jax.random.PRNGKey(0), {"image": img})

    def loss32(params, stats, img, lbl):
        out, mut = model32.apply(
            {"params": params, "batch_stats": stats},
            {"image": img}, train=True, mutable=["batch_stats"],
        )
        logits = out["logits"].astype(jnp.float32)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, lbl
        ).mean()
        return loss, mut["batch_stats"]

    grad32 = jax.jit(jax.grad(loss32, has_aux=True))
    t = _time(grad32, v32["params"], v32["batch_stats"], img, lbl)
    report("forward+backward f32 (ablation)", t, flops=_xla_flops(
        grad32, v32["params"], v32["batch_stats"], img, lbl))


def per_stage_blocks():
    """One bottleneck block per stage at its live CIFAR shape."""
    from functools import partial

    import flax.linen as nn

    from rocket_tpu.models.resnet import BottleneckBlock

    # (features, spatial, in_channels, strides) per ResNet-50 stage on
    # 32x32 inputs; stage 0 block 1 shape (past the projection block)
    stages = [
        ("stage1 block (32x32, 64f)", 64, 32, 256, (1, 1)),
        ("stage2 block (16x16, 128f)", 128, 16, 512, (1, 1)),
        ("stage3 block (8x8, 256f)", 256, 8, 1024, (1, 1)),
        ("stage4 block (4x4, 512f)", 512, 4, 2048, (1, 1)),
    ]
    if SMOKE:
        stages = stages[:1]
    for name, feat, hw, cin, strides in stages:
        conv = partial(nn.Conv, use_bias=False, dtype=jnp.bfloat16)
        norm = partial(nn.BatchNorm, use_running_average=False,
                       momentum=0.9, epsilon=1e-5, dtype=jnp.bfloat16)
        block = BottleneckBlock(feat, strides=strides, norm=norm, conv=conv)
        x = jnp.asarray(
            np.random.default_rng(1).normal(size=(B, hw, hw, cin)),
            jnp.bfloat16,
        )
        variables = jax.jit(block.init)(jax.random.PRNGKey(0), x)

        def loss_fn(params, stats, x):
            y, mut = block.apply(
                {"params": params, "batch_stats": stats}, x,
                mutable=["batch_stats"],
            )
            return jnp.sum(y.astype(jnp.float32)), mut

        grad = jax.jit(jax.grad(loss_fn, argnums=(0, 2), has_aux=True))
        args = (variables["params"], variables["batch_stats"], x)
        t = _time(grad, *args)
        report(name, t, flops=_xla_flops(grad, *args))

    # the stem: 3->64 3x3 conv — contraction depth 27 on a 128-deep MXU
    conv = nn.Conv(64, (3, 3), use_bias=False, dtype=jnp.bfloat16)
    x = jnp.asarray(
        np.random.default_rng(2).normal(size=(B, 32, 32, 3)), jnp.bfloat16
    )
    variables = jax.jit(conv.init)(jax.random.PRNGKey(0), x)

    def stem_loss(params, x):
        return jnp.sum(conv.apply(params, x).astype(jnp.float32))

    grad = jax.jit(jax.grad(stem_loss, argnums=(0, 1)))
    t = _time(grad, variables, x)
    report("stem conv 3->64 (depth-27 contraction)", t,
           flops=_xla_flops(grad, variables, x),
           note="structural MXU under-fill: 27/128 contraction depth")


def main():
    if not SMOKE:
        rec = bench.bench_resnet50(20, 3)
        report("full train step (bench)", rec["step_time_ms"] / 1e3,
               note=f"mfu={rec['mfu']}")
    full_model_pieces()
    per_stage_blocks()


if __name__ == "__main__":
    main()
