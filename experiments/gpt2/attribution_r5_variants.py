"""Candidate-fix microbenches for the GPT-2 step-time ceiling (round 5).

``attribution_r4.py`` answers "which component is below its own
ceiling"; this answers "which replacement wins" — so ONE chip
session yields both the diagnosis and the lever ordering.  All variants
run at the bench shapes (T = 16x1024 tokens, H=768, V=50304, bf16
weights) as standalone jitted fwd+bwd programs:

CE variants (the budget's #2 lever — the [T, V] logits tensor costs
~10 ms of HBM traffic in the unfused path):
  - unfused f32 logits (the measured default);
  - unfused bf16 logits (halved logits bytes; f32 logsumexp accum);
  - chunked logits-free ``ops.fused_ce`` at chunk 1024 / 4096 / 8192
    (the round-4 end-to-end loser — component numbers show why: its
    backward re-materializes chunk logits AND accumulates the full
    f32 dW across every scan step).

Projection-chain variants (the #1 FLOP block):
  - three separate q/k/v matmuls vs one fused [H, 3H] (r4 measured
    fused SLOWER end-to-end; per-component numbers isolate whether the
    matmul itself or downstream fusion is responsible);

Optimizer variants (pure bandwidth):
  - adamw f32 moments vs ``mu_dtype=bf16`` over 124M params.

One JSON line per variant (kind=variant), persisted to
``experiments/bench_runs.jsonl``.  Run on the chip (never ran):
``python experiments/gpt2/attribution_r5_variants.py``
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
T, H, V = (512, 128, 1024) if SMOKE else (16 * 1024, 768, 50304)
ITERS, WARMUP = (3, 1) if SMOKE else (30, 5)
PEAK_TFLOPS = 197.0  # device-aware value set in main() after init


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


def report(name, secs, flops=None, note=""):
    rec = {"kind": "variant", "component": name,
           "time_ms": round(secs * 1e3, 3)}
    if flops:
        rec["tflops_per_s"] = round(flops / secs / 1e12, 1)
        rec["mxu_frac"] = round(flops / secs / 1e12 / PEAK_TFLOPS, 3)
    if note:
        rec["note"] = note
    print(json.dumps(rec), flush=True)
    if not SMOKE:
        bench._persist_record(rec)
    return rec


def ce_variants(key):
    x = jax.random.normal(key, (T, H), jnp.bfloat16)
    emb = jax.random.normal(key, (V, H), jnp.bfloat16)
    ids = jax.random.randint(key, (T,), 0, V)
    # fwd (x@E^T) + dx + dW — the 3-matmul budget every variant shares
    ce_flops = 2.0 * T * H * V * 3

    def ce_f32(x, emb):
        logits = jax.lax.dot_general(
            x, emb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ids[:, None], axis=-1)[:, 0]
        return jnp.mean(lse - gold)

    step = jax.jit(jax.grad(ce_f32, argnums=(0, 1)))
    report("ce unfused f32 logits", _time(step, x, emb), flops=ce_flops)

    def ce_bf16(x, emb):
        # logits stay bf16 in HBM (half the bytes); the logsumexp
        # accumulates in f32 via the standard max-subtraction
        logits = x @ emb.T  # bf16
        m = jnp.max(logits, axis=-1, keepdims=True)
        lse = jnp.log(jnp.sum(
            jnp.exp((logits - m).astype(jnp.float32)), axis=-1
        )) + m[:, 0].astype(jnp.float32)
        gold = jnp.take_along_axis(logits, ids[:, None], axis=-1)[:, 0]
        return jnp.mean(lse - gold.astype(jnp.float32))

    step = jax.jit(jax.grad(ce_bf16, argnums=(0, 1)))
    report("ce unfused bf16 logits", _time(step, x, emb), flops=ce_flops)

    from rocket_tpu.ops.fused_ce import linear_cross_entropy

    # smoke must still exercise the fused path (clamp, dedup), not skip it
    for chunk in sorted({min(c, T) for c in (1024, 4096, 8192)}):

        def ce_fused(x, emb, chunk=chunk):
            return jnp.mean(linear_cross_entropy(
                x, emb, ids, chunk_size=chunk))

        step = jax.jit(jax.grad(ce_fused, argnums=(0, 1)))
        report(f"ce fused chunk {chunk}", _time(step, x, emb),
               flops=ce_flops,
               note="bwd recomputes chunk logits (checkpoint) + "
                    "scan-accumulates f32 dW")


def proj_variants(key):
    x = jax.random.normal(key, (T, H), jnp.bfloat16)
    wq = jax.random.normal(key, (H, H), jnp.bfloat16)
    wk = jax.random.normal(key, (H, H), jnp.bfloat16)
    wv = jax.random.normal(key, (H, H), jnp.bfloat16)
    wqkv = jax.random.normal(key, (H, 3 * H), jnp.bfloat16)
    flops = 2.0 * T * H * 3 * H * 3  # three H->H fwd + dx + dW

    def sep(x, wq, wk, wv):
        q, k, v = x @ wq, x @ wk, x @ wv
        return jnp.sum((q + k + v).astype(jnp.float32))

    step = jax.jit(jax.grad(sep, argnums=(0, 1, 2, 3)))
    report("qkv three separate matmuls", _time(step, x, wq, wk, wv),
           flops=flops)

    def fused(x, wqkv):
        y = x @ wqkv
        q, k, v = jnp.split(y, 3, axis=-1)
        return jnp.sum((q + k + v).astype(jnp.float32))

    step = jax.jit(jax.grad(fused, argnums=(0, 1)))
    report("qkv one fused [H,3H] matmul", _time(step, x, wqkv),
           flops=flops)


def optimizer_variants():
    import optax

    nparams = 1_048_576 if SMOKE else 124_475_904
    p = {"w": jnp.zeros((nparams // 1024, 1024), jnp.float32)}
    g = jax.tree_util.tree_map(jnp.ones_like, p)
    for name, kw, passes in (
        ("adamw f32 moments", {}, 7),
        # only mu shrinks (nu has no dtype knob): 6 f32-equivalent passes
        ("adamw bf16 first moment", {"mu_dtype": jnp.bfloat16}, 6),
    ):
        tx = optax.adamw(1e-4, **kw)
        s = tx.init(p)

        @jax.jit
        def step(p, g, s, tx=tx):
            u, s2 = tx.update(g, s, p)
            return optax.apply_updates(p, u), s2

        t = _time(step, p, g, s)
        gbs = passes * nparams * 4 / t / 1e9
        report(name, t, note=f"~{passes} f32-equiv passes -> "
                             f"{gbs:.0f} GB/s apparent")


def main():
    global PEAK_TFLOPS
    if not SMOKE:
        PEAK_TFLOPS = bench.peak_flops_per_chip() / 1e12  # not always v5e
    key = jax.random.PRNGKey(0)
    ce_variants(key)
    proj_variants(key)
    optimizer_variants()


if __name__ == "__main__":
    main()
