"""Component-level attribution of the GPT-2 124M step time on a real chip.

The bench's best measured point (bs16x1024, blocks 512/1024) reaches
0.459 MFU; the 50% north star asks where the remaining time goes.  An
xplane trace answers "which fused op", but the actionable question is
"which *component* is below its own ceiling" — so this times each
component as its own jitted program on the bench shapes and compares
against the v5e peaks (197 bf16 TFLOP/s MXU, ~819 GB/s HBM):

- flash attention fwd+bwd alone (the pallas kernels);
- the MLP/projection matmul chain alone (pure MXU work);
- tied unembed matmul + softmax-CE (the vocab-sized tail);
- embedding gather fwd + scatter-add bwd (the other half of tying);
- the adamw update alone (pure HBM bandwidth);
- the full train step (the reference point the pieces must sum to).

Writes one JSON line per component to stdout and appends them to
``experiments/bench_runs.jsonl`` (kind=attribution).  Run on the chip
(never ran, ROADMAP S3): ``python experiments/gpt2/attribution_r4.py``.
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

SMOKE = bool(int(os.environ.get("ATTRIB_SMOKE", "0")))  # tiny CPU check
B, S, H, D, L = 16, 1024, 12, 64, 12
HID, FF, V = 768, 3072, 50304
BLOCK_Q, BLOCK_K = 512, 1024
if SMOKE:
    B, S, H, D, L = 2, 256, 4, 64, 2
    HID, FF, V = 256, 1024, 1024
    BLOCK_Q, BLOCK_K = 128, 128
PEAK_TFLOPS = 197.0  # v5e bf16
PEAK_HBM_GBS = 819.0


def _time(fn, *args, iters=3 if SMOKE else 30, warmup=1 if SMOKE else 5):
    """Median wall time of a jitted fn; blocks on the final output."""
    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def report(name, secs, flops=None, bytes_moved=None, note=""):
    rec = {"kind": "attribution", "component": name,
           "time_ms": round(secs * 1e3, 3)}
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


def main():
    key = jax.random.PRNGKey(0)

    # -- flash attention fwd+bwd, ONE layer's shapes (extrapolated xL in
    # the note; the summed components compare against the full step)
    from rocket_tpu.ops.flash import flash_attention

    q = jax.random.normal(key, (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(key, (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(key, (B, S, H, D), jnp.bfloat16)

    def attn_loss(q, k, v):
        o = flash_attention(q, k, v, causal=True,
                            block_q=BLOCK_Q, block_k=BLOCK_K)
        return jnp.sum(o.astype(jnp.float32))

    attn_step = jax.jit(jax.grad(attn_loss, argnums=(0, 1, 2)))
    t = _time(attn_step, q, k, v)
    # causal fwd 2*S*S*D*2 halved, bwd ~2.5x fwd (dq + dkv re-run scores)
    attn_flops_1l = 2 * (B * H * S * S * D * 2) / 2 * 3.5
    report("flash_attention fwd+bwd (1 layer)", t, flops=attn_flops_1l,
           note=f"x{L} layers = {round(t*1e3*L, 1)} ms/step share")

    # -- the projection + MLP matmul chain of one layer, fwd+bwd
    wqkv = jax.random.normal(key, (HID, 3 * HID), jnp.bfloat16)
    wo = jax.random.normal(key, (HID, HID), jnp.bfloat16)
    w1 = jax.random.normal(key, (HID, FF), jnp.bfloat16)
    w2 = jax.random.normal(key, (FF, HID), jnp.bfloat16)
    x = jax.random.normal(key, (B * S, HID), jnp.bfloat16)

    def mlp_loss(x, wqkv, wo, w1, w2):
        y = x @ wqkv
        y = y[:, :HID] @ wo
        y = jax.nn.gelu(y @ w1) @ w2
        return jnp.sum(y.astype(jnp.float32))

    mlp_step = jax.jit(jax.grad(mlp_loss, argnums=(0, 1, 2, 3, 4)))
    t = _time(mlp_step, x, wqkv, wo, w1, w2)
    mm_flops = 2 * B * S * (HID * 3 * HID + HID * HID + 2 * HID * FF) * 3
    report("proj+mlp matmuls fwd+bwd (1 layer)", t, flops=mm_flops,
           note=f"x{L} layers = {round(t*1e3*L, 1)} ms/step share")

    # -- unembed matmul + softmax-CE fwd+bwd
    emb = jax.random.normal(key, (V, HID), jnp.bfloat16)
    ids = jax.random.randint(key, (B * S,), 0, min(50257, V))

    def ce_loss(x, emb):
        logits = (x @ emb.T).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ids[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - gold)

    ce_step = jax.jit(jax.grad(ce_loss, argnums=(0, 1)))
    t = _time(ce_step, x, emb)
    ce_flops = 2 * B * S * HID * V * 3
    report("unembed matmul + CE fwd+bwd", t, flops=ce_flops)

    # -- embedding gather fwd + scatter-add bwd
    def emb_loss(emb):
        return jnp.sum(emb[ids].astype(jnp.float32))

    emb_step = jax.jit(jax.grad(emb_loss))
    t = _time(emb_step, emb)
    report("embedding gather+scatter bwd", t,
           bytes_moved=2 * B * S * HID * 2 + V * HID * 4)

    # -- adamw update alone over a 124M-param pytree (pure bandwidth)
    import optax

    nparams = 1_048_576 if SMOKE else 124_475_904
    p = {"w": jnp.zeros((nparams // 1024, 1024), jnp.float32)}
    g = jax.tree_util.tree_map(jnp.ones_like, p)
    tx = optax.adamw(1e-4)
    opt_state = tx.init(p)

    @jax.jit
    def opt_step(p, g, s):
        u, s = tx.update(g, s, p)
        return optax.apply_updates(p, u), s

    t = _time(opt_step, p, g, opt_state)
    # read p,m,v,g + write p,m,v — 7 f32 passes over 124M params
    report("adamw update (124M params)", t,
           bytes_moved=7 * nparams * 4)

    # -- the full train step at the same config, via the bench itself
    if not SMOKE:
        rec = bench.bench_gpt2(15, 3)
        report("full train step (bench)", rec["step_time_ms"] / 1e3,
               note=f"mfu={rec['mfu']}")


if __name__ == "__main__":
    main()
