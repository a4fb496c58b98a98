"""On-chip speculative-decode benchmark (VERDICT r4 next #3/#4).

Measures, on the real chip, the serving paths that round 4 left
CPU-only:

1. plain KV-cache ``generate`` (the baseline tokens/sec), B=1 and B=8;
2. the host-driven B=1 ``speculative_generate`` loop (round-4 design);
3. the device-resident ``speculative_generate_batched`` (round-5: fused
   draft scan + ``lax.while_loop``, per-row frontiers), B=1 and B=8 —
   the comparison that decides whether killing the per-token host sync
   pays on silicon.

Draft = the target quantized to int8 W8A16 (same weights → high
acceptance, half the weight bytes), mirroring ``examples/generate_demo``.
All variants are verified to emit EXACTLY the plain greedy tokens before
timing.  One JSON line per measurement; persisted to
``experiments/bench_runs.jsonl`` (kind=spec_decode).

Run: ``python experiments/spec_bench_r5.py`` (on the chip; never ran), or
``SPEC_SMOKE=1`` for a tiny CPU check of the harness itself.
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np

import bench

SMOKE = bool(int(os.environ.get("SPEC_SMOKE", "0")))
PROMPT, NEW, NDRAFT = 128, 128, 4
ITERS, WARMUP = (2, 1) if SMOKE else (10, 2)


def build():
    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
    from rocket_tpu.ops.quant import quantize_params

    if SMOKE:
        kw = dict(vocab_size=256, hidden=64, n_layers=2, n_heads=4,
                  norm="layernorm", mlp="gelu", positions="learned",
                  tie_embeddings=True, use_bias=True)
        cfg = TransformerConfig(max_seq=PROMPT + NEW + NDRAFT, **kw)
        qcfg = TransformerConfig(max_seq=PROMPT + NEW + NDRAFT,
                                 weights_int8=True, **kw)
    else:
        cfg = TransformerConfig.gpt2_124m(
            vocab_size=50304, max_seq=PROMPT + NEW + NDRAFT)
        qcfg = TransformerConfig.gpt2_124m(
            vocab_size=50304, max_seq=PROMPT + NEW + NDRAFT,
            weights_int8=True)
    model, qmodel = TransformerLM(cfg), TransformerLM(qcfg)
    rng = np.random.default_rng(0)
    prompt1 = jnp.asarray(
        rng.integers(0, min(cfg.vocab_size, 50257), size=(1, PROMPT)),
        jnp.int32)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0),
                                    {"tokens": prompt1})
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if isinstance(a, jax.Array) and jnp.issubdtype(a.dtype, jnp.floating)
        else a,
        variables["params"])
    qparams = jax.jit(quantize_params)(params)
    jax.block_until_ready(qparams)
    del variables
    prompt8 = jnp.asarray(
        rng.integers(0, min(cfg.vocab_size, 50257), size=(8, PROMPT)),
        jnp.int32)
    return model, params, qmodel, qparams, prompt1, prompt8


def report(name, secs_per_call, batch, extra=None):
    rec = {"kind": "spec_decode", "config": name,
           "value": round(batch * NEW / secs_per_call, 1),
           "unit": "tokens/sec/chip",
           "per_call_ms": round(secs_per_call * 1e3, 2),
           "batch": batch, "prompt": PROMPT, "new": NEW,
           "device": jax.devices()[0].device_kind}
    rec.update(extra or {})
    print(json.dumps(rec), flush=True)
    if not SMOKE:
        bench._persist_record(rec)
    return rec


def timeit(fn, iters=ITERS, warmup=WARMUP):
    out = None
    for _ in range(warmup):
        out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters, out


def main():
    from rocket_tpu.models.generate import (
        generate, speculative_generate, speculative_generate_batched)

    model, params, qmodel, qparams, prompt1, prompt8 = build()

    jgen = jax.jit(lambda p, pr: generate(model, p, pr, NEW,
                                          temperature=0.0))
    t1, want1 = timeit(lambda: jgen(params, prompt1))
    report("generate-b1", t1, 1)
    t8, want8 = timeit(lambda: jgen(params, prompt8))
    report("generate-b8", t8, 8)

    # host-loop B=1 speculative (round-4 design: one host sync per token)
    def host_spec():
        return speculative_generate(
            model, params, qmodel, qparams, prompt1, NEW,
            n_draft=NDRAFT, return_stats=True)
    th, (toks_h, stats_h) = timeit(host_spec)
    assert np.array_equal(np.asarray(toks_h), np.asarray(want1)), \
        "host-loop speculative diverged from plain greedy"
    acc_h = stats_h["accepted"] / max(stats_h["drafted"], 1)
    report("spec-host-b1", th, 1,
           {"acceptance": round(float(acc_h), 3),
            "rounds": stats_h["rounds"],
            "speedup_vs_generate": round(t1 / th, 3)})

    # device-resident batched speculative (round-5), B=1 then B=8
    for name, pr, want, base in (("spec-batched-b1", prompt1, want1, t1),
                                 ("spec-batched-b8", prompt8, want8, t8)):
        def dev_spec():
            return speculative_generate_batched(
                model, params, qmodel, qparams, pr, NEW,
                n_draft=NDRAFT, return_stats=True)
        td, (toks_d, stats_d) = timeit(dev_spec)
        assert np.array_equal(np.asarray(toks_d), np.asarray(want)), \
            f"{name} diverged from plain greedy"
        acc = stats_d["accepted"].sum() / max(stats_d["drafted"].sum(), 1)
        report(name, td, pr.shape[0],
               {"acceptance": round(float(acc), 3),
                "rounds": int(stats_d["rounds"]),
                "speedup_vs_generate": round(base / td, 3)})

    # batched speculative SAMPLING at T=0.8 (no exactness assert —
    # randomness differs from generate; acceptance is the story)
    from rocket_tpu.models.generate import speculative_sample_batched

    def dev_sample():
        return speculative_sample_batched(
            model, params, qmodel, qparams, prompt8, NEW, n_draft=NDRAFT,
            temperature=0.8, rng=jax.random.PRNGKey(0), return_stats=True)
    ts, (toks_s, stats_s) = timeit(dev_sample)
    acc = stats_s["accepted"].sum() / max(stats_s["drafted"].sum(), 1)
    report("spec-sample-batched-b8-T0.8", ts, 8,
           {"acceptance": round(float(acc), 3),
            "rounds": int(stats_s["rounds"]),
            "speedup_vs_generate": round(t8 / ts, 3)})


if __name__ == "__main__":
    main()
