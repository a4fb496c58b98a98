"""Train → generate → quantize → generate again, end to end.

The reference has no generation path at all (SURVEY §2: the framework
stops at training); this demo shows the serving half of the TPU build:

1. train a small LM on the synthetic Markov stream for a few epochs via
   the capsule pipeline (same API as examples/train_gpt2.py);
2. KV-cache decode continuations with temperature / top-k / top-p
   (``models.generate``);
3. rewrite the trained weights into the int8 W8A16 layout
   (``ops.quant.quantize_params``) and decode again — same tokens API,
   half the weight bytes per decoded token (``docs/performance.md``,
   "Decode (serving) configs");
4. report per-path decode wall time and the fraction of continuations
   the two paths agree on (greedy argmax can differ at quantization
   error; on the learned Markov structure agreement stays high).

    python examples/generate_demo.py [--epochs 3]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import rocket_tpu as rt  # noqa: E402
from rocket_tpu.data.toys import synthetic_lm_tokens  # noqa: E402
from rocket_tpu.models.generate import generate  # noqa: E402
from rocket_tpu.models.objectives import lm_cross_entropy  # noqa: E402
from rocket_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    TransformerLM,
)
from rocket_tpu.ops.quant import quantize_params  # noqa: E402

VOCAB, SEQ = 256, 128


def _cfg(**kw):
    return TransformerConfig(
        vocab_size=VOCAB, hidden=128, n_layers=2, n_heads=4, max_seq=SEQ,
        norm="layernorm", mlp="gelu", positions="learned",
        tie_embeddings=True, use_bias=True, attention="dot", **kw,
    )


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--epochs", type=int, default=3)
    parser.add_argument("--prompt-len", type=int, default=16)
    parser.add_argument("--new-tokens", type=int, default=32)
    args = parser.parse_args()

    data = synthetic_lm_tokens(n_docs=512, seq_len=SEQ, vocab=VOCAB)

    module = rt.Module(
        TransformerLM(_cfg()),
        capsules=[
            rt.Loss(lm_cross_entropy(), name="lm"),
            rt.Optimizer(learning_rate=3e-4),
        ],
    )
    launcher = rt.Launcher(
        capsules=[
            rt.Looper(
                capsules=[
                    rt.Dataset(
                        rt.ArraySource({"tokens": data["tokens"]}),
                        batch_size=32, shuffle=True,
                    ),
                    module,
                ],
            )
        ],
        tag="generate_demo",
        num_epochs=args.epochs,
        mixed_precision="bf16",
    )
    launcher.launch()

    import flax.linen as nn

    params = nn.meta.unbox(module.state.params)
    prompts = jnp.asarray(
        data["tokens"][:4, : args.prompt_len], jnp.int32
    )

    model = TransformerLM(_cfg())
    qmodel = TransformerLM(_cfg(weights_int8=True))
    qparams = jax.jit(quantize_params)(params)

    def timed(model_, params_, label, **sample_kw):
        t0 = time.perf_counter()
        toks = generate(
            model_, params_, prompts, max_new_tokens=args.new_tokens,
            **sample_kw,
        )
        toks.block_until_ready()
        dt = time.perf_counter() - t0
        print(f"  {label:28s} {dt * 1e3:8.1f} ms  "
              f"first row: {np.asarray(toks)[0, args.prompt_len:][:12]}")
        return np.asarray(toks)

    print("greedy (temperature=0):")
    bf16 = timed(model, params, "bf16", temperature=0.0)
    int8 = timed(qmodel, qparams, "int8 weights", temperature=0.0)
    agree = (bf16[:, args.prompt_len:] == int8[:, args.prompt_len:]).mean()
    print(f"  greedy agreement bf16 vs int8: {agree:.1%}")

    print("sampled:")
    timed(model, params, "temperature=0.8 top_k=40", temperature=0.8,
          top_k=40)
    timed(model, params, "temperature=0.9 top_p=0.95", temperature=0.9,
          top_p=0.95)

    # speculative decoding: the int8-quantized model drafts for the bf16
    # target (same weights, quantized — high agreement, half the draft
    # bandwidth); output is bit-identical to the target's plain greedy
    from rocket_tpu.models.generate import speculative_generate

    one = prompts[:1]
    # the exactness contract is against a batch-1 greedy decode (a
    # batch-4 forward may reassociate reductions and flip argmax ties)
    plain = generate(model, params, one, max_new_tokens=args.new_tokens,
                     temperature=0.0)
    spec, stats = speculative_generate(
        model, params, qmodel, qparams, one,
        max_new_tokens=args.new_tokens, n_draft=4, return_stats=True,
    )
    assert np.array_equal(np.asarray(plain), np.asarray(spec))
    rate = stats["accepted"] / max(stats["drafted"], 1)
    print(f"speculative (int8 draft): exact match in {stats['rounds']} "
          f"target forwards for {args.new_tokens} tokens "
          f"(acceptance {rate:.0%})")

    # sampled flavor: rejection-based, emitted tokens exactly
    # target-distributed whatever the draft proposes
    from rocket_tpu.models.generate import speculative_sample

    _, sstats = speculative_sample(
        model, params, qmodel, qparams, one,
        max_new_tokens=args.new_tokens, n_draft=4, temperature=0.8,
        seed=0, return_stats=True,
    )
    srate = sstats["accepted"] / max(sstats["drafted"], 1)
    print(f"speculative sampling (T=0.8): {args.new_tokens} tokens in "
          f"{sstats['rounds']} target forwards (acceptance {srate:.0%})")

    # serving-shaped: the batched device-resident variant decodes ALL
    # four prompts in one dispatch (per-row KV frontiers, no per-token
    # host sync) and still matches the plain greedy batch bit for bit
    from rocket_tpu.models.generate import speculative_generate_batched

    t0 = time.perf_counter()
    btoks, bstats = speculative_generate_batched(
        model, params, qmodel, qparams, prompts,
        max_new_tokens=args.new_tokens, n_draft=4, return_stats=True,
    )
    jax.block_until_ready(btoks)
    dt = time.perf_counter() - t0
    assert np.array_equal(np.asarray(btoks), bf16)
    brate = bstats["accepted"].sum() / max(bstats["drafted"].sum(), 1)
    print(f"speculative batched (B={prompts.shape[0]}): exact match, "
          f"{bstats['rounds']} rounds, one dispatch, {dt * 1e3:.1f} ms "
          f"(acceptance {brate:.0%}, per-row "
          f"{bstats['accepted'].tolist()}/{bstats['drafted'].tolist()})")


if __name__ == "__main__":
    main()
