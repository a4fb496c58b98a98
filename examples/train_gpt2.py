"""GPT-2 124M language-model training (BASELINE.json config #3).

Demonstrates the LM pipeline: grad accumulation, cosine LR schedule with
warmup, gradient clipping, checkpoint + resume, flash attention.  Data is a
token file if given (``--data tokens.npy``: int32 ``[docs, seq]``; or
``--data train.bin``: a flat uint16 token stream, memory-mapped via
``TokenFileSource`` — the nanoGPT/OpenWebText layout), else a synthetic
Markov stream so the script runs anywhere.  With ``--stream`` the token
rows are consumed as a length-free iterator (reference parity: torch
IterableDataset through the loader, ``rocket/core/dataset.py:100-126``) —
resume still works because the stream replays deterministically.

    python examples/train_gpt2.py [--tiny] [--stream] [--resume path/to/ckpt]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import optax

import rocket_tpu as rt
from rocket_tpu.data.toys import synthetic_lm_tokens
from rocket_tpu.models.objectives import lm_cross_entropy
from rocket_tpu.models.transformer import TransformerConfig, TransformerLM


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--tiny", action="store_true", help="tiny config (CPU-friendly)")
    parser.add_argument(
        "--data", type=str, default=None,
        help="int32 [docs, seq] .npy, or a flat uint16 token stream .bin "
             "(nanoGPT-style train.bin, memory-mapped)",
    )
    parser.add_argument(
        "--stream", action="store_true",
        help="consume tokens as a length-free stream (IterableSource)",
    )
    parser.add_argument("--resume", type=str, default=None)
    parser.add_argument("--epochs", type=int, default=2)
    parser.add_argument(
        "--muon", action="store_true",
        help="Muon on hidden matrices + adamw on embeddings/rest "
             "(engine.muon; the paper's recommended split via param "
             "groups)",
    )
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--accum", type=int, default=2)
    parser.add_argument(
        "--fused", action="store_true",
        help="fused_qkv + fused_ce (logits-free loss); no benchmark "
             "cell runs either",
    )
    args = parser.parse_args()

    fused = dict(fused_qkv=True, fused_ce=True) if args.fused else {}
    data = bin_source = None
    if args.data and args.data.endswith(".bin"):
        # Flat uint16 token stream (nanoGPT-style train.bin), memory-mapped
        # and sliced into rows — never loaded into RAM; vocab_size= makes
        # the source fail fast on tokenizer mismatch.
        cfg = TransformerConfig.gpt2_124m(**fused)
        bin_source = rt.TokenFileSource(
            args.data, seq_len=cfg.max_seq, vocab_size=cfg.vocab_size
        )
    elif args.data:
        data = {"tokens": np.load(args.data).astype(np.int32)}
        vocab = int(data["tokens"].max()) + 1
        cfg = TransformerConfig.gpt2_124m(**fused)
        assert vocab <= cfg.vocab_size
    elif args.tiny:
        cfg = TransformerConfig.tiny(
            norm="layernorm", mlp="gelu", positions="learned",
            tie_embeddings=True, use_bias=True, **fused,
        )
        data = synthetic_lm_tokens(n_docs=256, seq_len=128, vocab=cfg.vocab_size)
    else:
        cfg = TransformerConfig.gpt2_124m(**fused)
        data = synthetic_lm_tokens(n_docs=256, seq_len=512, vocab=512)

    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0, peak_value=3e-4, warmup_steps=20,
        decay_steps=500, end_value=3e-5,
    )
    if args.muon:
        from rocket_tpu.engine.muon import hidden_matrices, muon

        # Muon gets its OWN warmup/decay (scaled to its 0.02 peak): a
        # ready tx= would take full-size orthogonalized steps from step 0
        # and never anneal, while the sibling Scheduler paces adamw only.
        muon_schedule = optax.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=0.02, warmup_steps=20,
            decay_steps=500, end_value=0.002,
        )
        optimizers = [
            rt.Optimizer(tx_factory=muon, params_filter=hidden_matrices,
                         schedule=muon_schedule, tag="lr_muon"),
            rt.Optimizer(
                tx_factory=optax.adamw, learning_rate=3e-4,
                grad_clip_norm=1.0, weight_decay=0.1,
                params_filter=lambda p, x: not hidden_matrices(p, x),
                tag="lr_adamw",
            ),
        ]
    else:
        optimizers = [
            rt.Optimizer(
                tx_factory=optax.adamw, learning_rate=3e-4,
                grad_clip_norm=1.0, weight_decay=0.1,
            ),
        ]
    model = rt.Module(
        TransformerLM(cfg),
        capsules=[
            rt.Loss(lm_cross_entropy(), name="lm"),
            *optimizers,
            rt.Scheduler(schedule),
        ],
    )
    eval_data = None
    if bin_source is not None:
        if args.stream:
            # Length-free view of the same memmapped rows.
            def bin_stream():
                for i in range(len(bin_source)):
                    yield bin_source[i]

            source = rt.GeneratorSource(bin_stream)
        else:
            source = bin_source
    elif args.stream:
        # Length-free streaming: rows leave the token store one at a time
        # (stand-in for an OpenWebText shard reader); the loader shards the
        # stream per host and shuffles through a seeded buffer.
        tokens = data["tokens"]

        def row_stream():
            for row in tokens:
                yield {"tokens": row}

        source = rt.GeneratorSource(row_stream)
    else:
        # Hold out the last 5% of rows for the eval pass; train on the
        # rest (fused_ce models score token_nll directly).
        n_eval = max(1, len(data["tokens"]) // 20)
        eval_data = {"tokens": data["tokens"][-n_eval:]}
        data = {"tokens": data["tokens"][:-n_eval]}
        source = rt.ArraySource(data)
    loopers = [
        rt.Looper(
            capsules=[
                rt.Dataset(source, batch_size=args.batch, shuffle=True),
                model,
                rt.Tracker("jsonl"),
                rt.Checkpointer(save_every=50, keep_last=2),
            ]
        )
    ]
    if eval_data is not None:
        # statefull=False: eval loop/data state is trivially re-derivable,
        # and keeping it out of the checkpointable topology means
        # checkpoints from the train-only script version still resume.
        loopers.append(
            rt.Looper(
                capsules=[
                    rt.Dataset(rt.ArraySource(eval_data),
                               batch_size=args.batch, statefull=False),
                    model,
                    rt.Meter(capsules=[rt.Perplexity()], mode="in_step"),
                    rt.Tracker("jsonl"),
                ],
                grad_enabled=False,
                statefull=False,
            )
        )
    launcher = rt.Launcher(
        capsules=loopers,
        tag="gpt2",
        num_epochs=args.epochs,
        mixed_precision="bf16",
        gradient_accumulation_steps=args.accum,
    )
    if args.resume:
        launcher.resume(args.resume)
    launcher.launch()
    print(f"done: {model.step} optimizer steps")


if __name__ == "__main__":
    main()
