"""Encoder-decoder demo: learn to REVERSE a token sequence.

The smallest task that actually needs the encoder-decoder shape (a
causal LM cannot look ahead, the encoder can): inputs are random token
rows, targets are the same rows reversed (with a BOS prefix).  A few
hundred steps reach high next-token accuracy on held-out rows.

    python examples/seq2seq_toy.py [--epochs N]

Runs anywhere (CPU/TPU); the pipeline is the standard capsule tree with
the EncoderDecoder model and the stock LM objective re-keyed to the
decoder side (tokens_key='targets').
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

import rocket_tpu as rt  # noqa: E402
from rocket_tpu.models import EncoderDecoder, Seq2SeqConfig  # noqa: E402
from rocket_tpu.models.objectives import lm_cross_entropy  # noqa: E402

VOCAB, SEQ, BOS = 64, 16, 1


def make_split(n, seed):
    rng = np.random.default_rng(seed)
    inputs = rng.integers(2, VOCAB, size=(n, SEQ)).astype(np.int32)
    # targets: BOS + reversed inputs (teacher forcing predicts the
    # reversal left to right)
    targets = np.concatenate(
        [np.full((n, 1), BOS, np.int32), inputs[:, ::-1]], axis=1
    )
    return {"inputs": inputs, "targets": targets}


class ReversalAccuracy(rt.StatMetric):
    """Next-token accuracy on the reversed positions (excludes BOS)."""

    def stats(self, batch):
        import jax.numpy as jnp

        pred = batch["logits"][:, :-1].argmax(-1)
        want = batch["targets"][:, 1:]
        hit = (pred == want).astype(jnp.float32)
        valid = batch.get("_valid")
        if valid is not None:
            hit = hit * valid.astype(jnp.float32)[:, None]
            count = valid.astype(jnp.float32).sum() * hit.shape[1]
        else:
            count = jnp.float32(hit.size)
        return {"hits": hit.sum(), "count": count}

    def finalize(self, stats):
        acc = float(stats["hits"]) / max(float(stats["count"]), 1.0)
        print(f"reversal accuracy: {acc:.4f}")
        return {"reversal_accuracy": acc}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--epochs", type=int, default=8)
    args = parser.parse_args()

    cfg = Seq2SeqConfig(
        vocab_size=VOCAB, hidden=128, n_encoder_layers=2,
        n_decoder_layers=2, n_heads=4, max_seq=SEQ + 1, attention="dot",
    )
    model_def = EncoderDecoder(cfg)
    model = rt.Module(
        model_def,
        capsules=[
            rt.Loss(lm_cross_entropy(tokens_key="targets"), name="rev"),
            rt.Optimizer(learning_rate=3e-3),
        ],
    )
    metric = ReversalAccuracy()
    launcher = rt.Launcher(
        capsules=[
            rt.Looper(capsules=[
                rt.Dataset(rt.ArraySource(make_split(4096, 0)),
                           batch_size=64, shuffle=True),
                model,
            ]),
            rt.Looper(capsules=[
                rt.Dataset(rt.ArraySource(make_split(512, 1)),
                           batch_size=128),
                model,
                rt.Meter(capsules=[metric], mode="in_step"),
                rt.Tracker("jsonl"),
            ], grad_enabled=False),
        ],
        tag="seq2seq-toy",
        num_epochs=args.epochs,
        mixed_precision="bf16",
    )
    launcher.launch()
    assert metric.last is not None
    print("final:", metric.last)

    # decode a few held-out examples greedily AND with beam search
    import jax.numpy as jnp

    from rocket_tpu.models.generate import (
        beam_search_seq2seq, generate_seq2seq)

    test = make_split(4, 2)
    inputs = jnp.asarray(test["inputs"][:4])
    params = {"params": model.state.params}
    greedy = generate_seq2seq(
        model_def, params, inputs, max_new_tokens=inputs.shape[1], bos_id=BOS
    )
    beam, scores = beam_search_seq2seq(
        model_def, params, inputs, max_new_tokens=inputs.shape[1],
        bos_id=BOS, eos_id=BOS, beam_size=4,  # ids 2.. are data; 1 never emits
    )
    for i in range(inputs.shape[0]):
        print(f"in : {list(map(int, inputs[i]))}")
        print(f"rev: {list(map(int, test['targets'][i][1:]))}")
        print(f"gr : {list(map(int, greedy[i][1:]))}")
        print(f"bm : {list(map(int, beam[i][1:]))} "
              f"(score {float(scores[i]):.2f})")


if __name__ == "__main__":
    main()
