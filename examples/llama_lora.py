"""Llama-style LoRA fine-tune, GSPMD-sharded (BASELINE.json config #4).

The full Llama-2 7B recipe on a pod slice is exactly this script with
``TransformerConfig.llama2_7b(lora_rank=16)`` and a real checkpoint loaded
via ``launcher.resume(path, load_capsules=False)`` (weights-only restore —
optimizer state starts fresh, sharded direct to mesh).  By default it runs a
scaled-down Llama so the full path (RoPE/RMSNorm/SwiGLU/GQA + frozen base +
trainable adapters + fsdp/tensor sharding) executes anywhere.

    python examples/llama_lora.py [--mesh fsdp=2,tensor=2] [--weights ckpt]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import rocket_tpu as rt
from rocket_tpu.data.toys import synthetic_lm_tokens
from rocket_tpu.models.lora import is_lora
from rocket_tpu.models.objectives import lm_cross_entropy
from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
from rocket_tpu.parallel.mesh import MeshSpec


def parse_mesh(text):
    spec = {}
    if text:
        for part in text.split(","):
            axis, size = part.split("=")
            spec[axis.strip()] = int(size)
    return MeshSpec(**spec) if spec else None


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--full-7b", action="store_true")
    parser.add_argument("--weights", type=str, default=None,
                        help="checkpoint dir for weights-only resume")
    parser.add_argument("--mesh", type=str, default=None, help="e.g. fsdp=2,tensor=2")
    parser.add_argument("--rank", type=int, default=8)
    parser.add_argument("--epochs", type=int, default=1)
    args = parser.parse_args()

    if args.full_7b:
        cfg = TransformerConfig.llama2_7b(
            lora_rank=args.rank, remat=True, scan_layers=True
        )
    else:
        cfg = TransformerConfig(
            vocab_size=512, hidden=256, n_layers=4, n_heads=8, n_kv_heads=4,
            max_seq=256, lora_rank=args.rank,
        )
    data = synthetic_lm_tokens(
        n_docs=128, seq_len=min(256, cfg.max_seq), vocab=cfg.vocab_size
    )

    model = rt.Module(
        TransformerLM(cfg),
        capsules=[
            rt.Loss(lm_cross_entropy(), name="lm"),
            # Base weights frozen; only LoRA adapters train.
            rt.Optimizer(learning_rate=1e-4, params_filter=is_lora),
        ],
    )
    launcher = rt.Launcher(
        capsules=[
            rt.Looper(
                capsules=[
                    rt.Dataset(rt.ArraySource(data), batch_size=8, shuffle=True),
                    model,
                    rt.Tracker("jsonl"),
                    rt.Checkpointer(save_every=100),
                ]
            )
        ],
        tag="llama-lora",
        num_epochs=args.epochs,
        mesh=parse_mesh(args.mesh),
        mixed_precision="bf16",
    )
    if args.weights:
        launcher.resume(args.weights, load_capsules=False)
    launcher.launch()
    print(f"done: {model.step} adapter steps")


if __name__ == "__main__":
    main()
