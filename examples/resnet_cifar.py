"""ResNet-50 on CIFAR-10-shaped data, data-parallel (BASELINE.json #1).

Every visible device joins the ``data`` mesh axis (the reference's DDP
topology); BatchNorm statistics update inside the jitted step.  Real CIFAR
loads from ``--data`` as ``.npz`` with ``image`` uint8 ``[N,32,32,3]`` +
``label``; synthetic otherwise.

    python examples/resnet_cifar.py [--small]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import rocket_tpu as rt
from rocket_tpu.models.objectives import cross_entropy
from rocket_tpu.models.resnet import ResNet, resnet50
from examples.mnist import Accuracy


def synthetic_cifar(n=4096, seed=0):
    rng = np.random.default_rng(seed)
    protos = rng.normal(0.5, 0.2, size=(10, 32, 32, 3)).astype(np.float32)
    labels = rng.integers(0, 10, size=n)
    images = protos[labels] + rng.normal(0, 0.15, size=(n, 32, 32, 3))
    return {
        "image": np.clip(images, 0, 1).astype(np.float32),
        "label": labels.astype(np.int32),
    }


def augment(sample):
    """Standard CIFAR train-time augmentation (random crop with 4px pad +
    horizontal flip) — pure numpy per sample, so fork workers
    (``--workers``) parallelize it off the host's critical path.  Uses
    the process-global RNG: crops vary per epoch, and the loader's
    worker init decorrelates the streams across forked workers."""
    img = sample["image"]
    padded = np.pad(img, ((4, 4), (4, 4), (0, 0)), mode="reflect")
    dy, dx = np.random.randint(0, 9, size=2)
    img = padded[dy:dy + 32, dx:dx + 32]
    if np.random.randint(2):
        img = img[:, ::-1]
    return {**sample, "image": np.ascontiguousarray(img)}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--data", type=str, default=None)
    parser.add_argument("--small", action="store_true", help="ResNet-8-ish for CPU")
    parser.add_argument("--epochs", type=int, default=3)
    parser.add_argument(
        "--workers", type=int, default=0,
        help="fork worker processes for the data pipeline "
             "(Dataset num_workers)",
    )
    parser.add_argument(
        "--augment", action="store_true",
        help="random-crop + flip train augmentation (use with real CIFAR "
             "--data; the synthetic protos task is pixel-aligned and "
             "augmentation defeats it)",
    )
    args = parser.parse_args()

    if args.data:
        blob = np.load(args.data)
        data = {
            "image": blob["image"].astype(np.float32) / 255.0,
            "label": blob["label"].astype(np.int32),
        }
    else:
        data = synthetic_cifar()

    if args.small:
        model_def = ResNet(
            stage_sizes=(1, 1), num_classes=10, width=16, small_images=True
        )
    else:
        model_def = resnet50(num_classes=10, small_images=True)

    model = rt.Module(
        model_def,
        capsules=[
            rt.Loss(cross_entropy(labels_key="label"), name="ce"),
            rt.Optimizer(learning_rate=1e-3),
        ],
    )
    accuracy = Accuracy()
    launcher = rt.Launcher(
        capsules=[
            rt.Looper(
                capsules=[
                    rt.Dataset(
                        rt.MapSource(rt.ArraySource(data), augment)
                        if args.augment else rt.ArraySource(data),
                        batch_size=256, shuffle=True,
                        num_workers=args.workers,
                    ),
                    model,
                    rt.Tracker("jsonl"),
                ]
            ),
            rt.Looper(
                capsules=[
                    rt.Dataset(rt.ArraySource(data), batch_size=256),
                    model,
                    rt.Meter(keys=["logits", "label"], capsules=[accuracy]),
                    rt.Tracker("jsonl"),
                ],
                grad_enabled=False,
                run_every=1,
            ),
        ],
        tag="resnet-cifar",
        num_epochs=args.epochs,
        mixed_precision="bf16",
    )
    launcher.launch()
    print("final accuracy:", accuracy.last)


if __name__ == "__main__":
    main()
