"""MNIST — the canonical pipeline (reference ``examples/mnist.py``).

The reference example is stale against its own library (SURVEY §2.4: wrong
kwargs, missing import, never calls ``.launch()``); this one is the working
equivalent: a LeNet classifier, a cross-entropy Loss, an Adam Optimizer, an
Accuracy Metric behind a Meter, tensorboard tracking, periodic checkpoints —
assembled as a capsule tree and launched.

Runs on anything: one CPU, one TPU chip, or a pod slice (the mesh defaults
to data-parallel over every visible device).  Uses real MNIST if
``$MNIST_DIR`` points at the IDX files, synthetic digits otherwise.

    python examples/mnist.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import rocket_tpu as rt
from rocket_tpu.data.toys import mnist
from rocket_tpu.models.lenet import LeNet
from rocket_tpu.models.objectives import cross_entropy


class Accuracy(rt.Metric):
    """Eval accuracy over the (globally gathered, dedup-masked) batches —
    the reference example's metric (``mnist.py:20-39``)."""

    def __init__(self, tag: str = "accuracy", priority: int = 1000):
        super().__init__(priority=priority)
        self._tag = tag
        self._correct = 0
        self._total = 0
        self.last = None

    def launch(self, attrs=None):
        batch = attrs.batch
        pred = np.asarray(batch["logits"]).argmax(-1)
        label = np.asarray(batch["label"])
        self._correct += int((pred == label).sum())
        self._total += len(label)

    def reset(self, attrs=None):
        if not self._total:
            return
        self.last = self._correct / self._total
        print(f"eval accuracy: {self.last:.4f} ({self._total} samples)")
        if attrs is not None and attrs.tracker is not None:
            attrs.tracker.scalars.append(
                rt.Attributes(step=self._step, data={self._tag: self.last})
            )
        self._correct = 0
        self._total = 0


def main():
    import argparse

    parser = argparse.ArgumentParser()
    # 6 epochs reproduces the committed 99.09% north-star log
    # (experiments/mnist/v0/logs/metrics.jsonl)
    parser.add_argument("--epochs", type=int, default=6)
    parser.add_argument(
        "--quick", action="store_true",
        help="small easy synthetic set (smoke run)",
    )
    args = parser.parse_args()

    if args.quick:
        from rocket_tpu.data.toys import synthetic_mnist

        train_data, test_data = synthetic_mnist()  # always small + easy
    else:
        # MNIST-sized hard synthetic set (real IDX files via $MNIST_DIR
        # take precedence) — the ≥99% north-star workload
        # (BASELINE.json configs[0]).
        train_data, test_data = mnist(n_train=60000, n_test=10000, hard=True)

    model = rt.Module(
        LeNet(num_classes=10),
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
                        rt.ArraySource(train_data),
                        batch_size=128,
                        shuffle=True,
                    ),
                    model,
                    rt.Tracker(["tensorboard", "jsonl"]),
                    rt.Checkpointer(save_every=500),
                ]
            ),
            rt.Looper(
                capsules=[
                    rt.Dataset(rt.ArraySource(test_data), batch_size=256),
                    model,
                    rt.Meter(keys=["logits", "label"], capsules=[accuracy]),
                    rt.Tracker(["tensorboard", "jsonl"]),
                ],
                grad_enabled=False,
            ),
        ],
        tag="mnist",
        num_epochs=args.epochs,
        mixed_precision="bf16",
    )
    print(launcher)  # config dump (reference §3.5)
    launcher.launch()
    assert accuracy.last is not None and accuracy.last > 0.99, (
        f"expected ≥99% accuracy, got {accuracy.last}"
    )
    print("PASSED: accuracy", accuracy.last)


if __name__ == "__main__":
    main()
