"""CLI: ``python -m rocket_tpu.tune`` — run (or inspect) the autotuner.

Examples::

    # full search on the local chip, persist the winner
    python -m rocket_tpu.tune --seed-k 9 --rungs 3,8,20

    # rank the space with the cost model only (no probes)
    python -m rocket_tpu.tune --dry-run --top 10

    # CPU-proxy smoke (the tier-1 test's shape): a toy model whose
    # candidates are ranked over a v5e's published peaks
    JAX_PLATFORMS=cpu python -m rocket_tpu.tune --tiny --seed-k 2 \
        --rungs 2 --force
"""

from __future__ import annotations

import argparse
import json

from rocket_tpu.tune.cost_model import predict_point
from rocket_tpu.tune.search import autotune, device_identity
from rocket_tpu.tune.space import gpt2_space
from rocket_tpu.tune.store import canonical_tune_key


# The chip the ``--tiny`` CPU proxy models when it ranks candidates.
PROXY_DEVICE_KIND = "TPU v5 lite"


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="python -m rocket_tpu.tune")
    parser.add_argument("--model", default="gpt2")
    parser.add_argument("--tiny", action="store_true",
                        help="CPU-proxy space over a toy model, ranked "
                             "over a v5e's peaks")
    parser.add_argument("--seed-k", type=int, default=9,
                        help="cost-model-seeded survivors entering rung 0")
    parser.add_argument("--eta", type=int, default=3)
    parser.add_argument("--rungs", default="3,8,20",
                        help="comma-separated timed steps per rung")
    parser.add_argument("--warmup", type=int, default=1)
    parser.add_argument("--probe-timeout", type=float, default=600.0)
    parser.add_argument("--force", action="store_true",
                        help="search even when a matching record exists")
    parser.add_argument("--dry-run", action="store_true",
                        help="print the cost-model ranking, probe nothing")
    parser.add_argument("--top", type=int, default=10)
    args = parser.parse_args(argv)

    space = gpt2_space(tiny=args.tiny)
    # This process never initialises a JAX backend (its probe children
    # need the chip): the proxy names the chip it models, a real search
    # asks a short child what the machine holds.
    identity = None if args.tiny else device_identity()
    device_kind = PROXY_DEVICE_KIND if args.tiny else identity["device"]
    if args.dry_run:
        seen, ranked = set(), []
        for point in space.candidates():
            key = canonical_tune_key(space.bench_tune(point))
            if key in seen:
                continue
            seen.add(key)
            ranked.append(
                (predict_point(point, device_kind)["seconds"], point))
        ranked.sort(key=lambda item: item[0])
        for secs, point in ranked[:args.top]:
            print(json.dumps({"predicted_step_s": round(secs, 6),
                              "tune": point}))
        return 0

    record = autotune(
        model=args.model, space=space, force=args.force,
        seed_k=args.seed_k, eta=args.eta,
        rung_steps=tuple(int(s) for s in args.rungs.split(",")),
        warmup=args.warmup, probe_timeout_s=args.probe_timeout,
        identity=identity, device_kind=device_kind,
    )
    print(json.dumps({k: record[k] for k in
                      ("model", "device", "backend", "batch", "tune",
                       "value", "mfu", "probes") if k in record}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
