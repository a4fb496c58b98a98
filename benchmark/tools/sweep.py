"""Find the knee of an open-loop mix once, on the chip.

    python benchmark/tools/sweep.py --workload <name> --rates 1.2,1.0,0.8 \
        --seconds 40 --seed 1

Each rate runs the cell's own driver with ``rate_per_s`` (and the rows in
service at the start, rate x mean service time) replaced; the line printed
says whether the queue grew through the window.  The rate the cell keeps is
written into its traffic file by hand, with these readings in PERF.md.
"""

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=40.0)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--service-s", type=float, default=21.0,
                        help="mean seconds a request holds a row")
    args = parser.parse_args(argv)

    from benchmark import harness

    cell = harness.resolve_cell(args.workload)
    harness.require_chips(cell)
    kind = harness.load_kind(cell)
    rows = int(cell.config["serving"]["rows"])
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic["rate_per_s"] = rate
        cell.traffic["initial_in_service"] = min(
            rows, int(round(rate * args.service_s)))
        t0 = time.perf_counter()
        run = kind.drive(cell, seed=args.seed, seconds=args.seconds,
                         compiles=harness.CompileCounter(), trace=None,
                         process_start=t0)
        pct = lambda key, q: harness.percentile(run[key], q)  # noqa: E731
        print(json.dumps({
            "rate_per_s": rate, "initial": cell.traffic["initial_in_service"],
            "completed": run["completed"], "attempted": run["attempted"],
            "queue_depth_end": run["queue_depth_end"],
            "queue_wait_p50_ms": pct("queue_wait_ms", 50),
            "queue_wait_p90_ms": pct("queue_wait_ms", 90),
            "ttft_p50_ms": pct("ttft_ms", 50), "ttft_p80_ms": pct("ttft_ms", 80),
            "tpot_p50_ms": pct("tpot_ms", 50), "tpot_p80_ms": pct("tpot_ms", 80),
            "late_p90_ms": pct("late_ms", 90),
            "tokens_per_s": run["tokens"] / run["window_s"],
            "live_rows_mean": run["row_rounds"] / max(1, run["rounds"]),
        }), flush=True)
        del run
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
