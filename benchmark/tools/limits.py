"""Readings for a cell's limits, on the chip, many seeds in one process.

    python benchmark/tools/limits.py --workload <name> --seeds 1,2,3 \
        --controls 3 --seconds 20

For each seed: the program's numbers against the plain reference (the lower
reading is their largest).  For the first ``--controls`` seeds also the
control's numbers: the reference put in the program's place and computed in
the nearest precision below the configuration's (fp8 for bf16), and for a
training cell the fault "half of the batch left out".  One JSON line per
reading on standard output; PERF.md keeps the readings each limit was set
from.  The benchmark's own runs never call this.
"""

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def emit(**kw):
    print(json.dumps(kw), flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--controls", type=int, default=3)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--program", type=int, choices=(0, 1), default=1,
                        help="0 (training cells): skip the program's runs, "
                             "whose numbers every benchmark run prints, and "
                             "read only the controls and faults")
    args = parser.parse_args(argv)

    from benchmark import harness

    cell = harness.resolve_cell(args.workload)
    harness.require_chips(cell)
    kind = harness.load_kind(cell)
    seeds = [int(s) for s in args.seeds.split(",")]
    if not args.program:
        for seed in seeds:
            t0 = time.perf_counter()
            emit(workload=cell.name, seed=seed, **train_controls(cell, seed),
                 check_s=round(time.perf_counter() - t0, 1))
        return 0
    for n, seed in enumerate(seeds):
        t0 = time.perf_counter()
        run = kind.drive(cell, seed=seed, seconds=args.seconds,
                         compiles=harness.CompileCounter(), trace=None,
                         process_start=t0)
        gc.collect()
        t1 = time.perf_counter()
        control = n < args.controls
        if cell.kind == "train":
            readings = train_readings(cell, seed, run, control)
        else:
            readings = serve_readings(cell, seed, run, control)
        emit(workload=cell.name, seed=seed, drive_s=round(t1 - t0, 1),
             check_s=round(time.perf_counter() - t1, 1),
             end_to_end=run["end_to_end"], **readings)
        del run
        gc.collect()
    return 0


def train_controls(cell, seed):
    """The controls and faults of a training cell need no run of the
    program: the reference in the program's place, on the batches the
    trainer would be fed (the data set's rows in order)."""
    from benchmark import traffic
    from benchmark.kinds import train

    mix = cell.traffic
    batch, steps = int(mix["batch"]), train.CHECK_STEPS
    tokens = traffic.markov_tokens(int(mix["docs"]), int(mix["seq"]),
                                   cell.arch["vocab"], seed)
    recorded = {"batches": [tokens[i * batch:(i + 1) * batch]
                            for i in range(steps)]}
    out = train_readings(cell, seed, {"recorded": recorded}, True,
                         program=False)
    return out


def train_readings(cell, seed, run, control, program=True):
    from benchmark.kinds import train

    reference = cell.family.reference
    rec = run["recorded"]
    opt = cell.traffic["optimizer"]
    batches = rec["batches"][:train.CHECK_STEPS]
    params = train.reference_params(cell, seed)
    ref = reference.train_steps(cell.arch, opt, params, batches)
    out = {"reference_losses": ref["losses"]}
    if program:
        out["program"] = train.gaps(rec, ref)
        out["program_losses"] = rec["losses"]
    if control:
        rows = range(batches[0].shape[0] // 2)
        for name, kw in (("control_fp8", {"prec": "fp8"}),
                         ("control_bf16", {"prec": "bf16"}),
                         ("fault_half_batch", {"rows": rows})):
            got = reference.train_steps(cell.arch, opt, params, batches,
                                        **kw)
            out[name] = train.gaps(got, ref)
    return out


def serve_readings(cell, seed, run, control):
    from benchmark.kinds import serving

    sample = serving.sample_finished(run["finished"], seed)
    out = {"program": serving.served_gaps(cell, seed, sample),
           "completed": run["completed"]}
    if control:
        for prec in ("fp8", "bf16"):
            out[f"control_{prec}"] = serving.served_gaps(
                cell, seed, sample, altered=prec)
    return out


if __name__ == "__main__":
    sys.exit(main())
