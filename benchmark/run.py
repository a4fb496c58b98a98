"""Run one cell once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Needs a TPU with the chips the cell asks for and fails without one: no CPU
mode, no option to skip that.  The last line of standard output is the
result as one JSON object; on any refusal the process exits non-zero and
prints none.
"""

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmark import harness

    harness.PROCESS_START = _PROCESS_START
    try:
        if not os.path.isdir(os.path.join(harness.ROOT, "rocket_tpu")):
            raise harness.BenchmarkError(
                "the system under test (rocket_tpu/) is not in this "
                "directory; the benchmark alone measures nothing")
        cell = harness.resolve_cell(args.workload)
        device = harness.require_chips(cell)
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), device)
    except harness.BenchmarkError as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    harness.print_compared(result["compared"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
