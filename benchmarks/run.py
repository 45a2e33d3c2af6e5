#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` once on the TPU this machine holds
and prints the result as the last line of standard output. Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""

import time

_T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
sys.path.insert(0, _HERE)
sys.path.insert(0, _ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)

    from lib import harness

    try:
        import protocol_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"benchmark FAILED: the program is not here: {e}",
              file=sys.stderr)
        return 1
    try:
        cell = harness.load_cell(_ROOT, args.workload)
        result = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace), t_start=_T0
        )
    except harness.BenchFailure as e:
        print(f"benchmark FAILED: {e}", file=sys.stderr)
        return 1
    for name, check in result["checks"].items():
        held = ("a count, no limit" if check["limit"] is None
                else f"limit {check['limit']}")
        print(f"check {name}: {check['value']} ({held})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
