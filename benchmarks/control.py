#!/usr/bin/env python3
"""Read the control and the planted faults at a cell's own size.

    python3 benchmarks/control.py --workload <name> --seeds 1,2,3 --seconds <s> [--runs sound,control,rounds_capped,...]

One process holds the chip and runs the cell once per seed and kind,
printing one JSON line per run with the numbers ``correct`` compared.
Not part of a benchmark run: it is how the limits in the configuration
files were read, and how to read them again.
"""

import argparse
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(0, os.path.dirname(_HERE))


def main(argv=None) -> int:
    from lib import faults, harness

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--runs", default="sound," + ",".join(faults.FAULTS))
    args = parser.parse_args(argv)
    cell = harness.load_cell(os.path.dirname(_HERE), args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        for kind in args.runs.split(","):
            t0 = time.perf_counter()
            with faults.planted(kind, cell) as planted:
                try:
                    r = harness.run_cell(planted, seed, args.seconds, False,
                                         t_start=t0)
                    line = {
                        "correct": r["correct"],
                        "window": r["window"],
                        "checks": {
                            k: v["value"] for k, v in r["checks"].items()
                        },
                    }
                except harness.BenchFailure as e:
                    line = {"correct": False, "crashed": str(e)}
            line.update(workload=args.workload, seed=seed, run=kind,
                        total_s=time.perf_counter() - t0)
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
