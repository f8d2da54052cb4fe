"""Read the benchmark's check on its control, on the chip at a cell's own
size: the plain reference in the program's place with its guarantee
broken (``harness.Control``), one run per seed, in one process.

    python3 cepbench/tools/control.py --workload <cell> --seconds 5 \
        --seeds 11 12 13

Prints one JSON line per seed with ``correct`` and each number compared.
The benchmark's own runs never run the control.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from cepbench import harness

    for seed in args.seeds:
        out = harness.run_cell(ROOT, args.workload, seed, args.seconds,
                               False, t_start=time.perf_counter(),
                               system_factory=harness.Control)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "compared": out["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
