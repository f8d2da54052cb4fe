"""Sweep the offered rate of a live cell on the chip, in one process.

    python3 cepbench/tools/sweep.py --workload seq4_order_k16.stocks_live \
        --rates 12 16 20 24 --seed 7 --seconds 15

For each per-partition rate (the mix's ``base_rate``) it runs the cell
once and prints one JSON line: how many slices were due but not started
when the window closed (the generator's lag), the tail latency, the
router's drops, the match-set overflow and ``correct``.  A rate is kept
up with where the lag stays at 0 or 1 slice, nothing is dropped and
nothing overflows.
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
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from cepbench import harness

    for rate in args.rates:
        out = harness.run_cell(
            ROOT, args.workload, args.seed, args.seconds, False,
            t_start=time.perf_counter(),
            patch={"mix": {"base_rate": rate}})
        c = out["compared"]
        print(json.dumps({
            "rate_per_partition": rate, "correct": out["correct"],
            "detect_p95_ms": out["metrics"]["detect_p95_ms"]["value"],
            "lag_slices": out["window"]["lag_slices"],
            "dropped": c["dropped_events"]["value"],
            "overflow": c["overflow"]["value"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
