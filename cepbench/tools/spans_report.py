"""Print what ``cepbench/spans.py`` reads from a saved profiler trace: the
host spans, the device time per scope and per scope rule, the ops without
an ``op_name`` of their own with the scope each got, and the idle time per
span, each also per processed slice of the window.

    python3 cepbench/tools/spans_report.py <trace.xplane.pb[.gz]> [--ops 40]

A traced benchmark run logs the same reduction (``[spans]`` lines); this
reads it again from a trace kept from such a run.
"""

import argparse
import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--ops", type=int, default=40)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT]
    from jax.profiler import ProfileData

    from cepbench import spans, trace
    from cepbench.harness import WINDOW_SPAN

    opener = gzip.open if args.trace.endswith(".gz") else open
    with opener(args.trace, "rb") as fh:
        xspace = fh.read()
    profile = ProfileData.from_serialized_xspace(xspace)
    program = spans.reduce(profile, spans.hlo_modules(xspace))
    reduced = trace.reduce(profile, WINDOW_SPAN)
    slices = sum(1 for events in trace._driver_lines(profile, WINDOW_SPAN)
                 for name, _, _ in events if name == WINDOW_SPAN)
    spans.report(program, print, args.ops)
    per_slice = {k: 1e3 * v / slices for k, v in program["scope_s"].items()}
    print(json.dumps({
        "slices": slices, "window_s": reduced["window_s"],
        "busy_s": reduced["busy_s"],
        "host_span_ms_per_slice": {k: 1e3 * v / slices for k, v
                                   in program["host_span_s"].items()},
        "host_span_n": program["host_span_n"],
        "scope_ms_per_slice": per_slice,
        "step_ms_per_slice": 1e3 * reduced["busy_s"] / slices,
        "idle_span_s": program["idle_span_s"]}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
