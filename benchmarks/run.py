"""Benchmark aggregator: one harness per paper table/figure + the
framework-level benchmarks.  Default mode is `--quick` scale (bounded
minutes on a 1-core CPU container); pass --full for the complete grids.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only fig5,...]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
import traceback


def _headline_rows(d) -> list:
    """Headline ``(metric, display, numeric|None)`` rows for one artifact.

    Every field access is defensive: a harness that was interrupted (or an
    older schema revision) may have written a partial document, and the
    summary must still render the rows it *can* extract rather than
    crashing the whole table on the first malformed artifact.
    """
    rows = []
    if not isinstance(d, dict):
        return [("(malformed artifact)", "-", None)]
    schema = str(d.get("schema", "?"))
    if schema.startswith("kernel_bench"):
        best = {}
        for r in d.get("rows", []) or []:
            s = r.get("speedup_vs_baseline") if isinstance(r, dict) else None
            if isinstance(s, (int, float)):
                cfg = r.get("config", "?")
                best[cfg] = max(best.get(cfg, 0.0), float(s))
        for cfg, s in sorted(best.items()):
            rows.append((f"{cfg} speedup vs baseline", f"{s:.2f}x", s))
    elif schema.startswith("fleet_bench"):
        by_k = {}
        for r in d.get("rows", []) or []:
            if isinstance(r, dict) and "k" in r:
                by_k.setdefault(r["k"], {})[r.get("config")] = r
        for k, cfgs in sorted(by_k.items()):
            base = cfgs.get("baseline")
            vm = cfgs.get("vmapped")
            if base and vm and "seconds" in base and "seconds" in vm:
                s = base["seconds"] / max(vm["seconds"], 1e-9)
                rows.append((f"k={k} vmapped speedup", f"{s:.2f}x", s))
        sc = d.get("superchunk") or {}
        if sc:
            for key, label in (("speedup_scanned", "superchunk"),
                               ("speedup_sharded", "sharded")):
                s = sc.get(key)
                if isinstance(s, (int, float)):
                    rows.append((f"k={sc.get('k')} {label} speedup",
                                 f"{s:.2f}x", float(s)))
    elif schema.startswith("scenarios"):
        for name, s in sorted((d.get("scenarios") or {}).items()):
            ev = s.get("events", "?") if isinstance(s, dict) else "?"
            num = float(ev) if isinstance(ev, (int, float)) else None
            rows.append((f"{name} events", str(ev), num))
        rows.append(("all gates pass", str(d.get("all_gates_pass")), None))
    elif schema.startswith("rulebook_bench"):
        for s in d.get("summaries", []) or []:
            if not isinstance(s, dict) or "q" not in s:
                continue
            q = s["q"]
            sp = s.get("speedup")
            if isinstance(sp, (int, float)):
                rows.append((f"q={q} rulebook vs session loop",
                             f"{sp:.2f}x", float(sp)))
            sc = s.get("superchunk_speedup")
            if isinstance(sc, (int, float)):
                rows.append((f"q={q} superchunk vs per-chunk",
                             f"{sc:.2f}x", float(sc)))
            sh = s.get("sharing_ratio")
            if isinstance(sh, (int, float)):
                rows.append((f"q={q} sharing ratio", f"{sh:.2f}", float(sh)))
        hot = d.get("hot_add") or {}
        if ("hot_add_s" in hot) and ("cold_compile_s" in hot):
            rows.append(("hot-add latency / cold compile",
                         f"{hot['hot_add_s']:.2f}s/"
                         f"{hot['cold_compile_s']:.1f}s", None))
        if "retraces" in hot:
            rows.append(("hot-add retraces", str(hot["retraces"]), None))
    else:
        rows.append((f"(unrecognized schema {schema})", "-", None))
    return rows


def _committed_artifact(fname: str, root: str):
    """The HEAD-committed version of a BENCH file, or None if unreadable."""
    try:
        blob = subprocess.run(
            ["git", "show", f"HEAD:{fname}"], cwd=root,
            capture_output=True, timeout=30)
        if blob.returncode != 0:
            return None
        return json.loads(blob.stdout.decode("utf-8"))
    except Exception:  # noqa: BLE001 - deltas are best-effort decoration
        return None


def summarize(root: str = ".") -> None:
    """Aggregate every BENCH_*.json into one trajectory table.

    Each benchmark harness emits its own schema; this prints the headline
    rows of each so CI logs carry a single at-a-glance performance
    trajectory across kernel, fleet, scenario, and rulebook layers.
    Missing, truncated, or partially-written artifacts degrade to warning
    rows instead of aborting the table.  When a working-tree artifact
    differs from its HEAD-committed version (i.e. this PR refreshed it),
    a delta column shows the per-PR movement of each numeric metric.
    """
    files = sorted(f for f in os.listdir(root)
                   if f.startswith("BENCH_") and f.endswith(".json"))
    if not files:
        print("no BENCH_*.json artifacts found")
        return
    print(f"{'artifact':<22} {'metric':<38} {'value':>12} {'vs HEAD':>10}")
    print("-" * 85)

    def row(art, metric, value, delta=""):
        print(f"{art:<22} {metric:<38} {value:>12} {delta:>10}")

    for fname in files:
        art = fname[len("BENCH_"):-len(".json")]
        try:
            with open(os.path.join(root, fname)) as fh:
                d = json.load(fh)
        except Exception as e:  # noqa: BLE001 - keep the table rendering
            row(art, f"(unreadable: {type(e).__name__})", "-")
            continue
        prev = _committed_artifact(fname, root)
        prev_num = {m: n for m, _, n in _headline_rows(prev)
                    if n is not None} if prev is not None else {}
        headline = _headline_rows(d) or [("(no headline metrics)", "-", None)]
        for metric, display, num in headline:
            delta = ""
            if num is not None and metric in prev_num:
                diff = num - prev_num[metric]
                if abs(diff) >= 0.005:
                    delta = f"{diff:+.2f}"
            row(art, metric, display, delta)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    scale = ap.add_mutually_exclusive_group()
    scale.add_argument("--full", action="store_true")
    scale.add_argument("--quick", action="store_true",
                      help="bounded scale — the default; the explicit "
                           "flag exists for CI invocations and conflicts "
                           "with --full")
    ap.add_argument("--only", default=None,
                    help="comma list: fig5,table1,fig69,kernel,fleet,moe,"
                         "roofline,rulebook")
    ap.add_argument("--summary", action="store_true",
                    help="print one trajectory table aggregated from the "
                         "committed BENCH_*.json artifacts and exit")
    args = ap.parse_args(argv)
    if args.summary:
        summarize()
        return
    quick = not args.full
    only = set(args.only.split(",")) if args.only else None

    from . import (adaptive_moe, fig5_distance, fig69_methods,
                   fleet_bench, kernel_bench, roofline, rulebook_bench,
                   table1_davg)
    from .common import enable_compile_cache

    enable_compile_cache()

    sections = [
        ("fig5", "Figure 5 — throughput vs invariant distance d",
         lambda: fig5_distance.main([], quick=quick)),
        ("table1", "Table 1 — d_avg estimate quality",
         lambda: table1_davg.main([], quick=quick)),
        ("fig69", "Figures 6-9 — adaptation method comparison",
         lambda: fig69_methods.main([], quick=quick)),
        ("kernel", "window_join kernel microbenchmark",
         lambda: kernel_bench.main([], quick=quick)),
        ("fleet", "fleet executor — vmapped vs per-partition loop",
         lambda: fleet_bench.main([], quick=quick)),
        ("rulebook", "rulebook — Q patterns on one compiled data plane",
         lambda: rulebook_bench.main([], quick=quick)),
        ("moe", "adaptive MoE expert placement",
         lambda: adaptive_moe.main([], quick=quick)),
        ("roofline", "roofline table from dry-run artifacts",
         lambda: roofline.main([], quick=quick)),
    ]
    failed = []
    for key, title, fn in sections:
        if only and key not in only:
            continue
        print(f"\n===== {title} =====", flush=True)
        t0 = time.time()
        try:
            fn()
        except Exception as e:  # noqa: BLE001 - keep the suite running
            failed.append(key)
            print(f"!! {key} failed: {type(e).__name__}: {e}")
            if not quick:
                # A one-line message has hidden shape bugs before; --full
                # runs are for debugging, so show where it actually broke.
                traceback.print_exc(file=sys.stdout)
        print(f"===== {key} done in {time.time()-t0:.1f}s =====",
              flush=True)
    if failed:
        # Every selected section ran (failures don't mask each other),
        # but a red section must fail the invocation — CI smoke relies
        # on this exit code.
        print(f"\n!! failed sections: {', '.join(failed)}")
        sys.exit(1)


if __name__ == "__main__":
    main()
