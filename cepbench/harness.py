"""One run of one benchmark cell: set-up, the measured window, the trace
reduction and the check against the plain reference.

A cell names a configuration and a traffic mix.  Both are files found by
name (``configs/<config>.json``, ``traffic/<mix>.json``); the pattern
kind of the configuration names its program-side builder
(``patterns/<kind>.py``) and its plain reference
(``references/<kind>.py``); each per-layer metric is read by
``metrics/<metric>.py``.  Adding any of them adds files and edits none.

The system under test is the served session of the program,
``cep.open(pattern, partitions=K, plan=..., monitor=True).process(...)``,
one call per slice.  A drive mode feeds it:

* ``replay``: the slices of a pool generated from the seed in set-up, back
  to back; past the pool's end the pool repeats, moved forward by whole
  periods;
* ``live``: open loop on the wall clock.  Event time is wall seconds; a
  slice ``(t0, t1]`` is processed once ``t1`` has passed, or at once when
  the session is behind.  The schedule never slows.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

import numpy as np

from . import roofline, streams, trace

BENCH_FILE = "BENCHMARK.json"
WINDOW_SPAN = "cepbench.process"
WAIT_SPAN = "cepbench.wait"
# Answers still missing this long after the window has closed never come.
DRAIN_S = 60.0
# Stream time stays below this many seconds, so that a timestamp on the
# 2**-10 s grid, and the timestamp plus or minus a window, is exact in
# float32.
MAX_STREAM_S = 8192.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _module(path: str):
    name = "cepbench_" + os.path.relpath(path).replace(os.sep, "_") \
        .replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str) -> SimpleNamespace:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration,
    traffic mix and the metrics it reports."""
    bench = _load_json(os.path.join(root, BENCH_FILE))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    here = os.path.join(root, "cepbench")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name) and m["moves"] in reported]
    return SimpleNamespace(
        name=name, chips=int(cell["chips"]), here=here,
        config=_load_json(os.path.join(root, entry["file"])),
        mix=_load_json(os.path.join(here, "traffic",
                                    cell["traffic"] + ".json")),
        end_to_end=e2e, per_layer=per_layer)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


class Program:
    """The program's served session, as a deployment opens it."""

    def __init__(self, config: dict, here: str):
        from repro import cep
        from repro.cep import RuntimeConfig

        spec = config["pattern"]
        build = _module(os.path.join(here, "patterns",
                                     spec["kind"] + ".py")).build
        self.session = cep.open(
            build(spec), partitions=int(config["partitions"]),
            plan=config["plan"], monitor=True,
            config=RuntimeConfig(**config["runtime"]))

    def process(self, type_id, ts, attr, keys, t0, t1) -> np.ndarray:
        return self.session.process(type_id, ts, attr, keys, t0, t1)

    def counters(self) -> Dict[str, int]:
        tel = self.session.telemetry()
        return {"replans": tel.replans, "violations": tel.violations,
                "overflow": tel.overflow, "dropped": tel.dropped}


class Control:
    """The plain reference in the program's place, with its guarantee
    broken: each slice is matched without the slices before it, so
    matches that span a slice edge are lost."""

    def __init__(self, config: dict, here: str):
        spec = config["pattern"]
        ref = _module(os.path.join(here, "references",
                                   spec["kind"] + ".py")).Reference
        self.k = int(config["partitions"])
        self.refs = [ref(spec, carry=False) for _ in range(self.k)]

    def process(self, type_id, ts, attr, keys, t0, t1) -> np.ndarray:
        part = np.asarray(keys) % self.k
        return np.array([r.process(type_id[part == p], ts[part == p],
                                   attr[part == p], t0, t1)
                         for p, r in enumerate(self.refs)], np.int64)

    def counters(self) -> Dict[str, int]:
        return {"replans": 0, "violations": 0, "overflow": 0, "dropped": 0}


# ---------------------------------------------------------------------------
# Drive modes
# ---------------------------------------------------------------------------


class Recorder:
    """Per processed slice: its index, edges, counts and host times."""

    def __init__(self, annotate: bool):
        self.rows: List[tuple] = []
        self.annotate = annotate

    def span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def process(self, system, pool, s: int):
        batch, t0, t1 = streams.shifted(pool, s)
        if t1 > MAX_STREAM_S:
            raise RuntimeError(f"stream time {t1} s passed {MAX_STREAM_S}")
        start = time.perf_counter()
        with self.span(WINDOW_SPAN):
            counts = np.asarray(system.process(*batch, t0, t1), np.int64)
        end = time.perf_counter()
        self.rows.append((s, t0, t1, counts, start, end, len(batch[0])))
        if len(self.rows) == 1:
            log(f"[setup] first slice (compiles in a cold run) took "
                f"{end - start:.3f}s")
        return start, end


def drive_replay(system, pool, mix, seconds, rec: Recorder,
                 on_window: Callable) -> dict:
    warm = int(mix["warmup_slices"])
    for s in range(warm):
        rec.process(system, pool, s)
    on_window(True)
    s = warm
    first = None
    while True:
        start, end = rec.process(system, pool, s)
        first = start if first is None else first
        s += 1
        if end - first >= seconds:
            break
    on_window(False)
    win = rec.rows[warm:]
    events = sum(r[6] for r in win)
    span = win[-1][5] - win[0][4]
    return {"slices": len(win), "events": events, "seconds": span,
            "events_per_s": events / span, "unanswered": 0}


def drive_live(system, pool, mix, seconds, rec: Recorder,
               on_window: Callable) -> dict:
    warm = int(mix["warmup_slices"])
    slice_s = pool.slice_s
    n_win = int(round(seconds / slice_s))
    for s in range(warm):
        rec.process(system, pool, s)
    on_window(True)
    origin = time.perf_counter() - warm * slice_s  # wall time of stream 0
    close = origin + (warm + n_win) * slice_s
    late = 0
    for s in range(warm, warm + n_win):
        due = origin + (s + 1) * slice_s
        now = time.perf_counter()
        if now < due:
            with rec.span(WAIT_SPAN):
                time.sleep(due - now)
        elif now > close + DRAIN_S:
            break
        elif now > close:
            late += 1
        rec.process(system, pool, s)
    on_window(False)
    win = rec.rows[warm:]
    lat = np.concatenate([
        end - (origin + streams.shifted(pool, s)[0][1].astype(np.float64))
        for s, _, _, _, _, end, _ in win]) * 1e3
    return {"slices": len(win), "events": int(lat.size),
            "seconds": n_win * slice_s,
            "detect_p95_ms": float(np.percentile(lat, 95)),
            "detect_p50_ms": float(np.percentile(lat, 50)),
            "lag_slices": late, "unanswered": n_win - len(win)}


DRIVES = {"replay": drive_replay, "live": drive_live}


def pool_slices(mix: dict, seconds: float) -> int:
    if mix["drive"] == "live":
        return int(mix["warmup_slices"]) + int(round(
            seconds / mix["slice_s"]))
    return int(mix["pool_slices"])


# ---------------------------------------------------------------------------
# The check against the plain reference
# ---------------------------------------------------------------------------


def check(cell, pool, rows, counters: dict, unanswered: int):
    """Every answered slice's per-partition count against the reference.

    Returns ``(compared, attempted, failed)``: each number compared with
    its limit, the (slice, partition) answers checked, and those that
    differ from the reference.
    """
    spec = cell.config["pattern"]
    ref = _module(os.path.join(cell.here, "references",
                               spec["kind"] + ".py")).Reference
    k = int(cell.config["partitions"])
    refs = [ref(spec) for _ in range(k)]
    mismatches = 0
    for i, (s, t0, t1, counts, *_rest) in enumerate(rows):
        if s != i:
            raise RuntimeError(f"slice {s} processed out of order")
        for p in range(k):
            x = streams.partition_slice(pool, s, p)
            want = refs[p].process(x.type_id, x.ts, x.attr, t0, t1)
            mismatches += int(counts[p] != want)
    compared = {
        "count_mismatches": {"value": mismatches, "limit": 0},
        "dropped_events": {"value": int(counters["dropped"]), "limit": 0},
        "overflow": {"value": int(counters["overflow"]), "limit": 0},
        "unanswered_slices": {"value": int(unanswered), "limit": 0},
    }
    failed = mismatches + k * int(unanswered)
    return compared, len(rows) * k, failed


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


class CompileCounter:
    """Compilations and traces JAX reports while it is installed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        self.counts = {e: 0 for e in self.EVENTS}

    def __call__(self, event, duration_secs, **kw):
        if event in self.counts:
            self.counts[event] += 1

    def snapshot(self):
        return dict(self.counts)


def enable_compile_cache(here: str) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    holding every program, so that only a cell's first run compiles."""
    import jax

    path = os.path.join(here, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # No eviction: the cache of a cell holds a few MB, and eviction's
    # bookkeeping files are what failed to write on the chip's host.
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def device_info(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < chips):
        raise SystemExit(
            f"cepbench: needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} device(s) of platform "
            f"{devices[0].platform!r}")
    return devices


def require_all(cell, metrics: dict) -> None:
    """Fail the run where a per-layer metric that ``BENCHMARK.json``
    declares for this cell read nothing: the code it reads has moved, and
    a silent metric would hide that."""
    missing = [m["name"] for m in cell.per_layer if m["name"] not in metrics]
    if missing:
        raise SystemExit(f"cepbench: per-layer metric(s) {missing} of "
                         f"{cell.name} read nothing in the trace")


def run_cell(root: str, name: str, seed: int, seconds: float,
             traced: bool, *, t_start: float,
             system_factory=None, require_tpu: bool = True,
             patch: Optional[dict] = None) -> dict:
    """One run of cell ``name``; returns the result line as a dict.

    ``system_factory(config, here)`` puts another system in the program's
    place, and ``patch`` overrides keys of the configuration and the mix
    (``{"config": {...}, "mix": {...}}``); both serve the tests.  Without
    ``require_tpu`` (a rehearsal on the CPU, whose trace holds no device
    ops) a per-layer metric may read nothing.
    """
    cell = load_cell(root, name)
    for part in ("config", "mix"):
        getattr(cell, part).update((patch or {}).get(part, {}))
    devices = device_info(cell.chips, require_tpu)
    kind = devices[0].device_kind
    peak = roofline.peaks(kind) if require_tpu else None
    import jax

    cache = enable_compile_cache(cell.here)
    log(f"[setup] devices up at {time.perf_counter() - t_start:.3f}s")
    log(f"[cell] {name} seed={seed} seconds={seconds} trace={int(traced)} "
        f"device_kind={kind} count={len(devices)} jax={jax.__version__} "
        f"cache={cache}")
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        return _run(cell, seed, seconds, traced, t_start,
                    system_factory or Program, devices, peak, compiles,
                    strict=require_tpu)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)


def _run(cell, seed, seconds, traced, t_start, factory, devices, peak,
         compiles, strict) -> dict:
    import jax

    mix = cell.mix
    pool = streams.make_pool(seed, mix, cell.config,
                             pool_slices(mix, seconds))
    log(f"[setup] {pool.n_slices} slices generated at "
        f"{time.perf_counter() - t_start:.3f}s")
    system = factory(cell.config, cell.here)
    log(f"[setup] system open at {time.perf_counter() - t_start:.3f}s")
    trace_dir = os.path.join(cell.here, ".traces", cell.name)
    rec = Recorder(annotate=traced)
    marks: Dict[str, object] = {}

    def on_window(opening: bool):
        if opening:
            marks["setup_s"] = time.perf_counter() - t_start
            marks["c0"] = compiles.snapshot()
            marks["counters0"] = system.counters()
            if traced:
                shutil.rmtree(trace_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
        else:
            if traced:
                jax.profiler.stop_trace()
            marks["c1"] = compiles.snapshot()
            marks["counters1"] = system.counters()

    win = DRIVES[mix["drive"]](system, pool, mix, seconds, rec, on_window)
    n_compiles = {e.rsplit("/", 1)[-1]: marks["c1"][e] - marks["c0"][e]
                  for e in CompileCounter.EVENTS}
    log(f"[window] slices={win['slices']} events={win['events']} "
        f"seconds={win['seconds']:.6f} in_window={n_compiles}"
        + (f" generator_lag_slices={win['lag_slices']}"
           if "lag_slices" in win else ""))
    c0, c1 = marks["counters0"], marks["counters1"]
    deltas = {k: c1[k] - c0[k] for k in c0}
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices[:cell.chips])
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak_bytes)}

    result = {}
    if traced:
        reduced = trace.reduce(trace.load(trace_dir), WINDOW_SPAN)
        ctx = SimpleNamespace(
            mode=mix["drive"], trace=reduced, window=win, counters=deltas,
            join_calls=roofline.join_calls(
                reduced["op_text"].values() if reduced else ()),
            peak=peak, slices_traced=win["slices"], log=log)
        metrics = {}
        for m in cell.per_layer:
            read = _module(os.path.join(cell.here, "metrics",
                                        m["name"] + ".py")).read
            value = read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if strict:
            require_all(cell, metrics)
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": [[reduced["op_text"][k][:160], v] for k, v
                               in trace.top(reduced["op_time"])],
                "idle_gaps": trace.top(reduced["idle_gaps"])}
    else:
        metrics = {}
        for m in cell.end_to_end:
            value = marks["setup_s"] if m["name"] == "setup_s" \
                else win.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    counters = system.counters()
    del system
    t = time.perf_counter()
    compared, attempted, failed = check(cell, pool, rec.rows, counters,
                                        win["unanswered"])
    log(f"[check] reference over {attempted} answers: "
        f"{time.perf_counter() - t:.3f}s; counters={counters} "
        f"window={deltas}")
    correct = all(v["value"] <= v["limit"] for v in compared.values())
    for key, v in compared.items():
        log(f"[compared] {key} = {v['value']} (limit {v['limit']})")
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    out.update(result)
    out["window"] = dict(win, in_window=n_compiles, **deltas)
    out["compared"] = compared
    return out
