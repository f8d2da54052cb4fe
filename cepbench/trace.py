"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

* busy time: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:`` plane), clipped to
  the traced window and averaged over the devices;
* per-op device time: summed durations of each op, keyed by its HLO
  instruction name (a TPU trace names an op event after the whole HLO
  line, ``%name = shape op(...)``);
* idle gaps: the stretches of the window in which a device ran nothing,
  each named after the innermost span of the thread that drives the
  window (the thread that wrote the ``window_span`` spans) covering the
  gap's midpoint, summed per name.

The window is the stretch from the start of the first to the end of the
last span named ``window_span`` on the host, which the benchmark's own
``TraceAnnotation``s write, so the host and the devices are read on one
clock.  The functions take any object with the ``planes`` / ``lines`` /
``events`` shape of ``jax.profiler.ProfileData``, so they can be checked
on a synthetic trace.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:"
HOST_PREFIX = "/host:"
_INSTRUCTION = re.compile(r"%?([^\s=%]+) = ")


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event."""
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


def load(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(paths[-1])


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def _union(intervals: List[Tuple[float, float]]):
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _driver_lines(profile, window_span: str):
    """Host lines (threads) that hold the ``window_span`` spans."""
    for plane in profile.planes:
        if not plane.name.startswith(HOST_PREFIX):
            continue
        for line in plane.lines:
            events = list(_events(line))
            if any(name == window_span for name, _, _ in events):
                yield events


def _window(lines, window_span: str) -> Optional[Tuple[float, float]]:
    spans = [(s, e) for events in lines for name, s, e in events
             if name == window_span]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def _host_spans(lines):
    return sorted((s, e, name) for events in lines
                  for name, s, e in events if e > s)


def _label_gaps(gaps, spans) -> List[str]:
    """Innermost host span (latest start) covering each gap's midpoint."""
    starts = [s for s, _, _ in spans]
    labels = []
    for g0, g1 in gaps:
        mid = 0.5 * (g0 + g1)
        best = None
        # Spans that start before the midpoint; scan back from the
        # latest, keeping the first that still covers it.
        for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            s, e, name = spans[i]
            if e >= mid:
                best = name
                break
        labels.append(best or "no host span")
    return labels


def reduce(profile, window_span: str) -> Optional[dict]:
    """Busy and idle time of the devices over the traced window.

    Returns None where the trace holds no device op in the window.
    """
    lines = list(_driver_lines(profile, window_span))
    window = _window(lines, window_span)
    if window is None:
        return None
    w0, w1 = window
    busy_per_device = []
    op_time: Dict[str, float] = defaultdict(float)
    op_count: Dict[str, int] = defaultdict(int)
    op_text: Dict[str, str] = {}
    gaps_all = []
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        intervals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for name, s, e in _events(line):
                s, e = max(s, w0), min(e, w1)
                if e <= s:
                    continue
                intervals.append((s, e))
                key = op_name(name)
                op_text.setdefault(key, name)
                op_time[key] += (e - s) * 1e-9
                op_count[key] += 1
        if not intervals:
            continue
        merged = _union(intervals)
        busy_per_device.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps_all.extend((edges[i], edges[i + 1])
                        for i in range(0, len(edges), 2)
                        if edges[i + 1] > edges[i])
    if not busy_per_device:
        return None
    labels = _label_gaps(gaps_all, _host_spans(lines))
    idle: Dict[str, float] = defaultdict(float)
    for (g0, g1), label in zip(gaps_all, labels):
        idle[label] += (g1 - g0) * 1e-9 / len(busy_per_device)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy_per_device) / len(busy_per_device),
        "devices": len(busy_per_device),
        "op_time": dict(op_time),
        "op_count": dict(op_count),
        "op_text": op_text,
        "idle_gaps": dict(idle),
    }


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
