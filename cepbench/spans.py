"""The program's own spans and scopes in a profiler trace, reduced for the
per-layer metrics that read them.

* host spans: the ``cep.*`` TraceMe events the program writes around its
  layers (``repro/core/spans.py``), on the thread that drives the window;
  per name, their wall time clipped to the window and their count;
* device scopes: the ``cep.*`` named scopes of the compiled step.  Each
  device op is looked up in its own XLA module: the module is the
  ``XLA Modules`` event that covers the op on its device, and the
  module's HLO, as the executable that ran holds it, is in the trace's
  metadata plane.  An op belongs to the innermost ``cep.*`` scope of its
  instruction's ``op_name`` (``instruction_scopes`` says where an
  instruction without one takes its scope from); per scope, the device
  time of its ops in the window;
* idle gaps: the stretches of the window in which the device ran
  nothing, each named after the innermost ``cep.*`` or ``cepbench.*``
  span on the driving thread that covers the gap's midpoint.

``jax.profiler.ProfileData`` shows no event metadata, so the HLO is read
from the ``.xplane.pb`` bytes by a small protobuf reader.  The names are
copied from the program, not imported, so that the readers also run on a
program that writes none of them.  There, and only where the program has
no ``repro.core.spans`` module, a reader reads 0: ``harness.require_all``
fails a traced run whose metric reads nothing.  Everywhere else a span or
scope that is not in the trace reads nothing, and the run fails.
"""

from __future__ import annotations

import bisect
import glob
import importlib.util
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

from cepbench import trace
from cepbench.harness import WINDOW_SPAN

HERE = os.path.dirname(os.path.abspath(__file__))

# Host spans of one served slice.
PROCESS = "cep.process"
ROUTE = "cep.route"
STEP = "cep.step"
READBACK = "cep.readback"
CONTROL = "cep.control"
REPLAN = "cep.replan"

# Device scopes of the compiled step.
INGEST = "cep.ingest"
JOIN = "cep.join"
COMPACT = "cep.compact"
FINALIZE = "cep.finalize"
MONITOR = "cep.monitor"
VERIFY = "cep.verify"

PREFIX = "cep."
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
UNSCOPED = "unscoped"
# Instructions that join the dataflow of unrelated ops: the step's inputs,
# and constants that XLA shares between scopes.
_NEUTRAL = ("parameter", "constant")
# A scope under a transformation reads ``vmap(cep.join)``.
_SEGMENT = re.compile(r"^(?:[\w.\-]+\()*([^()]*)\)*$")


def innermost_scope(path: str) -> Optional[str]:
    """The innermost ``cep.*`` scope of an ``op_name`` path, or None."""
    found = None
    for segment in path.split("/"):
        m = _SEGMENT.match(segment)
        name = m.group(1) if m else segment
        if name.startswith(PREFIX):
            found = name
    return found


def program_writes_spans() -> bool:
    """Whether the program under test names its spans and scopes."""
    try:
        return importlib.util.find_spec("repro.core.spans") is not None
    except ModuleNotFoundError:
        return False


# ---------------------------------------------------------------------------
# Protobuf
# ---------------------------------------------------------------------------


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one protobuf message: an
    int for a varint, the bytes of any other wire type."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unknown protobuf wire type {wire}")
        yield key >> 3, value


def _ints(value) -> List[int]:
    """A repeated integer field's values: one varint, or packed."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def hlo_modules(xspace: bytes) -> Dict[str, bytes]:
    """The ``HloModuleProto`` of each module the trace's metadata plane
    holds, by its event name (``<module>(<program id>)``).

    Of the ``XSpace`` (``tsl/profiler/protobuf/xplane.proto``) it reads
    the planes (1); of a plane its name (2), event metadata (4: name 2,
    stats 5) and stat metadata (5: name 2); of a stat its metadata id (1)
    and bytes value (6); of the ``HloProto`` its module (1).
    """
    modules: Dict[str, bytes] = {}
    for num, plane in _fields(memoryview(xspace)):
        if num != 1:
            continue
        fields = list(_fields(plane))
        if not any(f == 2 and _text(v) == METADATA_PLANE
                   for f, v in fields):
            continue
        wanted = set()
        for f, value in fields:
            if f == 5:
                entry = dict(_fields(value))
                meta = dict(_fields(entry.get(2, b"")))
                if _text(meta.get(2, b"")) == HLO_PROTO_STAT:
                    wanted.add(entry.get(1, 0))
        for f, value in fields:
            if f != 4:
                continue
            name, proto = "", None
            for g, v in _fields(dict(_fields(value)).get(2, b"")):
                if g == 2:
                    name = _text(v)
                elif g == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in wanted and 6 in stat:
                        proto = dict(_fields(stat[6])).get(1)
            if name and proto is not None:
                modules[name] = bytes(proto)
    return modules


# ---------------------------------------------------------------------------
# Scopes of a module's instructions
# ---------------------------------------------------------------------------


class Instruction(NamedTuple):
    name: str
    opcode: str
    path: str             # op_name metadata
    operands: List[int]   # instruction ids
    called: List[int]     # computation ids


def _instructions(module: bytes):
    """``{computation id: {instruction id: Instruction}}`` of an
    ``HloModuleProto`` (computations 3; of a computation its instructions
    2 and id 5; of an instruction its name 1, opcode 2, metadata 7 with
    ``op_name`` 2, id 35, operand ids 36 and called computation ids
    38)."""
    comps: Dict[int, Dict[int, Instruction]] = {}
    for f, comp in _fields(memoryview(module)):
        if f != 3:
            continue
        cid, instrs = 0, {}
        for g, value in _fields(comp):
            if g == 5:
                cid = value
            if g != 2:
                continue
            iid, name, opcode, path, operands, called = 0, "", "", "", [], []
            for h, v in _fields(value):
                if h == 1:
                    name = _text(v)
                elif h == 2:
                    opcode = _text(v)
                elif h == 7:
                    path = _text(dict(_fields(v)).get(2, b""))
                elif h == 35:
                    iid = v
                elif h == 36:
                    operands += _ints(v)
                elif h == 38:
                    called += _ints(v)
            instrs[iid] = Instruction(name, opcode, path, operands, called)
        comps[cid] = instrs
    return comps


def _named(path: str) -> bool:
    """Whether an ``op_name`` carries JAX's name stack (``jit(f)/...``);
    XLA's own names (``reduce_window_sum``, ``scatter``) do not."""
    return "/" in path


def instruction_scopes(module: bytes) -> Dict[str, Tuple[str, str]]:
    """``(scope, rule)`` of every instruction of a module, by name; the
    scope is a ``cep.*`` name or ``UNSCOPED``, the rule says how it was
    found.

    * ``own``: the instruction's ``op_name`` carries JAX's name stack;
    * ``fused``: it has none, and the instructions of its fused (called)
      computations that do name one scope between them;
    * ``inputs``: neither, and the instructions of known scope (by the
      two rules above) nearest to it upstream in its computation's
      dataflow graph (its operands, theirs, and so on through unknown
      ones) name one scope between them: an op that XLA made out of one
      scope's values continues that scope's work (the scatter that
      writes ingested events, the prefix sums of a compaction);
    * ``outputs``: none of these, and the nearest ones of known scope
      downstream (its users, theirs, ...) name one scope: an op that
      only readies values for one scope, such as a layout copy of a
      step input;
    * ``none``: none of these names one scope.

    XLA drops the ``op_name`` of ops it rewrites (the batched scatter of
    a ``vmap``-ped ``jnp.nonzero``, its prefix sums as reduce-windows,
    layout copies), so the time of such ops is put down to a scope only
    through the module's own structure, never through the order in which
    the device ran them.
    """
    comps = _instructions(module)
    by_id = {iid: ins for instrs in comps.values()
             for iid, ins in instrs.items()}

    def fused_scopes(ins, seen) -> set:
        out = set()
        for cid in ins.called:
            if cid in seen:
                continue
            seen.add(cid)
            for sub in comps.get(cid, {}).values():
                if sub.opcode in _NEUTRAL:
                    continue
                if _named(sub.path):
                    out.add(innermost_scope(sub.path) or UNSCOPED)
                else:
                    out |= fused_scopes(sub, seen)
        return out

    known: Dict[int, Tuple[str, str]] = {}  # by own op_name or fused
    pending = []
    for instrs in comps.values():
        users: Dict[int, List[int]] = defaultdict(list)
        for iid, ins in instrs.items():
            for op in ins.operands:
                users[op].append(iid)
        for iid, ins in instrs.items():
            if _named(ins.path):
                known[iid] = (innermost_scope(ins.path) or UNSCOPED, "own")
                continue
            found = fused_scopes(ins, set())
            if len(found) == 1:
                known[iid] = (found.pop(), "fused")
            else:
                pending.append((iid, instrs, users))
    scopes = {by_id[iid].name: got for iid, got in known.items()}

    def nearest(iid, instrs, edges) -> set:
        seen, frontier = {iid}, [iid]
        while frontier:
            nxt, found = [], set()
            for j in frontier:
                for k in edges(j):
                    if k in seen or k not in instrs:
                        continue
                    seen.add(k)
                    if instrs[k].opcode in _NEUTRAL:
                        continue
                    if k in known:
                        found.add(known[k][0])
                    else:
                        nxt.append(k)
            if found:
                return found
            frontier = nxt
        return set()

    for iid, instrs, users in pending:
        scope, rule = UNSCOPED, "none"
        for rule_, edges in (("inputs", lambda j: by_id[j].operands),
                             ("outputs", lambda j: users[j])):
            found = nearest(iid, instrs, edges)
            if len(found) == 1:
                scope, rule = found.pop(), rule_
                break
        scopes[by_id[iid].name] = (scope, rule)
    return scopes


# ---------------------------------------------------------------------------
# Reduction
# ---------------------------------------------------------------------------


def host_spans(lines, window):
    """``(seconds, count)`` per ``cep.*`` span name on the driving
    thread's ``lines``, clipped to the window."""
    w0, w1 = window
    seconds: Dict[str, float] = defaultdict(float)
    count: Dict[str, int] = defaultdict(int)
    for events in lines:
        for name, s, e in events:
            if not name.startswith(PREFIX):
                continue
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            seconds[name] += (e - s) * 1e-9
            count[name] += 1
    return dict(seconds), dict(count)


def _module_at(modules, t: float) -> str:
    """The ``XLA Modules`` event (sorted ``(start, end, name)``) that
    covers time ``t``, or ""."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return modules[i][2]
    return ""


def device_scopes(profile, hlo: Dict[str, bytes], window):
    """Device seconds per scope in the window, and per op
    ``(module, op) -> [seconds, scope, rule]``."""
    w0, w1 = window
    scopes_of: Dict[str, Dict[str, Tuple[str, str]]] = {}
    per_op: Dict[Tuple[str, str], list] = {}
    devices = 0
    for plane in profile.planes:
        if not plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        lines = {line.name: line for line in plane.lines}
        if trace.OPS_LINE not in lines:
            continue
        devices += 1
        modules = sorted((s, e, name) for name, s, e in trace._events(
            lines[MODULES_LINE])) if MODULES_LINE in lines else []
        for name, s, e in trace._events(lines[trace.OPS_LINE]):
            c0, c1 = max(s, w0), min(e, w1)
            if c1 <= c0:
                continue
            module = _module_at(modules, s)
            key = (module, trace.op_name(name))
            if key not in per_op:
                if module not in scopes_of:
                    proto = hlo.get(module)
                    scopes_of[module] = {} if proto is None \
                        else instruction_scopes(proto)
                scope, rule = scopes_of[module].get(
                    key[1], (UNSCOPED, "no hlo"))
                per_op[key] = [0.0, scope, rule]
            per_op[key][0] += (c1 - c0) * 1e-9
    scope_s: Dict[str, float] = defaultdict(float)
    for seconds, scope, _ in per_op.values():
        scope_s[scope] += seconds / max(devices, 1)
    return dict(scope_s), per_op


def idle_spans(profile, lines, window) -> Dict[str, float]:
    """Idle device seconds in the window, per innermost ``cep.*`` or
    ``cepbench.*`` span of the driving thread over each gap's midpoint
    (``trace.reduce`` names a gap after the innermost span of any
    name)."""
    w0, w1 = window
    gaps, devices = [], 0
    for plane in profile.planes:
        if not plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        intervals = []
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            for _, s, e in trace._events(line):
                s, e = max(s, w0), min(e, w1)
                if e > s:
                    intervals.append((s, e))
        if not intervals:
            continue
        devices += 1
        edges = [w0] + [x for iv in trace._union(intervals) for x in iv] \
            + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    ours = [sp for sp in trace._host_spans(lines)
            if sp[2].startswith((PREFIX, "cepbench."))]
    idle: Dict[str, float] = defaultdict(float)
    for (g0, g1), label in zip(gaps, trace._label_gaps(gaps, ours)):
        idle[label] += (g1 - g0) * 1e-9 / max(devices, 1)
    return dict(idle)


def reduce(profile, hlo: Dict[str, bytes],
           window_span: str = WINDOW_SPAN) -> Optional[dict]:
    """Host spans, device time per scope and idle time per span of a
    trace; ``hlo`` from ``hlo_modules``.  None without a window."""
    lines = list(trace._driver_lines(profile, window_span))
    window = trace._window(lines, window_span)
    if window is None:
        return None
    seconds, count = host_spans(lines, window)
    scope_s, per_op = device_scopes(profile, hlo, window)
    return {"host_span_s": seconds, "host_span_n": count,
            "scope_s": scope_s, "op_scope": per_op,
            "idle_span_s": idle_spans(profile, lines, window)}


def report(program: dict, log, n: int = 12) -> None:
    """Log the reduction: device time per scope, the ops that carry no
    ``op_name`` of their own with the scope each got, and idle time per
    span."""
    log(f"[spans] device s per scope: "
        f"{trace.top(program['scope_s'], 20)}")
    rules = defaultdict(float)
    for seconds, _, rule in program["op_scope"].values():
        rules[rule] += seconds
    log(f"[spans] device s per scope rule: {trace.top(rules)}")
    borrowed = {f"{m}/{op} -> {scope} ({rule})": seconds
                for (m, op), (seconds, scope, rule)
                in program["op_scope"].items() if rule != "own"}
    log(f"[spans] ops without an op_name of their own: "
        f"{trace.top(borrowed, n)}")
    log(f"[spans] idle s per span: {trace.top(program['idle_span_s'])}")


_CACHE: Dict[Tuple[str, float], Optional[dict]] = {}


def load(log=None,
         trace_dir: str = os.path.join(HERE, ".traces")) -> Optional[dict]:
    """``reduce`` of the newest trace under ``trace_dir`` (the run's own,
    written just before its metrics are read), kept for the other
    readers of the same run; ``report``-ed to ``log`` once."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return None
    key = (paths[-1], os.path.getmtime(paths[-1]))
    if key not in _CACHE:
        from jax.profiler import ProfileData

        with open(paths[-1], "rb") as fh:
            xspace = fh.read()
        _CACHE.clear()
        _CACHE[key] = reduce(ProfileData.from_serialized_xspace(xspace),
                             hlo_modules(xspace))
        if _CACHE[key] is not None and log is not None:
            report(_CACHE[key], log)
    return _CACHE[key]


def _without_spans(ctx, what) -> bool:
    if program_writes_spans():
        return False
    ctx.log(f"[spans] the program has no repro.core.spans: it writes no "
            f"{what}; read as 0")
    return True


def host_span(ctx, name: str) -> Optional[Tuple[float, int]]:
    """``(seconds, count)`` of the run's host spans ``name``."""
    if _without_spans(ctx, name):
        return 0.0, 0
    program = load(ctx.log)
    if program is None or name not in program["host_span_n"]:
        return None
    return program["host_span_s"][name], program["host_span_n"][name]


def host_ms_per_slice(ctx, name: str) -> Optional[float]:
    """Milliseconds per traced slice in the host spans ``name``."""
    got = host_span(ctx, name) if ctx.slices_traced else None
    return None if got is None else 1e3 * got[0] / ctx.slices_traced


def scope_ms_per_slice(ctx, names: Iterable[str]) -> Optional[float]:
    """Device milliseconds per traced slice of the ops scoped ``names``."""
    if ctx.trace is None or not ctx.slices_traced:
        return None
    names = tuple(names)
    if _without_spans(ctx, names):
        return 0.0
    program = load(ctx.log)
    if program is None or not set(names) & set(program["scope_s"]):
        return None
    spent = sum(program["scope_s"].get(n, 0.0) for n in names)
    return 1e3 * spent / ctx.slices_traced
