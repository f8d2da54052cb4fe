"""The reduction of the program's own spans and scopes, and the metrics
that read them, on a small synthetic trace and a real one from the CPU."""

import glob
import os
from types import SimpleNamespace as NS

import pytest

from cepbench import harness, spans, trace

MS = 1e6  # nanoseconds per millisecond
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
STEP, STACK = "jit__mprocess(7)", "jit__stack(9)"


def ev(name, start_ms, dur_ms, **stats):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS,
              stats=list(stats.items()))


def op(name, start_ms, dur_ms):
    return ev(f"%{name} = s32[8] fusion(s32[64] %a)", start_ms, dur_ms)


# -- protobuf writers --------------------------------------------------------


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(num, value):
    """One protobuf field: a varint for an int, else length-delimited."""
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _packed(num, ints):
    return _field(num, b"".join(_varint(i) for i in ints))


def instruction(iid, name, opcode, path="", operands=(), called=()):
    out = _field(1, name) + _field(2, opcode) + _field(35, iid)
    if path:
        out += _field(7, _field(1, opcode) + _field(2, path))
    if operands:
        out += _packed(36, operands)
    if called:
        out += _packed(38, called)
    return out


def module(computations):
    """An ``HloModuleProto``: ``{computation id: [instruction fields]}``."""
    return b"".join(
        _field(3, _field(1, f"c{cid}") + _field(5, cid)
               + b"".join(_field(2, i) for i in instrs))
        for cid, instrs in computations.items())


def _plane(name, stat_names, events, value_field=5):
    """An ``XPlane``: stat metadata by id, event metadata ``(name,
    [(stat id, value)])``."""
    out = _field(2, name)
    for sid, sname in stat_names.items():
        out += _field(5, _field(1, sid) + _field(2, _field(1, sid)
                                                 + _field(2, sname)))
    for eid, (ename, stats) in enumerate(events, 1):
        meta = _field(1, eid) + _field(2, ename) + b"".join(
            _field(5, _field(1, sid) + _field(value_field, value))
            for sid, value in stats)
        out += _field(4, _field(1, eid) + _field(2, meta))
    return out


# -- the synthetic program ---------------------------------------------------

J = "jit(_mprocess)/vmap({})/x"
STEP_HLO = module({
    1: [  # entry
        instruction(1, "p0", "parameter", "chunk.ts"),
        instruction(2, "fusion.1", "fusion", J.format("cep.ingest"), [1]),
        instruction(3, "fusion.2", "fusion", J.format("cep.join"), [2]),
        # XLA dropped its op_name; its fused scatter's reducer names one.
        instruction(4, "fusion.3", "fusion", "", [3], [2]),
        # A prefix sum XLA named itself, between two compaction ops.
        instruction(5, "reduce-window", "reduce-window",
                    "reduce_window_sum", [4]),
        instruction(6, "fusion.4", "fusion", J.format("cep.compact"), [5]),
        # A layout copy after the compaction continues it.
        instruction(7, "copy.5", "copy", "", [6]),
        instruction(8, "fusion.6", "fusion", J.format("cep.monitor"), [7]),
        instruction(9, "fusion.7", "fusion", J.format("cep.verify"), [8]),
        instruction(10, "copy.8", "copy", "jit(_mprocess)/copy", [9]),
        # A constant that XLA shares between scopes joins nothing; a copy
        # of a step input readies it for the monitor.
        instruction(11, "constant.9", "constant", J.format("cep.join")),
        instruction(12, "copy.10", "copy", "monitor.counts", [1, 11]),
        instruction(13, "fusion.11", "fusion", J.format("cep.monitor"),
                    [12]),
        # Made of two scopes' values, and used by nothing.
        instruction(14, "copy.12", "copy", "", [3, 8]),
    ],
    2: [  # the fused computation of fusion.3
        instruction(20, "param_0", "parameter"),
        instruction(21, "scatter.1", "scatter", "", [20], [3]),
    ],
    3: [  # the scatter's reducer
        instruction(30, "add.1", "add", J.format("cep.compact")),
    ],
})
STACK_HLO = module({1: [instruction(1, "fusion.1", "fusion",
                                    "jit(_stack)/concatenate")]})
HLO = {STEP: STEP_HLO, STACK: STACK_HLO}


def test_instruction_scopes():
    got = spans.instruction_scopes(STEP_HLO)
    assert got["fusion.2"] == ("cep.join", "own")
    assert got["fusion.3"] == ("cep.compact", "fused")
    assert got["reduce-window"] == ("cep.compact", "inputs")
    assert got["copy.5"] == ("cep.compact", "inputs")
    assert got["copy.8"] == ("unscoped", "own")
    assert got["copy.10"] == ("cep.monitor", "outputs")
    assert got["copy.12"] == ("unscoped", "none")

def slice_spans(t, i, replans):
    """The host spans of one served slice starting at ``t`` ms."""
    out = [ev("cepbench.process", t, 40), ev("cep.process", t + 1, 38,
                                             chunk=i),
           ev("cep.route", t + 2, 4, chunk=i),
           ev("cep.step", t + 6, 2, chunk=i),
           ev("cep.readback", t + 8, 12, chunk=i),
           ev("cep.readback", t + 20, 1, chunk=i),
           ev("cep.control", t + 21, 2 + 6 * replans, chunk=i)]
    for r in range(replans):
        s = t + 22 + 6 * r
        out += [ev("cep.replan", s, 6, chunk=i, partition=r),
                ev("cep.readback", s, 1, chunk=i),
                ev("cep.readback", s + 1, 1, chunk=i),
                # One of JAX's own TraceMes inside the replan.
                ev("PjitFunction(_squeeze)", s + 2, 3)]
    return out


def profile():
    host = slice_spans(0, 0, 1) + slice_spans(50, 1, 0)
    ops, modules = [], []
    for t in (8, 58):
        modules += [ev(STEP, t, 10), ev(STACK, t + 10, 1)]
        ops += [op("fusion.1", t, 1), op("fusion.2", t + 1, 1.5),
                op("fusion.3", t + 2.5, 4), op("reduce-window", t + 6.5, 1),
                op("fusion.4", t + 7.5, 1), op("copy.5", t + 8.5, 0.25),
                op("fusion.6", t + 8.75, 0.5),
                op("fusion.7", t + 9.25, 0.25),
                op("copy.8", t + 9.5, 0.25), op("copy.10", t + 9.75, 0.25),
                # The readback's own module: its fusion.1 is not the
                # step's.
                op("fusion.1", t + 10, 0.5)]
    modules.append(ev("jit_other(3)", 200, 10))
    ops.append(op("fusion.4", 200, 5))
    return NS(planes=[
        NS(name="/host:CPU", lines=[
            NS(name="futex", events=[ev("cep.route", 0, 1000)]),
            NS(name="python3", events=host)]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=modules),
            NS(name="XLA Ops", events=ops)]),
    ])


def test_program_names_match_the_benchmark_copy():
    from repro.core import spans as program

    for name in ("PROCESS", "ROUTE", "STEP", "READBACK", "CONTROL",
                 "REPLAN", "INGEST", "JOIN", "COMPACT", "FINALIZE",
                 "MONITOR", "VERIFY"):
        assert getattr(program, name) == getattr(spans, name), name


@pytest.mark.parametrize("path,want", [
    ("jit(mprocess)/vmap(cep.compact)/jit(cumsum)/cumsum", "cep.compact"),
    ("jit(f)/cep.join/vmap(cep.compact)/gather", "cep.compact"),
    ("jit(f)/vmap(vmap(cep.verify))/max", "cep.verify"),
    ("jit(mprocess)/copy", None),
    ("", None),
])
def test_innermost_scope(path, want):
    assert spans.innermost_scope(path) == want


def test_host_spans_device_scopes_and_idle_spans():
    r = spans.reduce(profile(), HLO)
    s, n = r["host_span_s"], r["host_span_n"]
    assert n == {"cep.process": 2, "cep.route": 2, "cep.step": 2,
                 "cep.readback": 6, "cep.control": 2, "cep.replan": 1}
    assert s["cep.route"] == pytest.approx(0.008)
    assert s["cep.control"] == pytest.approx(0.010)
    assert s["cep.readback"] == pytest.approx(0.028)
    # Two slices; the op after the last one is cut.  The readback's
    # fusion.1 (0.5 ms) and the step's copy.8 are unscoped.
    assert r["scope_s"] == pytest.approx({
        "cep.ingest": 0.002, "cep.join": 0.003, "cep.compact": 0.0125,
        "cep.monitor": 0.0015, "cep.verify": 0.0005, "unscoped": 0.0015})
    assert r["op_scope"][(STACK, "fusion.1")][1:] == ["unscoped", "own"]
    assert r["op_scope"][(STEP, "fusion.1")][1:] == ["cep.ingest", "own"]
    # [0, 8]: mid 4 in cep.route; [18.5, 58]: mid 38.25 in slice 0's
    # cep.process after its control span; [68.5, 90] likewise in slice
    # 1's.  JAX's own TraceMe names no gap.
    assert r["idle_span_s"] == pytest.approx({"cep.route": 0.008,
                                              "cep.process": 0.061})
    assert sum(r["scope_s"].values()) + sum(r["idle_span_s"].values()) \
        == pytest.approx(0.090)


def test_trace_reduce_reads_the_same_trace():
    r = trace.reduce(profile(), "cepbench.process")
    assert r["op_count"]["copy.5"] == 2
    assert r["busy_s"] == pytest.approx(0.021)
    assert r["idle_gaps"] == pytest.approx({"cep.route": 0.008,
                                            "cep.process": 0.061})


def readers(monkeypatch, prof, mode="replay", slices=2, events=480,
            writes_spans=True):
    program = spans.reduce(prof, HLO)
    monkeypatch.setattr(spans, "load", lambda *a: program)
    monkeypatch.setattr(spans, "program_writes_spans", lambda: writes_spans)
    ctx = NS(mode=mode, trace=trace.reduce(prof, "cepbench.process"),
             window={"events": events}, slices_traced=slices,
             log=lambda msg: None)
    out = {}
    for m in harness._load_json(os.path.join(ROOT, "BENCHMARK.json"))[
            "per_layer"]:
        if m["source"] == "program_span" or m["name"].startswith(
                ("compact_", "monitor_")):
            mod = harness._module(os.path.join(ROOT, "cepbench", "metrics",
                                               m["name"] + ".py"))
            out[m["name"]] = mod.read(ctx)
    return out


def test_new_metrics_read_the_synthetic_trace(monkeypatch):
    got = readers(monkeypatch, profile())
    assert got["route_ms_per_slice.replay"] == pytest.approx(4.0)
    assert got["control_ms_per_slice.replay"] == pytest.approx(5.0)
    assert got["control_ms_per_slice.live"] is None  # a replay run
    assert got["readbacks_per_1k_events.replay"] == pytest.approx(12.5)
    assert got["compact_ms_per_slice.replay"] == pytest.approx(6.25)
    assert got["monitor_ms_per_slice.replay"] == pytest.approx(1.0)
    live = readers(monkeypatch, profile(), mode="live")
    assert live["control_ms_per_slice.live"] == pytest.approx(5.0)
    assert live["route_ms_per_slice.replay"] is None


def without_program_names(prof):
    """The trace of a program that writes no ``cep.*`` span."""
    for plane in prof.planes:
        for line in plane.lines:
            line.events = [e for e in line.events
                           if not e.name.startswith("cep.")]
    return prof


def test_program_without_spans_module_reads_zero(monkeypatch):
    got = readers(monkeypatch, without_program_names(profile()),
                  writes_spans=False)
    assert set(got.values()) - {None} == {0.0}
    assert got["route_ms_per_slice.replay"] == 0.0
    assert got["compact_ms_per_slice.replay"] == 0.0


def test_program_with_spans_but_none_in_the_trace_reads_nothing(
        monkeypatch):
    monkeypatch.setitem(HLO, STEP, module({1: [
        instruction(1, "fusion.1", "fusion", "jit(_mprocess)/x")]}))
    got = readers(monkeypatch, without_program_names(profile()))
    assert set(got.values()) == {None}


def test_missing_span_or_scope_reads_nothing(monkeypatch):
    prof = profile()
    host, ops = prof.planes[0].lines[1], prof.planes[1].lines[1]
    host.events = [e for e in host.events if e.name != "cep.route"]
    ops.events = [e for e in ops.events if trace.op_name(e.name) not in (
        "fusion.3", "reduce-window", "fusion.4", "copy.5")]
    got = readers(monkeypatch, prof)
    assert got["route_ms_per_slice.replay"] is None
    assert got["compact_ms_per_slice.replay"] is None
    assert got["control_ms_per_slice.replay"] == pytest.approx(5.0)
    assert got["monitor_ms_per_slice.replay"] == pytest.approx(1.0)


def test_hlo_modules_from_the_metadata_plane():
    hlo = _field(1, STEP_HLO) + _field(3, b"buffer assignment")
    meta = _plane("/host:metadata", {1: "Hlo Proto"},
                  [(STEP, [(1, hlo)])], value_field=6)
    device = _plane("/device:TPU:0", {9: "tf_op"}, [
        ("%fusion.3 = s32[8] fusion(s32[64] %a)", [(9, "jit(f)/x:")])])
    xspace = _field(1, device) + _field(1, meta) + _field(4, "host")
    assert spans.hlo_modules(xspace) == {STEP: STEP_HLO}


def test_scopes_of_a_real_executable(tmp_path):
    """The compaction's own ops on the CPU: XLA names the prefix sums and
    the scatter of ``jnp.nonzero`` after itself, and they still take the
    scope."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        with jax.named_scope(spans.COMPACT):
            idx = jnp.nonzero(x > 0.5, size=8, fill_value=0)[0]
        with jax.named_scope(spans.MONITOR):
            return idx, x.at[idx].set(0.0).sum()

    x = jnp.linspace(0.0, 1.0, 64)
    jax.block_until_ready(step(x))
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(step(x))
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    with open(path, "rb") as fh:
        hlo = spans.hlo_modules(fh.read())
    name, = [m for m in hlo if m.startswith("jit_step(")]
    got = spans.instruction_scopes(hlo[name])
    # The CPU runs each op as a ``wrapped_*`` fusion of the entry.
    sums = {n: v for n, v in got.items()
            if n.startswith("wrapped_reduce-window")}
    assert sums and {s for s, _ in sums.values()} == {
        spans.COMPACT, spans.MONITOR}
    assert {rule for _, rule in sums.values()} >= {"fused", "inputs"}
    assert got["wrapped_scatter"] == (spans.COMPACT, "own")
