"""The trace reduction on a small synthetic trace."""

from types import SimpleNamespace as NS

import pytest

from cepbench import trace

MS = 1e6  # nanoseconds per millisecond


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS)


def profile(device_ops, host_events):
    return NS(planes=[
        NS(name="/host:CPU", lines=[
            # A runtime thread: its spans do not name the gaps.
            NS(name="futex", events=[ev("ReadSyncFlag", 0, 1000)]),
            NS(name="python3", events=host_events)]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=[ev("jit_step", 0, 1000)]),
            NS(name="XLA Ops", events=device_ops)]),
    ])


def test_busy_union_ops_and_gaps():
    host = [ev("cepbench.process", 10, 40), ev("route", 12, 5),
            ev("cepbench.process", 60, 40), ev("readback", 90, 8)]
    join = "%join.1 = s8[16,8,128]{2,1,0} custom-call(f32[16,8,3] %a)"
    ops = [ev(join, 20, 10), ev("%fusion.2 = f32[4] fusion(%b)", 25, 10),
           ev(join, 70, 5), ev("outside", 200, 10)]
    r = trace.reduce(profile(ops, host), "cepbench.process")
    assert r["window_s"] == pytest.approx(0.090)
    # Union of [20, 35] and [70, 75]; the op after the window is cut.
    assert r["busy_s"] == pytest.approx(0.020)
    assert r["op_time"]["join.1"] == pytest.approx(0.015)
    assert r["op_count"] == {"join.1": 2, "fusion.2": 1}
    assert r["op_text"]["join.1"] == join
    gaps = r["idle_gaps"]
    # [10, 20]: midpoint 15 lies in "route" (innermost); [35, 70]: mid
    # 52.5 is between the two process spans; [75, 100]: mid 87.5 in
    # the second process span but outside "readback".
    assert gaps["route"] == pytest.approx(0.010)
    assert gaps["no host span"] == pytest.approx(0.035)
    assert gaps["cepbench.process"] == pytest.approx(0.025)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_no_window_or_no_device_op_reads_nothing():
    assert trace.reduce(profile([ev("x", 0, 1)], []), "cepbench.process") \
        is None
    host = [ev("cepbench.process", 10, 40)]
    assert trace.reduce(profile([ev("x", 100, 1)], host),
                        "cepbench.process") is None


def test_top_orders_by_value():
    assert trace.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0],
                                                           ["c", 2.0]]
