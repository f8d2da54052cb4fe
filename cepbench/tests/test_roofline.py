"""Peaks table, parsing of the join calls from traced HLO lines, and the roofline
arithmetic."""

import pytest

from cepbench import roofline

# Device op names of a traced K = 16 step on a v5e: each is the op's HLO
# instruction line (shortened).
OPS = [
    '%window_join_packed_pallas.6 = s8[16,2048,128]'
    '{2,1,0:T(8,128)(4,1)S(1)} custom-call(f32[16,2048,10]'
    '{2,1,0:T(8,128)S(1)} %copy.605, f32[16,10,128]{2,1,0:T(8,128)} '
    '%copy.606, s32[16,1,10]{2,1,0} %a, f32[1,10]{1,0} %b, '
    's32[16,2048,1]{2,1,0} %c, s32[16,1,128]{2,1,0} %d), '
    'custom_call_target="tpu_custom_call", backend_config={"x": 1}',
    '%other = f32[8]{0} custom-call(f32[8]{0} %a), '
    'custom_call_target="tpu_custom_call"',
    '%fusion.62 = s32[32768]{0:T(1024)S(1)} fusion(s32[67108864]'
    '{0:T(1024)} %bitcast.194), kind=kLoop, calls=%fused_computation.1',
]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("cpu")
    assert roofline.peaks("TPU v5 lite")["bytes_s"] == 819e9


def test_join_calls_from_traced_op_lines():
    (call,) = roofline.join_calls(OPS)
    assert (call["name"], call["C"], call["M"], call["B"],
            call["batch"]) == ("window_join_packed_pallas.6",
                               10, 2048, 128, 16)
    c, m, b, k = 10, 2048, 128, 16
    # Copied model with today's dtypes: f32 strips, int32 op codes and
    # masks, f32 thresholds (not batched), an int8 (M, B) mask out.
    want = k * (4 * c * (m + b) + 4 * c + 4 * (m + b) + m * b) + 4 * c
    assert call["bytes"] == want
    assert call["ops"] == 5 * c * m * b * k


def test_least_time_takes_the_larger_bound():
    peak = roofline.peaks("TPU v5 lite")
    t, bound = roofline.least_time({"bytes": 819e9, "ops": 1.0}, peak)
    assert (t, bound) == (pytest.approx(1.0), "memory")
    t, bound = roofline.least_time({"bytes": 1.0, "ops": 2 * 197e12},
                                   peak)
    assert (t, bound) == (pytest.approx(2.0), "compute")
