"""Peaks of the chips the benchmark runs on, and the operations and bytes
of one window-join kernel call.

The peaks are keyed by ``device_kind`` as JAX reports it.  A device that
is not in the table is an error, never a default.

The cost model is that of one packed windowed cross-join (the program's
``benchmarks/roofline.py::join_roofline``, copied here so that no change
to the program moves it): the call reads the ``C`` constraint strips of
both sides, ``L`` as ``(M, C)`` and ``R`` as ``(C, B)``, the op codes,
the thresholds and the two validity masks, and writes its output; it
does about five operations per ``(c, m, b)`` cell (three comparison
planes, the mask-select and the AND).  The copied model counted the op
codes and validity masks as int8 and the output as an ``(M, B)`` int8
mask; the kernel family of today passes them as int32, and its row-count
variant writes counts instead of a mask, so the bytes are taken from the
shapes and dtypes of the call's operands and result.  Those come from the
profiler trace of the window itself: a TPU trace names each device op
after its HLO instruction line, operand shapes included, so the calls
read are those of the executable the window ran.
"""

from __future__ import annotations

import math
import re
from typing import Dict, Iterable, List

PEAKS: Dict[str, dict] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 16 GB HBM at 819 GB/s per chip.
    "TPU v5 lite": {"flops_s": 197e12, "bytes_s": 819e9,
                    "hbm_bytes": 16e9},
}

OPS_PER_CELL = 5

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
                "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8}
_SHAPE = re.compile(r"(pred|[suf]\d+|bf16)\[([\d,]*)\]")
# An operand of an HLO instruction line: its shape, an optional layout,
# then its name.
_OPERAND = re.compile(r"(pred|[suf]\d+|bf16)\[([\d,]*)\](?:\{[^}]*\})? %")
_CALL = re.compile(r"%?([\w.\-]+) = (.*?) custom-call\((.*)")


class UnknownDevice(ValueError):
    pass


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None


def _shape(dtype: str, dims: str):
    return dtype, tuple(int(d) for d in dims.split(",") if d)


def _nbytes(dtype: str, dims) -> int:
    return _DTYPE_BYTES[dtype] * math.prod(dims)


def join_calls(op_lines: Iterable[str],
               target: str = "window_join") -> List[dict]:
    """The window-join kernel calls among HLO instruction lines.

    A call is a ``custom-call`` whose instruction name holds ``target``.
    Its first two operands are ``L`` as ``(..., M, C)`` and ``R`` as
    ``(..., C, B)``; the leading dimensions are the batch (the K
    partitions of the fleet).  Returns, per call, its instruction name,
    ``C``, ``M``, ``B``, the batch, its bytes and its operations.
    """
    calls = []
    for line in op_lines:
        m = _CALL.match(line.strip())
        if m is None or target not in m.group(1):
            continue
        result = [_shape(*s) for s in _SHAPE.findall(m.group(2))]
        operands = [_shape(*s) for s in _OPERAND.findall(m.group(3))]
        if len(operands) < 2 or not result:
            continue
        (_, l_dims), (_, r_dims) = operands[0], operands[1]
        c, mm, b = l_dims[-1], l_dims[-2], r_dims[-1]
        batch = math.prod(l_dims[:-2])
        calls.append({
            "name": m.group(1), "C": c, "M": mm, "B": b, "batch": batch,
            "bytes": sum(_nbytes(*s) for s in operands + result),
            "ops": OPS_PER_CELL * c * mm * b * batch,
        })
    return calls


def least_time(call: dict, peak: dict):
    """Least seconds the chip could take for one call, and its bound."""
    mem = call["bytes"] / peak["bytes_s"]
    comp = call["ops"] / peak["flops_s"]
    return (mem, "memory") if mem >= comp else (comp, "compute")
