"""Public jit'd entry points for the CEP join kernels.

Backend dispatch:

* ``"ref"``       — pure-jnp oracle (XLA fusion; default on CPU hosts).
* ``"pallas"``    — the TPU Pallas kernel (default when a TPU is present).
* ``"interpret"`` — the Pallas kernel in interpret mode (CPU correctness
                    validation of the TPU kernel body; used by tests).

The engine calls these through ``window_join(...)`` so the whole data plane
switches backend with one flag.
"""

from __future__ import annotations

import os

import jax

from . import ref as _ref
from .window_join import (
    window_join_count_pallas,
    window_join_packed_pallas,
    window_join_pallas,
    window_join_rowcount_pallas,
)

_BACKEND = None


def default_backend() -> str:
    # CI's parity matrix forces the engine-wide default through the
    # environment (set_backend / per-call overrides still win).
    env = os.environ.get("REPRO_KERNEL_BACKEND")
    if env:
        if env not in ("ref", "pallas", "interpret"):
            raise ValueError(f"REPRO_KERNEL_BACKEND={env!r} is not one of "
                             "'ref' | 'pallas' | 'interpret'")
        return env
    return "pallas" if jax.devices()[0].platform == "tpu" else "ref"


def set_backend(name: str) -> None:
    """Force a kernel backend: 'ref' | 'pallas' | 'interpret'."""
    global _BACKEND
    if name not in ("ref", "pallas", "interpret", None):
        raise ValueError(f"unknown kernel backend {name!r}")
    _BACKEND = name


def get_backend() -> str:
    return _BACKEND or default_backend()


def window_join(L, R, ops, thetas, *, backend: str | None = None):
    """ok[m, b] = AND_c cmp(op[c], L[c, m], R[c, b], theta[c]) — (M, B) bool."""
    be = backend or get_backend()
    if be == "ref":
        return _ref.window_join_ref(L, R, ops, thetas)
    if be == "pallas":
        return window_join_pallas(L, R, ops, thetas)
    if be == "interpret":
        return window_join_pallas(L, R, ops, thetas, interpret=True)
    raise ValueError(f"unknown kernel backend {be!r}")


def window_join_count(L, R, ops, thetas, *, backend: str | None = None):
    """Count of matching pairs without materializing the mask."""
    be = backend or get_backend()
    if be == "ref":
        return _ref.window_join_ref(L, R, ops, thetas).sum()
    if be == "pallas":
        return window_join_count_pallas(L, R, ops, thetas)
    if be == "interpret":
        return window_join_count_pallas(L, R, ops, thetas, interpret=True)
    raise ValueError(f"unknown kernel backend {be!r}")


def window_join_packed(L, R, ops8, thetas, mvalid, bvalid, *,
                       backend: str | None = None):
    """Packed-strip join: validity as int8 vectors, op dispatch as
    mask-select — bit-identical to ``window_join`` over the equivalent
    unpacked stack (validity encoded as two extra f32 rows)."""
    be = backend or get_backend()
    if be == "ref":
        return _ref.window_join_packed_ref(L, R, ops8, thetas, mvalid,
                                           bvalid)
    if be == "pallas":
        return window_join_packed_pallas(L, R, ops8, thetas, mvalid, bvalid)
    if be == "interpret":
        return window_join_packed_pallas(L, R, ops8, thetas, mvalid, bvalid,
                                         interpret=True)
    raise ValueError(f"unknown kernel backend {be!r}")


def window_join_rowcount(L, R, ops, thetas, *, backend: str | None = None):
    """Per-m row counts — (M,) i32 — without materializing (M, B)."""
    be = backend or get_backend()
    if be == "ref":
        return _ref.window_join_rowcount_ref(L, R, ops, thetas)
    if be == "pallas":
        return window_join_rowcount_pallas(L, R, ops, thetas)
    if be == "interpret":
        return window_join_rowcount_pallas(L, R, ops, thetas,
                                           interpret=True)
    raise ValueError(f"unknown kernel backend {be!r}")
