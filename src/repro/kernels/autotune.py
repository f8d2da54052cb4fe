"""Block-size autotuning for the window-join Pallas kernels.

The join kernels tile the (M, B) output into ``(block_m, block_b)`` VMEM
tiles.  The best tile is a function of the join *shape class* — the
constraint count ``C`` and the padded extents of ``M`` (match capacity)
and ``B`` (buffer capacity) — and of the platform.  Because the engine
only ever instantiates a handful of shape classes (capacities are
config, not data), the tuning problem is tiny: sweep the block grid once
per shape class, persist the winners in a small on-disk table, and let
every kernel entry point consult it at trace time (block sizes are
static arguments — a table hit never recompiles anything that already
compiled with the same blocks).

Table location: ``benchmarks/autotune_cache.json`` at the repo root (the
committed table tracks the shapes ``benchmarks/kernel_bench.py`` sweeps;
override with ``REPRO_AUTOTUNE_TABLE=/path/to.json``, disable with
``REPRO_AUTOTUNE_TABLE=""``).  Missing table / missing class fall back
to the lane-aligned ``(128, 128)`` default, so the engine never depends
on the file existing.

Schema (versioned, one entry per shape class per platform)::

    {"schema": "autotune/v1",
     "entries": {"cpu/C16_M4096_B256": {"block_m": 128, "block_b": 128,
                                        "us": 812.4, "kernel": "packed"},
                 ...}}

``kernel_bench --sweep`` regenerates the table (see
``benchmarks/kernel_bench.py::autotune_sweep``).
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional, Tuple

_DEFAULT_BLOCKS = (128, 128)

# Candidate tile grid swept by the autotuner.  Lane dim (block_b) stays a
# multiple of 128 (TPU lane width); sublane dim (block_m) a multiple of 8.
BLOCK_M_CANDIDATES = (8, 32, 128, 256, 512)
BLOCK_B_CANDIDATES = (128, 256, 512)

_TABLE_CACHE: Optional[Dict[str, dict]] = None
_TABLE_PATH_CACHE: Optional[str] = None


def default_table_path() -> str:
    """benchmarks/autotune_cache.json relative to the repo root."""
    env = os.environ.get("REPRO_AUTOTUNE_TABLE")
    if env is not None:
        return env
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(os.path.dirname(os.path.dirname(here)))
    return os.path.join(root, "benchmarks", "autotune_cache.json")


def _pow2_bucket(x: int) -> int:
    """Round up to the next power of two (shape-class bucketing)."""
    p = 1
    while p < x:
        p *= 2
    return p


def shape_class(C: int, M: int, B: int) -> str:
    """Bucketed shape-class key: exact in C, pow2 in M and B.

    Capacities are configuration (b_cap / m_cap), already powers of two in
    every shipped config, so bucketing only matters for ad-hoc shapes.
    """
    return f"C{int(C)}_M{_pow2_bucket(int(M))}_B{_pow2_bucket(int(B))}"


def platform() -> str:
    import jax

    return jax.devices()[0].platform


def load_table(path: Optional[str] = None) -> Dict[str, dict]:
    """Load (and memoize) the on-disk table; {} when absent/disabled."""
    global _TABLE_CACHE, _TABLE_PATH_CACHE
    path = path if path is not None else default_table_path()
    if _TABLE_CACHE is not None and _TABLE_PATH_CACHE == path:
        return _TABLE_CACHE
    entries: Dict[str, dict] = {}
    if path and os.path.exists(path):
        try:
            with open(path) as fh:
                payload = json.load(fh)
            if payload.get("schema") == "autotune/v1":
                entries = dict(payload.get("entries", {}))
        except (OSError, ValueError):  # corrupt table == no table
            entries = {}
    _TABLE_CACHE = entries
    _TABLE_PATH_CACHE = path
    return entries


def invalidate_cache() -> None:
    """Drop the memoized table (tests / after a sweep rewrite)."""
    global _TABLE_CACHE, _TABLE_PATH_CACHE
    _TABLE_CACHE = None
    _TABLE_PATH_CACHE = None


def save_table(entries: Dict[str, dict], path: Optional[str] = None) -> str:
    path = path if path is not None else default_table_path()
    payload = {"schema": "autotune/v1", "entries": entries}
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
        fh.write("\n")
    invalidate_cache()
    return path


def best_blocks(C: int, M: int, B: int,
                plat: Optional[str] = None) -> Tuple[int, int]:
    """(block_m, block_b) for a join shape: table hit or (128, 128).

    Called by the kernel wrappers when the caller does not pin blocks
    explicitly; runs at trace time (shapes are static), so the lookup
    costs nothing per step.
    """
    plat = plat or platform()
    entry = load_table().get(f"{plat}/{shape_class(C, M, B)}")
    if entry:
        return int(entry["block_m"]), int(entry["block_b"])
    return _DEFAULT_BLOCKS
