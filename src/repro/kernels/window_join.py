"""Pallas TPU kernels for the CEP masked windowed cross-join.

The hot loop of the vectorized CEP engine is, per evaluation-plan step, a
dense cross-evaluation of ``C`` constraint rows between ``M`` partial matches
and ``B`` buffered events:

    ok[m, b] = mvalid[m] & bvalid[b] & AND_c cmp(op[c], L[c, m], R[c, b],
                                                  theta[c]).

One kernel family serves every entry point: a mask kernel (the ``(M, B)``
mask as int8, cast to bool by the wrapper) and a rowcount kernel (per-m
counts reduced in VMEM, for negation and Kleene).  The unpacked join is
the mask kernel with all-valid masks, the pair count is the sum of the
row counts.

TPU mapping
-----------
* The grid tiles (M, B) into ``(block_m, block_b)`` tiles (default
  128 x 128, or the autotune table's entry for the shape class).
* ``C`` is small and static: each tile unrolls the AND over the rows.
  Op codes and thresholds are *data*, so one compiled kernel serves every
  plan of a pattern — plan changes never recompile the data plane.
* Operand layouts are chosen for what Mosaic accepts (see the section
  comment below): L transposed to ``(M, C)``, R as ``(C, B)``, op codes
  and thresholds as 32-bit SMEM scalars, validity as int32 ``(M, 1)`` /
  ``(1, B)`` masks.

VMEM per tile at C = 32: operands 2 x 32 x 128 x 4 B (lane-padded) plus
the 128 x 128 mask, well under the scoped VMEM limit with double
buffering.

Checked against the jnp oracle (``ref.py``) in interpret mode on CPU
(``tests/test_kernels.py``, ``tests/test_packed_kernels.py``); compiled for
a described v5e at engine widths, alone and under the fleet's and the
rulebook's ``vmap`` (``tests/test_tpu_compile.py``); run on a TPU v5e
chip through ``cep.open`` and ``cep.open_rulebook`` by ``chip_smoke.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import autotune as _autotune
from . import ref as _ref


def _tile_waste(M, B, bm, bb) -> bool:
    """True when the padded grid does mostly-padding work: below one
    (8, 128) tile of real cells, or >= 4x padding blow-up (the B=4
    pathology: a 4-wide buffer pads to a full 128-lane tile, ~32x waste).
    Such shapes dispatch to the fused jnp reference instead — on small
    operands XLA's fusion beats a mostly-padded Pallas launch."""
    Mp = (M + bm - 1) // bm * bm
    Bp = (B + bb - 1) // bb * bb
    return (M * B < 8 * 128) or (Mp * Bp >= 4 * M * B)


# ---------------------------------------------------------------------------
# One kernel family
# ---------------------------------------------------------------------------
#
# Every entry point feeds the same tile body.  The operands are laid out so
# that the TPU compiler (Mosaic) accepts the kernel alone and under the
# engine's ``vmap`` over K partitions and (K, Q) rules:
#
# * L enters transposed, ``(M, C)``: a ``(block_m, C)`` tile yields each
#   constraint column as a ``(block_m, 1)`` slice (sublane-major), R stays
#   ``(C, B)`` and yields ``(1, block_b)`` rows — no 1-D -> 2-D reshape in
#   the kernel;
# * op-codes and thresholds are 32-bit scalars in SMEM, shaped ``(1, C)``
#   so that the batch axes ``vmap`` prepends never touch the last two block
#   dimensions;
# * each constraint row is a mask-select over the three comparison planes —
#   ``(lt & is_lt) | (gt & is_gt) | (ab & is_ab) | is_none`` — since Mosaic
#   cannot select between boolean vectors;
# * row validity enters as ``(M, 1)`` / ``(1, B)`` int32 masks seeding the
#   accumulator.  Padding extends them with zeros, so padded (m, b) cells
#   are excluded by construction for ANY op mix.
#
# The float comparisons are the exact expressions of ``ref.cmp_op``, so the
# kernels agree bit-for-bit with the jnp oracle — the property the engine's
# differential tests pin across the kernel switch.


def _tile_mask(lt_ref, r_ref, op_ref, th_ref, mv_ref, bv_ref):
    """(block_m, block_b) bool: validity AND every constraint row."""
    acc = (mv_ref[...] > 0) & (bv_ref[...] > 0)
    for c in range(r_ref.shape[0]):  # static unroll over the small C dim
        l = lt_ref[:, c:c + 1]            # (bm, 1)
        r = r_ref[c:c + 1, :]             # (1, bb)
        op = op_ref[0, c]
        th = th_ref[0, c]
        lt = l < r + th
        gt = l > r - th
        ab = jnp.abs(l - r) <= th
        ok = (lt & (op == 1)) | (gt & (op == 2)) | (ab & (op == 3)) \
            | (op == 0)
        acc = acc & ok
    return acc


def _mask_kernel(lt_ref, r_ref, op_ref, th_ref, mv_ref, bv_ref, out_ref):
    out_ref[...] = _tile_mask(lt_ref, r_ref, op_ref, th_ref, mv_ref,
                              bv_ref).astype(jnp.int8)


def _rowcount_kernel(lt_ref, r_ref, op_ref, th_ref, mv_ref, bv_ref,
                     out_ref):
    """Per-m surviving-pair counts, accumulated across the B-tile grid.

    The (bm, bb) mask never leaves VMEM: each tile reduces over its lanes
    and accumulates into the (bm, 1) output block, which the sequential
    j-sweep of the grid revisits.
    """
    j = pl.program_id(1)
    acc = _tile_mask(lt_ref, r_ref, op_ref, th_ref, mv_ref, bv_ref)
    partial = acc.astype(jnp.int32).sum(axis=1, keepdims=True)  # (bm, 1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(j != 0)
    def _accum():
        out_ref[...] = out_ref[...] + partial


def _blocks(C, M, B, block_m, block_b):
    """Static tiles (bm, bb) and padded extents (Mp, Bp).  Explicit caller
    pins win, else the autotune table (per shape class, per platform),
    else (128, 128); tiles are clamped to the operand."""
    if block_m is None or block_b is None:
        abm, abb = _autotune.best_blocks(C, M, B)
        block_m = block_m if block_m is not None else abm
        block_b = block_b if block_b is not None else abb
    bm = min(block_m, max(M, 8))
    bb = min(block_b, max(B, 128))
    return bm, bb, (M + bm - 1) // bm * bm, (B + bb - 1) // bb * bb


def _unpacked_ops(ops):
    """Unpacked op semantics (any code outside {1, 2, 3} is vacuous True)
    restated as the mask-select's codes: such codes become NONE (0)."""
    ops = ops.astype(jnp.int32)
    return jnp.where((ops >= 1) & (ops <= 3), ops, 0)


def _tiled(L, R, ops, thetas, mvalid, bvalid, tiles, interpret, *,
           rowcount=False):
    """Pad to whole tiles, lay the operands out and run the mask kernel,
    or with ``rowcount`` the rowcount kernel.

    Returns the padded output: the ``(Mp, Bp)`` int8 mask, or with
    ``rowcount`` the ``(Mp, 1)`` int32 per-m counts.
    """
    bm, bb, Mp, Bp = tiles
    C, M = L.shape
    B = R.shape[1]
    Lt = jnp.pad(L.astype(jnp.float32).T, ((0, Mp - M), (0, 0)))
    Rp = jnp.pad(R.astype(jnp.float32), ((0, 0), (0, Bp - B)))
    # Validity doubles as the padding mask: padded slots are invalid rows.
    mv = jnp.pad(mvalid.astype(jnp.int32), (0, Mp - M))[:, None]
    bv = jnp.pad(bvalid.astype(jnp.int32), (0, Bp - B))[None, :]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    if rowcount:
        kernel = _rowcount_kernel
        out_spec = pl.BlockSpec((bm, 1), lambda i, j: (i, 0))
        out_shape = jax.ShapeDtypeStruct((Mp, 1), jnp.int32)
    else:
        kernel = _mask_kernel
        out_spec = pl.BlockSpec((bm, bb), lambda i, j: (i, j))
        out_shape = jax.ShapeDtypeStruct((Mp, Bp), jnp.int8)
    return pl.pallas_call(
        kernel,
        grid=(Mp // bm, Bp // bb),
        in_specs=[
            pl.BlockSpec((bm, C), lambda i, j: (i, 0)),
            pl.BlockSpec((C, bb), lambda i, j: (0, j)),
            smem,
            smem,
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bb), lambda i, j: (0, j)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(Lt, Rp, ops.astype(jnp.int32)[None, :],
      thetas.astype(jnp.float32)[None, :], mv, bv)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_b", "interpret")
)
def window_join_pallas(
    L: jax.Array,
    R: jax.Array,
    ops: jax.Array,
    thetas: jax.Array,
    *,
    block_m: int | None = None,
    block_b: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Tiled Pallas evaluation of the constraint cross-join.

    L: (C, M) f32, R: (C, B) f32, ops: (C,) i32, thetas: (C,) f32.
    Returns ok: (M, B) bool: the packed join with every row valid.  M and
    B are padded up to tile multiples internally; padding is sliced away
    before returning.  Block sizes default to the autotune table for the
    shape class.  Interpret mode always runs the kernel body (it is the
    correctness harness); compiled mode falls back to the jnp reference
    for shapes that would be mostly tile padding.
    """
    return window_join_packed_pallas(
        L, R, _unpacked_ops(ops), thetas, jnp.ones(L.shape[1:], jnp.int32),
        jnp.ones(R.shape[1:], jnp.int32), block_m=block_m, block_b=block_b,
        interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_b", "interpret")
)
def window_join_packed_pallas(
    L, R, ops8, thetas, mvalid, bvalid, *, block_m: int | None = None,
    block_b: int | None = None, interpret: bool = False,
) -> jax.Array:
    """Packed-strip cross-join: ok[m, b] = mvalid & bvalid & AND_c row_c.

    L: (C, M) f32, R: (C, B) f32, ops8: (C,) i8, thetas: (C,) f32,
    mvalid: (M,), bvalid: (B,) i8/bool.  Returns (M, B) bool.
    """
    C, M = L.shape
    B = R.shape[1]
    tiles = _blocks(C, M, B, block_m, block_b)
    if not interpret and _tile_waste(M, B, tiles[0], tiles[1]):
        return _ref.window_join_packed_ref(L, R, ops8, thetas, mvalid,
                                           bvalid)
    out = _tiled(L, R, ops8, thetas, mvalid, bvalid, tiles, interpret)
    return out[:M, :B].astype(jnp.bool_)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_b", "interpret")
)
def window_join_rowcount_pallas(
    L, R, ops, thetas, *, block_m: int | None = None,
    block_b: int | None = None, interpret: bool = False,
) -> jax.Array:
    """Fused per-m row counts: cnt[m] = sum_b AND_c cmp(...) — (M,) i32.

    What the finalize pass actually consumes for negation (cnt > 0) and
    Kleene closure (cnt - 1): the (M, B) mask is reduced tile-locally and
    never materialized to HBM.
    """
    C, M = L.shape
    B = R.shape[1]
    tiles = _blocks(C, M, B, block_m, block_b)
    if not interpret and _tile_waste(M, B, tiles[0], tiles[1]):
        return _ref.window_join_rowcount_ref(L, R, ops, thetas)
    counts = _tiled(L, R, _unpacked_ops(ops), thetas,
                    jnp.ones((M,), jnp.int32), jnp.ones((B,), jnp.int32),
                    tiles, interpret, rowcount=True)
    return counts[:M, 0]


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_b", "interpret")
)
def window_join_count_pallas(
    L, R, ops, thetas, *, block_m: int | None = None,
    block_b: int | None = None, interpret: bool = False,
) -> jax.Array:
    """Total number of matching (m, b) pairs: the sum of the fused row
    counts, so the mask never reaches HBM."""
    return window_join_rowcount_pallas(
        L, R, ops, thetas, block_m=block_m, block_b=block_b,
        interpret=interpret).sum()
