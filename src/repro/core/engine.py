"""Vectorized CEP evaluation engine (data plane) in JAX.

Classical CEP engines (lazy-NFA [36], ZStream [42]) are event-at-a-time
pointer-chasing state machines — the worst possible shape for a TPU.  This
module re-thinks the data structures for the TPU memory hierarchy while
preserving the paper's semantics and cost model:

* **Per-type ring buffers** hold the recent stream history (struct-of-arrays,
  fixed capacity, masked).
* **Match sets are dense masked tensors**: a set of (partial) matches is a
  ``(M_cap, n)`` timestamp/attribute block plus a validity mask and a
  position-membership vector.
* **Every plan step is one masked windowed cross-join** — a stack of ``C``
  constraint rows (validity, time window, sequence order, pairwise
  predicates) evaluated between ``M`` partial matches and ``B`` candidate
  events by the ``window_join`` kernel (Pallas on TPU, jnp oracle on CPU),
  followed by prefix-sum compaction.  The number of surviving pairs is
  exactly the partial-match count the paper's plans minimize, so plan
  quality maps 1:1 onto join work.

* **Plans are data, not code.**  An order-based plan enters as a length-``n``
  permutation vector; a tree-based plan as ``(n-1, 2)`` slot-join indices.
  One compiled executor therefore serves *every* plan of a given pattern —
  an adaptation (plan switch) never recompiles the data plane.  This is the
  TPU-native answer to the paper's requirement that plan deployment be cheap
  relative to detection (§2.2).

Chunked semantics: the engine consumes the stream in chunks ``(t0, t1]``.
Each chunk is ingested into the ring buffers, the full join cascade runs
over the in-window history, and a match is **counted exactly once** — in the
chunk where its latest event arrives (``max_ts ∈ (t0, t1]``).  This is the
sliding-window re-evaluation formulation: it preserves SASE detection
semantics while keeping every tensor shape static.

Operator support beyond SEQ/AND (§2.1, via the paper's transformation-rule
approach): negation is a post-join anti-filter against the negated type's
buffer; Kleene closure is a bounded companion count per base match
(count-only semantics — see DESIGN.md); OR-composites are evaluated as
independent branches by the adaptation layer.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from . import spans
from .patterns import PRED_GT, PRED_LT, PRED_NONE, Pattern
from .plans import OrderPlan, TreeNode, TreePlan

_LT = PRED_LT
_GT = PRED_GT
_NONE = PRED_NONE

# Born-window sentinels shared by every stepping contract (f32-safe ±inf).
# The pure step signature is ``process_fn(buffers, chunk, plan, t0, t1,
# born_lo, born_hi) -> (buffers, StepResult)`` — state first, outputs
# second — which is what lets one function serve jit (single stream),
# jit(vmap) (fleet), lax.scan (superchunk) and shard_map (multi-device)
# without adaptation shims; see ``core/scan.py``.
NEG_INF = -3.0e38
POS_INF = 3.0e38

# Width of the column blocks the compaction counts survivors in: one lane
# row of the TPU's vector registers.
_BLOCK = 128


class Chunk(NamedTuple):
    """One stream chunk (struct-of-arrays)."""

    type_id: jax.Array  # (N,) i32 global event-type ids
    ts: jax.Array       # (N,) f32 timestamps (non-decreasing)
    attr: jax.Array     # (N, A) f32 attributes
    valid: jax.Array    # (N,) bool


class Buffers(NamedTuple):
    """Per-position ring buffers (+ one extra row for a negated type)."""

    ts: jax.Array      # (T, B) f32
    attr: jax.Array    # (T, B, A) f32
    valid: jax.Array   # (T, B) bool
    ptr: jax.Array     # (T,) i32 cumulative writes


class MatchSet(NamedTuple):
    """A dense masked set of (partial) matches."""

    ts: jax.Array       # (M, n) f32 per-position timestamps
    attr: jax.Array     # (M, n, A) f32 per-position attributes
    min_ts: jax.Array   # (M,) f32
    max_ts: jax.Array   # (M,) f32
    valid: jax.Array    # (M,) bool
    member: jax.Array   # (n,) bool — positions filled in this set


class StepResult(NamedTuple):
    full_matches: jax.Array        # i32 — completed this chunk
    pm_created: jax.Array          # i32 — total partial matches materialized
    overflow: jax.Array            # i32 — candidates dropped by capacity
    closure_expansions: jax.Array  # i32 — Kleene companion count
    neg_rejected: jax.Array        # i32 — matches vetoed by negation


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    b_cap: int = 128   # ring-buffer capacity per event type
    m_cap: int = 256   # match-set row capacity (>= b_cap)
    backend: Optional[str] = None  # kernel backend override

    def __post_init__(self):
        if self.m_cap < self.b_cap:
            raise ValueError("m_cap must be >= b_cap")


# ---------------------------------------------------------------------------
# Shared join machinery
# ---------------------------------------------------------------------------


def _rows_to_stacks(rows, m, b):
    """rows: list of (lvals (M,), rvals (B,), op scalar, theta scalar)."""
    L = jnp.stack([jnp.broadcast_to(r[0], (m,)).astype(jnp.float32)
                   for r in rows])
    R = jnp.stack([jnp.broadcast_to(r[1], (b,)).astype(jnp.float32)
                   for r in rows])
    ops_ = jnp.stack([jnp.asarray(r[2], jnp.int32) for r in rows])
    ths = jnp.stack([jnp.asarray(r[3], jnp.float32) for r in rows])
    return L, R, ops_, ths


def _validity_rows(l_valid, r_valid, m, b):
    return [
        (l_valid.astype(jnp.float32), jnp.ones((b,), jnp.float32), _GT, 0.5),
        (jnp.ones((m,), jnp.float32), r_valid.astype(jnp.float32), _LT, 0.5),
    ]


def _window_rows(l_min, l_max, r_min, r_max, window):
    # span(L ∪ R) < W  ⇔  maxL < minR + W  ∧  minL > maxR − W.
    return [
        (l_max, r_min, _LT, float(window)),
        (l_min, r_max, _GT, float(window)),
    ]


def _pred_rows(spec, L: MatchSet, R: MatchSet):
    """Two orientation rows per static predicate pair, masked by membership."""
    rows = []
    for (p, q) in spec.pred_pairs:
        for (a, b_) in ((p, q), (q, p)):
            active = L.member[a] & R.member[b_]
            op = jnp.where(active, spec.op_t[a, b_], _NONE)
            lv = L.attr[:, a, spec.a_attr_t[a, b_]]
            rv = R.attr[:, b_, spec.b_attr_t[a, b_]]
            rows.append((lv, rv, op, spec.theta_t[a, b_]))
    return rows


def _rank_level(running, ranks):
    """Per rank ``r``: how many of its ``running`` counts lie below ``r``,
    and ``r`` less the last of them (its rank within the next entry).

    ``running`` is nondecreasing along its last axis and broadcasts against
    ``ranks[:, None]``; the search is one dense compare and reduction."""
    below = running < ranks[:, None]
    return (below.sum(axis=1, dtype=jnp.int32),
            ranks - jnp.max(jnp.where(below, running, 0), axis=1))


def _select(ok, out_cap: int):
    """Flat indices of the first ``out_cap`` true entries of ``ok``
    ``(m, b)`` in row-major order, ``m * b`` past the last of them.

    A rank search in three levels, by gathers and dense compares alone: the
    row of the r-th survivor from the rows' running counts, its 128-wide
    block within the row from the row's running block counts, and its
    column from a prefix sum over that one gathered block: no scatter, and
    no prefix sum over the whole mask."""
    m, b = ok.shape
    w = min(b, _BLOCK)
    nb = -(-b // w)
    if nb * w != b:
        ok = jnp.pad(ok, ((0, 0), (0, nb * w - b)))
    blocks = ok.reshape(m * nb, w)
    block_run = jnp.cumsum(
        blocks.sum(axis=1, dtype=jnp.int32).reshape(m, nb), axis=1)
    row, r = _rank_level(jnp.cumsum(block_run[:, -1]),
                         jnp.arange(1, out_cap + 1, dtype=jnp.int32))
    rowc = jnp.minimum(row, m - 1)
    blk = jnp.zeros_like(row)
    if nb > 1:
        blk, r = _rank_level(block_run[rowc], r)
        blk = jnp.minimum(blk, nb - 1)
    col, _ = _rank_level(
        jnp.cumsum(blocks[rowc * nb + blk], axis=1, dtype=jnp.int32), r)
    return jnp.where(row < m, rowc * b + blk * w + col, m * b)


def _gather_rows(S: MatchSet, i):
    """``ts``, ``attr``, ``min_ts`` and ``max_ts`` of the rows ``i`` of
    ``S``, by one gather of the fields packed side by side."""
    rows, n = S.ts.shape
    packed = jnp.concatenate(
        [S.ts, S.attr.reshape(rows, -1), S.min_ts[:, None],
         S.max_ts[:, None]], axis=1)[i]
    return (packed[:, :n],
            packed[:, n:-2].reshape(i.shape + S.attr.shape[1:]),
            packed[:, -2], packed[:, -1])


@jax.named_scope(spans.COMPACT)
def _compact(L: MatchSet, R: MatchSet, ok, pm_created, out_cap: int):
    """Compaction of the surviving (m, b) pairs into a MatchSet.

    The r-th output row is the r-th true pair of ``ok`` in row-major order;
    rows past the survivors get the index ``m * b`` and come out invalid.
    This is what ``jnp.nonzero(flat, size=out_cap, fill_value=m * b)``
    selects, found by ``_select``'s rank search instead of ``nonzero``'s
    scatter of every mask element into ``out_cap`` bins, which a TPU runs
    serially."""
    m = L.valid.shape[0]
    b = R.valid.shape[0]
    idx = _select(ok, out_cap)
    mi = jnp.clip(idx // b, 0, m - 1)
    bi = jnp.clip(idx % b, 0, b - 1)

    l_ts, l_attr, l_min, l_max = _gather_rows(L, mi)
    r_ts, r_attr, r_min, r_max = _gather_rows(R, bi)
    memL = L.member[None, :]
    out = MatchSet(
        ts=jnp.where(memL, l_ts, r_ts),
        attr=jnp.where(memL[:, :, None], l_attr, r_attr),
        min_ts=jnp.minimum(l_min, r_min),
        max_ts=jnp.maximum(l_max, r_max),
        valid=idx < m * b,
        member=L.member | R.member,
    )
    overflow = jnp.maximum(0, pm_created - out_cap).astype(jnp.int32)
    return out, pm_created, overflow


def _join(spec, cfg, L: MatchSet, R: MatchSet, order_rows, out_cap: int):
    """One plan step: constraint cross-join + compaction."""
    m = L.valid.shape[0]
    b = R.valid.shape[0]
    with jax.named_scope(spans.JOIN):
        rows = (
            _validity_rows(L.valid, R.valid, m, b)
            + _window_rows(L.min_ts, L.max_ts, R.min_ts, R.max_ts,
                           spec.window)
            + order_rows
            + _pred_rows(spec, L, R)
        )
        Ls, Rs, ops_, ths = _rows_to_stacks(rows, m, b)
        ok = kops.window_join(Ls, Rs, ops_, ths, backend=cfg.backend)
        pm_created = ok.sum().astype(jnp.int32)
    return _compact(L, R, ok, pm_created, out_cap)


def _row_counts(cfg, rows, m, b):
    """Per-m 'compatible event' counts (negation veto / Kleene count).

    Routed through the fused rowcount kernel, which reduces each tile in
    VMEM instead of materializing the (m, b) mask to HBM."""
    Ls, Rs, ops_, ths = _rows_to_stacks(rows, m, b)
    return kops.window_join_rowcount(Ls, Rs, ops_, ths,
                                     backend=cfg.backend)


# ---------------------------------------------------------------------------
# Spec: static pattern-derived data shared by both engines
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Spec:
    n: int
    n_attrs: int
    window: float
    is_seq: bool
    pred_pairs: Tuple[Tuple[int, int], ...]
    op_t: np.ndarray
    a_attr_t: np.ndarray
    b_attr_t: np.ndarray
    theta_t: np.ndarray
    kleene_pos: Optional[int]
    kleene_bound: Optional[int]
    has_neg: bool
    negated_pos: Optional[int]
    # negated-predicate rows: (match_pos, op, match_attr, neg_attr, theta)
    neg_rows: Tuple[Tuple[int, int, int, int, float], ...]
    type_ids: Tuple[int, ...]
    negated_type: Optional[int]


def make_spec(pattern: Pattern) -> _Spec:
    t = pattern.pred_tensors()
    mirror = {PRED_NONE: PRED_NONE, PRED_LT: PRED_GT, PRED_GT: PRED_LT, 3: 3}
    neg_rows = []
    if pattern.negated_type is not None:
        pos_of = {tid: p for p, tid in enumerate(pattern.type_ids)}
        for pr in pattern.negated_predicates:
            if pr.a_type == pattern.negated_type:
                # cmp(neg, match) -> mirror so the match side is L.
                neg_rows.append((pos_of[pr.b_type], mirror[pr.op],
                                 pr.b_attr, pr.a_attr, pr.theta))
            else:
                neg_rows.append((pos_of[pr.a_type], pr.op,
                                 pr.a_attr, pr.b_attr, pr.theta))
    return _Spec(
        n=pattern.n,
        n_attrs=pattern.n_attrs,
        window=pattern.window,
        is_seq=pattern.is_sequence,
        pred_pairs=pattern.selectivity_pairs(),
        op_t=t["op"],
        a_attr_t=t["a_attr"],
        b_attr_t=t["b_attr"],
        theta_t=t["theta"],
        kleene_pos=pattern.kleene_pos,
        kleene_bound=pattern.kleene_bound,
        has_neg=pattern.negated_type is not None,
        negated_pos=pattern.negated_pos,
        neg_rows=tuple(neg_rows),
        type_ids=pattern.type_ids,
        negated_type=pattern.negated_type,
    )


# ---------------------------------------------------------------------------
# Buffers
# ---------------------------------------------------------------------------


def init_buffers(spec: _Spec, cfg: EngineConfig) -> Buffers:
    t = spec.n + (1 if spec.has_neg else 0)
    b, a = cfg.b_cap, spec.n_attrs
    return Buffers(
        ts=jnp.zeros((t, b), jnp.float32),
        attr=jnp.zeros((t, b, a), jnp.float32),
        valid=jnp.zeros((t, b), bool),
        ptr=jnp.zeros((t,), jnp.int32),
    )


@jax.named_scope(spans.INGEST)
def _ingest(spec: _Spec, cfg: EngineConfig, buffers: Buffers,
            chunk: Chunk) -> Buffers:
    """Route chunk events into their per-type ring buffers."""
    bcap = cfg.b_cap
    gids = list(spec.type_ids)
    if spec.has_neg:
        gids.append(spec.negated_type)
    ts, attr, valid, ptr = buffers
    for row, gid in enumerate(gids):  # static loop, n+1 rows max
        mask = (chunk.type_id == gid) & chunk.valid
        k = jnp.cumsum(mask.astype(jnp.int32)) - 1
        slot = jnp.where(mask, (ptr[row] + k) % bcap, bcap)  # bcap -> drop
        ts = ts.at[row, slot].set(chunk.ts, mode="drop")
        attr = attr.at[row, slot].set(chunk.attr, mode="drop")
        valid = valid.at[row, slot].set(True, mode="drop")
        ptr = ptr.at[row].add(mask.sum().astype(jnp.int32))
    return Buffers(ts, attr, valid, ptr)


def _leaf(spec: _Spec, cfg: EngineConfig, buffers: Buffers, row, pos,
          t0, out_rows: int) -> MatchSet:
    """View one buffer row as a single-position match set (padded).

    Eviction threshold is ``t0 - W``: a match completed in (t0, t1] may
    reference events up to one window older than the chunk start.
    """
    n, a, b = spec.n, spec.n_attrs, cfg.b_cap
    ts_b = buffers.ts[row]                       # (B,)
    attr_b = buffers.attr[row]                   # (B, A)
    valid = buffers.valid[row] & (ts_b > t0 - spec.window)
    onehot = (jnp.arange(n) == pos)              # (n,) bool
    ts = jnp.where(onehot[None, :], ts_b[:, None], 0.0)
    attr = jnp.where(onehot[None, :, None], attr_b[:, None, :], 0.0)
    ms = MatchSet(ts, attr, ts_b, ts_b, valid, onehot)
    if out_rows != b:
        pad = out_rows - b
        ms = MatchSet(
            ts=jnp.pad(ms.ts, ((0, pad), (0, 0))),
            attr=jnp.pad(ms.attr, ((0, pad), (0, 0), (0, 0))),
            min_ts=jnp.pad(ms.min_ts, (0, pad)),
            max_ts=jnp.pad(ms.max_ts, (0, pad)),
            valid=jnp.pad(ms.valid, (0, pad)),
            member=ms.member,
        )
    return ms


# ---------------------------------------------------------------------------
# Post-processing: completion filter, negation, Kleene
# ---------------------------------------------------------------------------


@jax.named_scope(spans.FINALIZE)
def _finalize(spec: _Spec, cfg: EngineConfig, buffers: Buffers,
              pm: MatchSet, t0, t1, born_lo,
              born_hi) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Count full matches completed in (t0, t1]; apply negation and Kleene.

    ``born_lo <= min_ts < born_hi`` implements the [36] plan-migration
    split: during a migration window the old plan is responsible for
    matches containing at least one pre-replan event (min_ts < t_replan)
    and the new plan for matches born entirely after it — disjoint sets,
    so no match is detected twice (§2.2).
    """
    n = spec.n
    m = pm.valid.shape[0]
    b = cfg.b_cap
    completed = (pm.valid & (pm.max_ts > t0) & (pm.max_ts <= t1)
                 & (pm.min_ts >= born_lo) & (pm.min_ts < born_hi))
    neg_rejected = jnp.int32(0)

    if spec.has_neg:
        row = n  # negated buffer row
        nts = buffers.ts[row]
        nvalid = buffers.valid[row] & (nts > t0 - spec.window)
        rows = _validity_rows(completed, nvalid, m, b)
        rows += _window_rows(pm.min_ts, pm.max_ts, nts, nts, spec.window)
        np_ = spec.negated_pos
        if np_ is not None and np_ > 0:
            rows.append((pm.ts[:, np_ - 1], nts, _LT, 0.0))
        if np_ is not None and np_ < n:
            rows.append((pm.ts[:, np_], nts, _GT, 0.0))
        for (pos, op, ma, na, th) in spec.neg_rows:
            rows.append((pm.attr[:, pos, ma], buffers.attr[row][:, na],
                         op, th))
        cnt = _row_counts(cfg, rows, m, b)
        veto = cnt > 0
        neg_rejected = (completed & veto).sum().astype(jnp.int32)
        completed = completed & ~veto

    closure = jnp.int32(0)
    if spec.kleene_pos is not None:
        kp = spec.kleene_pos
        kts = buffers.ts[kp]
        kvalid = buffers.valid[kp] & (kts > t0 - spec.window)
        rows = _validity_rows(completed, kvalid, m, b)
        rows += _window_rows(pm.min_ts, pm.max_ts, kts, kts, spec.window)
        if spec.is_seq and kp > 0:
            rows.append((pm.ts[:, kp - 1], kts, _LT, 0.0))
        if spec.is_seq and kp < n - 1:
            rows.append((pm.ts[:, kp + 1], kts, _GT, 0.0))
        for (p, q) in spec.pred_pairs:
            if q == kp:
                rows.append((pm.attr[:, p, spec.a_attr_t[p, kp]],
                             buffers.attr[kp][:, spec.b_attr_t[p, kp]],
                             spec.op_t[p, kp], spec.theta_t[p, kp]))
            elif p == kp:
                rows.append((pm.attr[:, q, spec.a_attr_t[q, kp]],
                             buffers.attr[kp][:, spec.b_attr_t[q, kp]],
                             spec.op_t[q, kp], spec.theta_t[q, kp]))
        cnt = _row_counts(cfg, rows, m, b)
        comp = jnp.maximum(cnt - 1, 0)  # exclude the match's own
        if spec.kleene_bound is not None:
            comp = jnp.minimum(comp, spec.kleene_bound)
        closure = jnp.where(completed, comp, 0).sum().astype(jnp.int32)

    return completed.sum().astype(jnp.int32), neg_rejected, closure


# ---------------------------------------------------------------------------
# Predicate strips: the plan-constant half of the join operands
# ---------------------------------------------------------------------------
#
# The constraint stack fed to the kernel at plan step ``i`` splits into two
# halves with very different lifetimes:
#
# * **stream-dependent values** (timestamps, attributes, validity) — these
#   change every chunk and are pure gathers from the ring buffers / match
#   set;
# * **plan-dependent structure** (which op applies per row, and which
#   already-placed position anchors the sequence-order rows) — a function
#   of the order vector alone, constant for as long as the plan is
#   deployed.
#
# ``PredicateStrips`` captures the second half.  The per-chunk step used to
# rebuild it inside every trace; precomputing it once per deployed plan
# (``OrderEngine.plan_operands``) and carrying it through the superchunk
# scan turns the per-chunk work into gather + kernel.  Thresholds and the
# attribute gather columns are static pattern data and are baked into the
# compiled step directly (``_packed_thetas`` / ``_pred_cols``).


class PredicateStrips(NamedTuple):
    """Plan-constant packed join operands for an order plan (n-1 steps)."""

    ops8: jax.Array    # (n-1, C) i8 — per-step op-code strip
    lo_idx: jax.Array  # (n-1,) i32 — clipped lower order-anchor position
    hi_idx: jax.Array  # (n-1,) i32 — clipped upper order-anchor position


class PlanOperands(NamedTuple):
    """An order row together with its precomputed strips.

    The engine's ``process`` accepts either the raw row (strips are then
    derived in-trace — the per-chunk path) or this pair (the scanned path,
    where the derivation runs once per superchunk dispatch).  Both are
    pytrees, so the same vmapped/scanned executor serves both.
    """

    row: jax.Array           # (n,) i32 order vector
    strips: PredicateStrips


def packed_row_count(spec: _Spec) -> int:
    """Rows in the packed constraint stack (validity lives in the masks)."""
    return 2 + (2 if spec.is_seq else 0) + 2 * len(spec.pred_pairs)


def _packed_thetas(spec: _Spec) -> jnp.ndarray:
    """Static per-row thresholds matching the packed row layout."""
    ths = [float(spec.window), float(spec.window)]
    if spec.is_seq:
        ths += [0.0, 0.0]
    for (p, q) in spec.pred_pairs:
        for (a, b_) in ((p, q), (q, p)):
            ths.append(float(spec.theta_t[a, b_]))
    return jnp.asarray(ths, jnp.float32)


def _pred_cols(spec: _Spec):
    """Static (a, b, a_attr_col, b_attr_col) per packed predicate row."""
    cols = []
    for (p, q) in spec.pred_pairs:
        for (a, b_) in ((p, q), (q, p)):
            cols.append((a, b_, int(spec.a_attr_t[a, b_]),
                         int(spec.b_attr_t[a, b_])))
    return tuple(cols)


def build_order_strips(spec: _Spec, order) -> PredicateStrips:
    """Derive the plan-constant strips from an order vector.

    Step ``i`` joins the accumulated prefix {order[0..i-1]} with the leaf
    of position ``order[i]``; row activation therefore depends only on the
    order vector: a predicate row (a, b) fires iff ``a`` is already placed
    and ``b == order[i]``, and the sequence-order rows anchor on the
    nearest placed position below/above ``order[i]``.  O(n^2) scalar work
    — negligible once per plan, pure waste once per chunk.
    """
    n = spec.n
    C = packed_row_count(spec)
    if n <= 1:
        return PredicateStrips(
            ops8=jnp.zeros((0, C), jnp.int8),
            lo_idx=jnp.zeros((0,), jnp.int32),
            hi_idx=jnp.zeros((0,), jnp.int32))
    order = jnp.asarray(order, jnp.int32)
    pos = jnp.arange(n)
    member = (pos == order[0])
    ops_steps, lo_steps, hi_steps = [], [], []
    for i in range(1, n):
        q = order[i]
        row_ops = [jnp.asarray(_LT, jnp.int8), jnp.asarray(_GT, jnp.int8)]
        lo = jnp.int32(0)
        hi = jnp.int32(0)
        if spec.is_seq:
            lo_cand = jnp.where(member & (pos < q), pos, -1)
            p_lo = lo_cand.max()
            hi_cand = jnp.where(member & (pos > q), pos, n)
            p_hi = hi_cand.min()
            row_ops.append(
                jnp.where(p_lo >= 0, _LT, _NONE).astype(jnp.int8))
            row_ops.append(
                jnp.where(p_hi < n, _GT, _NONE).astype(jnp.int8))
            lo = jnp.clip(p_lo, 0, n - 1).astype(jnp.int32)
            hi = jnp.clip(p_hi, 0, n - 1).astype(jnp.int32)
        for (a, b_, _ac, _bc) in _pred_cols(spec):
            active = member[a] & (q == b_)
            row_ops.append(jnp.where(
                active, jnp.int8(spec.op_t[a, b_]), jnp.int8(_NONE)))
        ops_steps.append(jnp.stack(row_ops))
        lo_steps.append(lo)
        hi_steps.append(hi)
        member = member | (pos == q)
    return PredicateStrips(
        ops8=jnp.stack(ops_steps),
        lo_idx=jnp.stack(lo_steps),
        hi_idx=jnp.stack(hi_steps))


# ---------------------------------------------------------------------------
# Order-based engine (lazy-NFA style)
# ---------------------------------------------------------------------------


class OrderEngine:
    """Executes order-based plans; the order vector is a dynamic argument."""

    def __init__(self, pattern: Pattern, cfg: EngineConfig = EngineConfig()):
        self.pattern = pattern
        self.spec = make_spec(pattern)
        self.cfg = cfg
        # The raw (un-jitted) pure function is kept for vmapping: the fleet
        # executor batches K partitions through one compiled vmap of it.
        self.process_fn = self._make_process()
        self._process = jax.jit(self.process_fn)

    def init_state(self) -> Buffers:
        return init_buffers(self.spec, self.cfg)

    def plan_operands(self, rows) -> PlanOperands:
        """Precompute the strips for one (n,) or a stacked (K, n) row set.

        Used by the superchunk scan to hoist the strip derivation out of
        the per-chunk body — it runs once per scanned dispatch instead of
        once per chunk.  Traceable (rows may be device arrays).
        """
        spec = self.spec
        rows = jnp.asarray(rows, jnp.int32)
        if rows.ndim == 1:
            return PlanOperands(rows, build_order_strips(spec, rows))
        return jax.vmap(
            lambda r: PlanOperands(r, build_order_strips(spec, r)))(rows)

    def _make_process(self):
        spec, cfg = self.spec, self.cfg
        n = spec.n
        ths_const = _packed_thetas(spec)
        pred_cols = _pred_cols(spec)

        def packed_step(buffers, pm, q, sops, lo, hi, t0):
            """gather + packed kernel + compaction — one plan step."""
            with jax.named_scope(spans.JOIN):
                R = _leaf(spec, cfg, buffers, q, q, t0, cfg.b_cap)
                attr_b = buffers.attr[q]
                Lr = [pm.max_ts, pm.min_ts]
                Rr = [R.min_ts, R.max_ts]
                if spec.is_seq:
                    Lr += [pm.ts[:, lo], pm.ts[:, hi]]
                    Rr += [R.min_ts, R.min_ts]
                for (a, _b, ac, bc) in pred_cols:
                    Lr.append(pm.attr[:, a, ac])
                    Rr.append(attr_b[:, bc])
                Ls = jnp.stack([x.astype(jnp.float32) for x in Lr])
                Rs = jnp.stack([x.astype(jnp.float32) for x in Rr])
                ok = kops.window_join_packed(Ls, Rs, sops, ths_const,
                                             pm.valid, R.valid,
                                             backend=cfg.backend)
                created = ok.sum().astype(jnp.int32)
            return _compact(pm, R, ok, created, cfg.m_cap)

        def process(buffers: Buffers, chunk: Chunk, plan, t0, t1,
                    born_lo, born_hi):
            if isinstance(plan, PlanOperands):
                order, strips = plan.row, plan.strips
            else:
                order = plan
                strips = build_order_strips(spec, order)
            buffers = _ingest(spec, cfg, buffers, chunk)
            pm = _leaf(spec, cfg, buffers, order[0], order[0], t0, cfg.m_cap)
            pm_total = pm.valid.sum().astype(jnp.int32)
            overflow = jnp.int32(0)
            for i in range(1, n):  # static loop over plan steps
                pm, created, ov = packed_step(
                    buffers, pm, order[i], strips.ops8[i - 1],
                    strips.lo_idx[i - 1], strips.hi_idx[i - 1], t0)
                pm_total = pm_total + created
                overflow = overflow + ov
            full, neg_rej, closure = _finalize(
                spec, cfg, buffers, pm, t0, t1, born_lo, born_hi)
            return buffers, StepResult(full, pm_total, overflow, closure,
                                       neg_rej)

        return process

    def process_chunk(self, buffers: Buffers, chunk: Chunk, plan: OrderPlan,
                      t0: float, t1: float,
                      born_lo: float = -3.0e38, born_hi: float = 3.0e38):
        order = jnp.asarray(plan.order, jnp.int32)
        return self._process(buffers, chunk, order,
                             jnp.float32(t0), jnp.float32(t1),
                             jnp.float32(born_lo), jnp.float32(born_hi))


# ---------------------------------------------------------------------------
# Tree-based engine (ZStream style)
# ---------------------------------------------------------------------------


def tree_plan_to_slots(plan: TreePlan) -> np.ndarray:
    """Convert a TreePlan into an (n-1, 2) slot-join program.

    Slots 0..n-1 are the leaves (pattern positions); slot n+s is the result
    of join step s.  The interval DP guarantees every node's left child
    covers the earlier contiguous interval, which the tree engine's single
    cross-order constraint relies on for sequence patterns.
    """
    n = plan.n
    slot_of = {}
    steps = []

    def walk(node: TreeNode) -> int:
        if node.is_leaf:
            return node.leaf
        li = walk(node.left)
        ri = walk(node.right)
        # Contiguity + ordering sanity (host-side).
        ll, rl = node.left.leaves(), node.right.leaves()
        leaves = sorted(ll + rl)
        assert leaves == list(range(leaves[0], leaves[-1] + 1)), (
            "tree engine requires contiguous-interval plans")
        assert max(ll) < min(rl), "left child must cover earlier interval"
        sid = n + len(steps)
        steps.append((li, ri))
        return sid

    walk(plan.root)
    return np.asarray(steps, np.int32)


class TreeEngine:
    """Executes tree-based plans; the slot program is a dynamic argument."""

    def __init__(self, pattern: Pattern, cfg: EngineConfig = EngineConfig()):
        self.pattern = pattern
        self.spec = make_spec(pattern)
        self.cfg = cfg
        self.process_fn = self._make_process()
        self._process = jax.jit(self.process_fn)

    def init_state(self) -> Buffers:
        return init_buffers(self.spec, self.cfg)

    def _make_process(self):
        spec, cfg = self.spec, self.cfg
        n = spec.n
        m = cfg.m_cap

        def process(buffers: Buffers, chunk: Chunk, steps, t0, t1,
                    born_lo, born_hi):
            buffers = _ingest(spec, cfg, buffers, chunk)
            # Stacked slots: leaves first, then one per join step.
            leaves = [
                _leaf(spec, cfg, buffers, p, p, t0, m) for p in range(n)
            ]
            empty = MatchSet(
                ts=jnp.zeros((m, n), jnp.float32),
                attr=jnp.zeros((m, n, spec.n_attrs), jnp.float32),
                min_ts=jnp.zeros((m,), jnp.float32),
                max_ts=jnp.zeros((m,), jnp.float32),
                valid=jnp.zeros((m,), bool),
                member=jnp.zeros((n,), bool),
            )
            slots = jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *(leaves + [empty] * (n - 1)),
            )
            # Leaf cardinalities count as materialized state (ZStream cost).
            pm_total = sum(
                l.valid.sum() for l in leaves).astype(jnp.int32)
            overflow = jnp.int32(0)
            pm = leaves[0]
            for s in range(n - 1):  # static loop; slot gathers are dynamic
                L = jax.tree.map(lambda x: x[steps[s, 0]], slots)
                R = jax.tree.map(lambda x: x[steps[s, 1]], slots)
                rows = []
                if spec.is_seq:
                    rows.append((L.max_ts, R.min_ts, _LT, 0.0))
                pm, created, ov = _join(spec, cfg, L, R, rows, m)
                pm_total = pm_total + created
                overflow = overflow + ov
                slots = jax.tree.map(
                    lambda full, new: full.at[n + s].set(new), slots, pm)
            full, neg_rej, closure = _finalize(
                spec, cfg, buffers, pm, t0, t1, born_lo, born_hi)
            return buffers, StepResult(full, pm_total, overflow, closure,
                                       neg_rej)

        return process

    def process_chunk(self, buffers: Buffers, chunk: Chunk, plan: TreePlan,
                      t0: float, t1: float,
                      born_lo: float = -3.0e38, born_hi: float = 3.0e38):
        steps = jnp.asarray(tree_plan_to_slots(plan), jnp.int32)
        return self._process(buffers, chunk, steps,
                             jnp.float32(t0), jnp.float32(t1),
                             jnp.float32(born_lo), jnp.float32(born_hi))


def _make_engine(kind: str, pattern: Pattern,
                 cfg: EngineConfig = EngineConfig()):
    if kind == "order":
        return OrderEngine(pattern, cfg)
    if kind == "tree":
        return TreeEngine(pattern, cfg)
    raise ValueError(f"unknown engine kind {kind!r}")


def make_engine(kind: str, pattern: Pattern,
                cfg: EngineConfig = EngineConfig()):
    """Deprecated: the ``repro.cep`` facade selects the plan family via
    ``cep.open(..., plan="order"|"tree"|"auto")``."""
    from .compat import warn_legacy

    warn_legacy("make_engine")
    return _make_engine(kind, pattern, cfg)


# ---------------------------------------------------------------------------
# Device-resident monitoring: process + statistics + invariants in one step
# ---------------------------------------------------------------------------


def make_monitored_process(process_fn, spec: _Spec, laplace: float = 1.0):
    """Fuse a plan-execution step with invariant monitoring (paper §3.3-§3.5).

    The returned pure function runs, inside ONE traced program:

    1. the join cascade (``process_fn`` — the plan is still data);
    2. the per-chunk statistics observation (``stats.chunk_observations``)
       and the sliding-window ring update (``stats.monitor_update``);
    3. the lowered deciding-condition evaluation
       (``invariants.eval_lowered``) over the fresh snapshot.

    It returns ``(buffers, monitor, StepResult, violated, drift, rates,
    sel)``.  Only ``violated`` (one bool) and ``drift`` (one f32) need to
    reach the host each chunk; ``rates``/``sel`` stay device-resident and
    are pulled **only** when the flag fired — this is the paper's
    low-overhead-monitoring claim realized in the data plane.  Vmapping
    over a leading partition axis gives the fleet variant.
    """
    from .invariants import eval_lowered
    from .stats import chunk_observations, monitor_snapshot, monitor_update

    def mprocess(buffers, monitor, chunk, plan, lowered, t0, t1,
                 born_lo, born_hi):
        buffers, res = process_fn(buffers, chunk, plan, t0, t1,
                                  born_lo, born_hi)
        with jax.named_scope(spans.MONITOR):
            counts, trials, hits = chunk_observations(
                chunk.type_id, chunk.attr, chunk.valid, spec.type_ids,
                {"op": spec.op_t, "a_attr": spec.a_attr_t,
                 "b_attr": spec.b_attr_t, "theta": spec.theta_t})
            monitor = monitor_update(monitor, counts, t1 - t0, trials,
                                     hits)
            rates, sel = monitor_snapshot(monitor, laplace)
        with jax.named_scope(spans.VERIFY):
            violated, drift = eval_lowered(lowered, rates, sel)
        return buffers, monitor, res, violated, drift, rates, sel

    return mprocess


class MonitoredEngine:
    """Single-stream engine with the monitored step compiled in.

    The fleet executor (`fleet.FleetEngine`) vmaps the same fused step; this
    wrapper is the K = 1 building block used by examples and tests.  Plans
    enter as rows (``plan_row``) and invariant sets as ``LoweredInvariants``
    tensors, so neither a replan nor an invariant redeployment recompiles.
    """

    def __init__(self, kind: str, pattern: Pattern,
                 cfg: EngineConfig = EngineConfig(),
                 monitor_buckets: int = 16, laplace: float = 1.0):
        from .compat import warn_legacy

        warn_legacy("MonitoredEngine")
        self.base = _make_engine(kind, pattern, cfg)
        self.kind = kind
        self.pattern = pattern
        self.cfg = cfg
        self.monitor_buckets = monitor_buckets
        self._step = jax.jit(make_monitored_process(
            self.base.process_fn, self.base.spec, laplace))

    def init_state(self) -> Buffers:
        return self.base.init_state()

    def init_monitor(self):
        from .stats import monitor_init

        return monitor_init(self.pattern.n, self.monitor_buckets)

    def plan_row(self, plan) -> np.ndarray:
        if self.kind == "order":
            return np.asarray(plan.order, np.int32)
        return tree_plan_to_slots(plan)

    def process_chunk(self, buffers, monitor, chunk, plan_row, lowered,
                      t0: float, t1: float,
                      born_lo: float = -3.0e38, born_hi: float = 3.0e38):
        lowered = jax.tree.map(jnp.asarray, lowered)
        return self._step(buffers, monitor, chunk,
                          jnp.asarray(plan_row), lowered,
                          jnp.float32(t0), jnp.float32(t1),
                          jnp.float32(born_lo), jnp.float32(born_hi))
