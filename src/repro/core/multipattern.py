"""Multi-pattern data plane: Q heterogeneous rules through one compiled step.

``core.engine`` compiles ONE pattern into a fused join cascade whose plan is
data.  This module generalizes the remaining static ingredient — the pattern
itself — into data: every structural quantity the engine bakes into the
trace (type ids, predicate op/attr/theta tensors, the window, the negation
and Kleene annotations, sequence-ness) becomes a tensor with a leading
**rule axis** (``Qb``), so one traced program evaluates a whole *bucket* of
same-arity rules per dispatch.  Stacked next to the existing K-partition
axis this yields the Q×K rulebook plane:

* ``RuleOps`` — the per-rule structural tensors (host-lowered from a
  ``Pattern`` by :func:`lower_rule`, stacked by :func:`stack_rule_ops`).
  Adding / removing / editing a rule is a **row write**, never a recompile;
  only growing the bucket's rule capacity retraces (same callable, new
  shape — exactly like growing K).
* ``BucketSpec`` — the static residue that *must* stay trace-constant:
  arity ``n``, whether the bucket carries negation / Kleene post-blocks,
  the attribute width, and the negation-predicate row capacity.  Rules are
  bucketed by this spec; buckets are padded with inert rows
  (:func:`pad_rule`) whose joins are empty by construction.
* **Sub-join sharing lattice** (multi-query optimization after Kolchinsky
  & Schuster's join-query-sharing work, arXiv 1801.09413): rules whose
  plans open with the identical sub-join *chain* — same positions, types,
  window, sequence-ness and every pairwise predicate live at each step —
  are grouped per *depth* at compile time.  Depth ``d`` covers the
  ``d + 2``-position sub-join after plan step ``d + 1``; ``ShareOps.rep[d]``
  gathers the rule slot whose operands drive each depth-``d`` equivalence
  class, ``ShareOps.parent[d]`` chains each class to the depth-``d-1``
  class it extends, and ``ShareOps.expand`` fans the final-depth partial
  match sets out to every rule for the per-rule post-blocks.  Each shared
  sub-join therefore runs **once per class per step** instead of once per
  rule; the opening-prefix grouping of PR 8 is the ``d = 0`` slice of this
  lattice.  Sound because a ``MatchSet`` stores event *values*, not buffer
  indices, and the class key pins every operand of the shared steps (only
  strip rows whose right operand is the newly joined position are active
  at that step, the rest are ``PRED_NONE`` — vacuous).

Bit-identity with the single-pattern engine is a design invariant, not an
aspiration: every generalized helper below mirrors its ``core.engine``
twin row for row, with rule-varying structure entering only through
op-code strips whose inactive rows carry ``PRED_NONE`` — vacuous-true in
the join kernels — so the surviving masks, the compaction order and hence
all counters are bitwise equal to Q independent ``OrderEngine`` runs
(asserted by ``tests/test_rulebook.py`` and ``benchmarks/rulebook_bench``).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from . import spans
from .engine import (Buffers, Chunk, EngineConfig, MatchSet, PredicateStrips,
                     _compact, _row_counts, _rows_to_stacks, _validity_rows,
                     make_spec)
from .patterns import PRED_ABS_LE, PRED_GT, PRED_LT, PRED_NONE, Pattern

_LT = PRED_LT
_GT = PRED_GT
_NONE = PRED_NONE

# Kleene bound sentinel for "unbounded": large enough that min() is a no-op
# for any physical companion count, small enough to stay exact in int32.
KLEENE_UNBOUNDED = 1 << 30


# ---------------------------------------------------------------------------
# Bucket spec: the static residue of a rule set
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BucketSpec:
    """Trace-constant shape of one arity bucket.

    Everything else a pattern specifies lives in ``RuleOps`` rows.  Two
    rules land in the same bucket iff they agree on this spec (with
    ``neg_rows_cap`` an upper bound, not an exact match).  ``n_attrs`` is
    the rulebook-wide attribute width — chunks are shared across rules, so
    every rule's buffers carry the same A.
    """

    n: int                 # pattern arity (primitive positions)
    has_neg: bool          # bucket carries the negation post-block
    has_kleene: bool       # bucket carries the Kleene post-block
    n_attrs: int           # shared attribute width A
    neg_rows_cap: int = 0  # max negated-predicate rows per rule

    @property
    def rows(self) -> int:
        """Ring-buffer rows per rule (one extra for the negated type)."""
        return self.n + (1 if self.has_neg else 0)


def packed_rule_row_count(n: int) -> int:
    """Packed constraint rows per plan step, bucket-wide.

    Unlike the single-pattern engine (which emits rows only for predicate
    pairs the pattern actually has), the bucket layout reserves two rows
    for EVERY ordered position pair plus the two sequence-anchor rows —
    rules activate their subset via the int8 op strip, the rest are
    ``PRED_NONE`` (vacuous-true, exact padding in the kernels).
    """
    return 4 + n * (n - 1)


def _ordered_pairs(n: int) -> Tuple[Tuple[int, int], ...]:
    """Both orientations of every position pair, in strip-row order."""
    out = []
    for p in range(n):
        for q in range(p + 1, n):
            out.append((p, q))
            out.append((q, p))
    return tuple(out)


# ---------------------------------------------------------------------------
# RuleOps: one rule as data
# ---------------------------------------------------------------------------


class RuleOps(NamedTuple):
    """Structural tensors for one rule (stack along a leading Qb axis).

    All shapes are per-rule; ``stack_rule_ops`` prepends the rule axis.
    ``type_rows[r] == -1`` marks an inactive buffer row (padding slots
    ingest nothing, so their joins are empty).  ``has_neg``/``has_kleene``
    gate the post-blocks *per rule* so buckets fused across shape classes
    (a plain rule riding in a Kleene-capable bucket) stay bit-identical to
    their solo engines: the blocks run bucket-wide, the rule-less ones are
    masked to zero.
    """

    valid: np.ndarray        # ()  bool — False for padding slots
    window: np.ndarray       # ()  f32
    is_seq: np.ndarray       # ()  bool
    has_neg: np.ndarray      # ()  bool — rule uses the negation post-block
    has_kleene: np.ndarray   # ()  bool — rule uses the Kleene post-block
    type_rows: np.ndarray    # (rows,) i32 global type per buffer row
    op_t: np.ndarray         # (n, n) i32 predicate op codes
    a_attr: np.ndarray       # (n, n) i32
    b_attr: np.ndarray       # (n, n) i32
    theta: np.ndarray        # (n, n) f32
    ths: np.ndarray          # (C,) f32 packed per-row thresholds
    neg_pos: np.ndarray      # ()  i32 required-absence position
    neg_row_op: np.ndarray   # (Rn,) i32 negation predicate rows (padded)
    neg_row_pos: np.ndarray  # (Rn,) i32
    neg_row_ma: np.ndarray   # (Rn,) i32
    neg_row_na: np.ndarray   # (Rn,) i32
    neg_row_th: np.ndarray   # (Rn,) f32
    kleene_pos: np.ndarray   # ()  i32
    kleene_bound: np.ndarray  # () i32 (KLEENE_UNBOUNDED = no bound)


class ShareOps(NamedTuple):
    """Sub-join sharing lattice routing for one bucket.

    One entry per lattice depth ``d in [0, n - 2]``; depth ``d`` holds the
    equivalence classes of the ``d + 2``-position sub-joins after plan step
    ``d + 1``.  Classes are capacity-padded like rule slots (free classes
    compute garbage that is never fanned out); growing a depth's class
    capacity retraces the same callable, exactly like growing Qb.
    """

    rep: Tuple[jnp.ndarray, ...]     # [d]: (U_d,) i32 rule slot driving
                                     #      each depth-d class's operands
    parent: Tuple[jnp.ndarray, ...]  # [d]: (U_d,) i32 depth-(d-1) class
                                     #      each class extends (d=0: zeros)
    expand: jnp.ndarray              # (Qb,) i32 final-depth class per rule


class RuleStepResult(NamedTuple):
    """Per-rule counters for one chunk tick (each leads with Qb)."""

    full: jnp.ndarray      # i32 full matches completed this chunk
    pm: jnp.ndarray        # i32 partial matches materialized
    overflow: jnp.ndarray  # i32 candidates dropped by m_cap
    closure: jnp.ndarray   # i32 Kleene companion count
    neg: jnp.ndarray       # i32 matches vetoed by negation


def lower_rule(pattern: Pattern, bspec: BucketSpec) -> RuleOps:
    """Lower one pattern into its bucket's row layout (host numpy).

    The bucket spec is a *superset* contract, not an exact match: a rule
    without negation / Kleene may ride in a bucket that carries those
    post-blocks (cross-bucket fusion pads the spec up); the rule's
    ``has_neg``/``has_kleene`` flags mask the blocks it does not use.
    """
    spec = make_spec(pattern)
    if spec.n != bspec.n:
        raise ValueError(f"rule arity {spec.n} != bucket arity {bspec.n}")
    if spec.has_neg and not bspec.has_neg:
        raise ValueError("rule needs negation; bucket has no neg post-block")
    if (spec.kleene_pos is not None) and not bspec.has_kleene:
        raise ValueError("rule needs Kleene; bucket has no Kleene post-block")
    if spec.n_attrs > bspec.n_attrs:
        raise ValueError(
            f"rule has {spec.n_attrs} attributes; rulebook width is "
            f"{bspec.n_attrs}")
    if len(spec.neg_rows) > bspec.neg_rows_cap:
        raise ValueError(
            f"{len(spec.neg_rows)} negation predicate rows exceed the "
            f"bucket capacity {bspec.neg_rows_cap}")
    n = bspec.n
    type_rows = list(spec.type_ids)
    if bspec.has_neg:
        # A rule without negation in a neg-capable bucket gets an inert
        # extra row (-1 ingests nothing, so its veto count is always 0).
        type_rows.append(spec.negated_type if spec.has_neg else -1)
    ths = [spec.window, spec.window, 0.0, 0.0]
    for (a, b_) in _ordered_pairs(n):
        ths.append(float(spec.theta_t[a, b_]))
    rn = bspec.neg_rows_cap
    nr_op = np.zeros((rn,), np.int32)
    nr_pos = np.zeros((rn,), np.int32)
    nr_ma = np.zeros((rn,), np.int32)
    nr_na = np.zeros((rn,), np.int32)
    nr_th = np.zeros((rn,), np.float32)
    for i, (pos, op, ma, na, th) in enumerate(spec.neg_rows):
        nr_op[i], nr_pos[i], nr_ma[i], nr_na[i], nr_th[i] = (
            op, pos, ma, na, th)
    return RuleOps(
        valid=np.asarray(True),
        window=np.float32(spec.window),
        is_seq=np.asarray(bool(spec.is_seq)),
        has_neg=np.asarray(bool(spec.has_neg)),
        has_kleene=np.asarray(spec.kleene_pos is not None),
        type_rows=np.asarray(type_rows, np.int32),
        op_t=np.asarray(spec.op_t, np.int32),
        a_attr=np.asarray(spec.a_attr_t, np.int32),
        b_attr=np.asarray(spec.b_attr_t, np.int32),
        theta=np.asarray(spec.theta_t, np.float32),
        ths=np.asarray(ths, np.float32),
        neg_pos=np.int32(spec.negated_pos if spec.negated_pos is not None
                         else 0),
        neg_row_op=nr_op, neg_row_pos=nr_pos, neg_row_ma=nr_ma,
        neg_row_na=nr_na, neg_row_th=nr_th,
        kleene_pos=np.int32(spec.kleene_pos or 0),
        kleene_bound=np.int32(spec.kleene_bound
                              if spec.kleene_bound is not None
                              else KLEENE_UNBOUNDED),
    )


def pad_rule(bspec: BucketSpec) -> RuleOps:
    """An inert slot: ingests nothing, joins empty, counters masked out."""
    n, rn = bspec.n, bspec.neg_rows_cap
    return RuleOps(
        valid=np.asarray(False),
        window=np.float32(1.0),
        is_seq=np.asarray(False),
        has_neg=np.asarray(False),
        has_kleene=np.asarray(False),
        type_rows=np.full((bspec.rows,), -1, np.int32),
        op_t=np.zeros((n, n), np.int32),
        a_attr=np.zeros((n, n), np.int32),
        b_attr=np.zeros((n, n), np.int32),
        theta=np.zeros((n, n), np.float32),
        ths=np.zeros((packed_rule_row_count(n),), np.float32),
        neg_pos=np.int32(0),
        neg_row_op=np.zeros((rn,), np.int32),
        neg_row_pos=np.zeros((rn,), np.int32),
        neg_row_ma=np.zeros((rn,), np.int32),
        neg_row_na=np.zeros((rn,), np.int32),
        neg_row_th=np.zeros((rn,), np.float32),
        kleene_pos=np.int32(0),
        kleene_bound=np.int32(KLEENE_UNBOUNDED),
    )


def stack_rule_ops(rows: Sequence[RuleOps]) -> RuleOps:
    """Stack per-rule ops along the leading Qb axis (host numpy)."""
    return RuleOps(*(np.stack([np.asarray(getattr(r, f)) for r in rows])
                     for f in RuleOps._fields))


# ---------------------------------------------------------------------------
# Traced generalizations of the engine's per-pattern helpers
# ---------------------------------------------------------------------------


def build_rule_strips(bspec: BucketSpec, ops: RuleOps,
                      order) -> PredicateStrips:
    """Per-step int8 op strips for one rule's order plan (traced twin of
    ``engine.build_order_strips`` — the pattern structure enters through
    ``ops`` instead of the closed-over spec).  Rows beyond the rule's own
    predicates carry ``PRED_NONE``, so the strip layout is bucket-wide."""
    n = bspec.n
    order = jnp.asarray(order, jnp.int32)
    pos = jnp.arange(n)
    member = (pos == order[0])
    ops_steps, lo_steps, hi_steps = [], [], []
    for i in range(1, n):
        q = order[i]
        row_ops = [jnp.asarray(_LT, jnp.int8), jnp.asarray(_GT, jnp.int8)]
        lo_cand = jnp.where(member & (pos < q), pos, -1)
        p_lo = lo_cand.max()
        hi_cand = jnp.where(member & (pos > q), pos, n)
        p_hi = hi_cand.min()
        # Sequence-anchor rows are always present in the bucket layout and
        # op-gated per rule (AND rules keep them vacuous).
        row_ops.append(jnp.where(ops.is_seq & (p_lo >= 0),
                                 _LT, _NONE).astype(jnp.int8))
        row_ops.append(jnp.where(ops.is_seq & (p_hi < n),
                                 _GT, _NONE).astype(jnp.int8))
        lo = jnp.clip(p_lo, 0, n - 1).astype(jnp.int32)
        hi = jnp.clip(p_hi, 0, n - 1).astype(jnp.int32)
        for (a, b_) in _ordered_pairs(n):
            active = member[a] & (q == b_)
            row_ops.append(jnp.where(active, ops.op_t[a, b_],
                                     _NONE).astype(jnp.int8))
        ops_steps.append(jnp.stack(row_ops))
        lo_steps.append(lo)
        hi_steps.append(hi)
        member = member | (pos == q)
    return PredicateStrips(
        ops8=jnp.stack(ops_steps),
        lo_idx=jnp.stack(lo_steps),
        hi_idx=jnp.stack(hi_steps))


@jax.named_scope(spans.INGEST)
def _rule_ingest(bspec: BucketSpec, cfg: EngineConfig, buffers: Buffers,
                 chunk: Chunk, type_rows) -> Buffers:
    """Route chunk events into one rule's ring rows (``engine._ingest``
    with the row→type map as data; ``-1`` rows match nothing)."""
    bcap = cfg.b_cap
    ts, attr, valid, ptr = buffers
    for row in range(bspec.rows):  # static loop
        gid = type_rows[row]
        mask = (chunk.type_id == gid) & chunk.valid & (gid >= 0)
        k = jnp.cumsum(mask.astype(jnp.int32)) - 1
        slot = jnp.where(mask, (ptr[row] + k) % bcap, bcap)  # bcap -> drop
        ts = ts.at[row, slot].set(chunk.ts, mode="drop")
        attr = attr.at[row, slot].set(chunk.attr, mode="drop")
        valid = valid.at[row, slot].set(True, mode="drop")
        ptr = ptr.at[row].add(mask.sum().astype(jnp.int32))
    return Buffers(ts, attr, valid, ptr)


@jax.named_scope(spans.JOIN)
def _rule_leaf(bspec: BucketSpec, cfg: EngineConfig, buffers: Buffers,
               row, pos, t0, window, out_rows: int) -> MatchSet:
    """One buffer row as a single-position match set (``engine._leaf`` with
    traced row/pos/window)."""
    n, b = bspec.n, cfg.b_cap
    ts_b = buffers.ts[row]
    attr_b = buffers.attr[row]
    valid = buffers.valid[row] & (ts_b > t0 - window)
    onehot = (jnp.arange(n) == pos)
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


def _rule_step(bspec: BucketSpec, cfg: EngineConfig, buffers: Buffers,
               ops: RuleOps, pm: MatchSet, q, sops, lo, hi, t0):
    """One plan step: gather + packed kernel + compaction (the traced twin
    of ``OrderEngine``'s ``packed_step``; thresholds come from the rule's
    packed ``ths`` strip instead of trace constants)."""
    R = _rule_leaf(bspec, cfg, buffers, q, q, t0, ops.window, cfg.b_cap)
    with jax.named_scope(spans.JOIN):
        attr_b = buffers.attr[q]
        Lr = [pm.max_ts, pm.min_ts, pm.ts[:, lo], pm.ts[:, hi]]
        Rr = [R.min_ts, R.max_ts, R.min_ts, R.min_ts]
        for (a, b_) in _ordered_pairs(bspec.n):
            Lr.append(pm.attr[:, a, ops.a_attr[a, b_]])
            Rr.append(attr_b[:, ops.b_attr[a, b_]])
        Ls = jnp.stack([x.astype(jnp.float32) for x in Lr])
        Rs = jnp.stack([x.astype(jnp.float32) for x in Rr])
        ok = kops.window_join_packed(Ls, Rs, sops, ops.ths, pm.valid,
                                     R.valid, backend=cfg.backend)
        created = ok.sum().astype(jnp.int32)
    return _compact(pm, R, ok, created, cfg.m_cap)


@jax.named_scope(spans.NEGATE)
def _rule_negate(bspec: BucketSpec, cfg: EngineConfig, ops: RuleOps,
                 buffers: Buffers, pm: MatchSet, completed, t0):
    """Negation veto: drop the completed matches with a compatible event
    of the negated type (ring row ``n``) between the negated position's
    neighbours; returns the surviving mask and the vetoed count."""
    n, m, b = bspec.n, pm.valid.shape[0], cfg.b_cap
    W = ops.window
    nts = buffers.ts[n]
    nvalid = buffers.valid[n] & (nts > t0 - W)
    rows = _validity_rows(completed, nvalid, m, b)
    rows += [(pm.max_ts, nts, _LT, W), (pm.min_ts, nts, _GT, W)]
    np_ = ops.neg_pos
    rows.append((pm.ts[:, jnp.clip(np_ - 1, 0, n - 1)], nts,
                 jnp.where(np_ > 0, _LT, _NONE), 0.0))
    rows.append((pm.ts[:, jnp.clip(np_, 0, n - 1)], nts,
                 jnp.where(np_ < n, _GT, _NONE), 0.0))
    for i in range(bspec.neg_rows_cap):  # static loop, op-gated rows
        rows.append((pm.attr[:, ops.neg_row_pos[i], ops.neg_row_ma[i]],
                     buffers.attr[n][:, ops.neg_row_na[i]],
                     ops.neg_row_op[i], ops.neg_row_th[i]))
    cnt = _row_counts(cfg, rows, m, b)
    veto = (cnt > 0) & ops.has_neg  # fused buckets: gate per rule
    neg_rejected = (completed & veto).sum().astype(jnp.int32)
    return completed & ~veto, neg_rejected


@jax.named_scope(spans.KLEENE)
def _rule_kleene(bspec: BucketSpec, cfg: EngineConfig, ops: RuleOps,
                 buffers: Buffers, pm: MatchSet, completed, t0):
    """Kleene companion count of the completed matches: the compatible
    events at the Kleene position besides the match's own, capped at the
    rule's bound."""
    n, m, b = bspec.n, pm.valid.shape[0], cfg.b_cap
    W = ops.window
    kp = ops.kleene_pos
    kts = buffers.ts[kp]
    kvalid = buffers.valid[kp] & (kts > t0 - W)
    attr_k = buffers.attr[kp]
    rows = _validity_rows(completed, kvalid, m, b)
    rows += [(pm.max_ts, kts, _LT, W), (pm.min_ts, kts, _GT, W)]
    rows.append((pm.ts[:, jnp.clip(kp - 1, 0, n - 1)], kts,
                 jnp.where(ops.is_seq & (kp > 0), _LT, _NONE), 0.0))
    rows.append((pm.ts[:, jnp.clip(kp + 1, 0, n - 1)], kts,
                 jnp.where(ops.is_seq & (kp < n - 1), _GT, _NONE), 0.0))
    for o in range(n):  # static loop over partner positions
        op = jnp.where(o == kp, _NONE, ops.op_t[o, kp])
        rows.append((pm.attr[:, o, ops.a_attr[o, kp]],
                     attr_k[:, ops.b_attr[o, kp]],
                     op, ops.theta[o, kp]))
    cnt = _row_counts(cfg, rows, m, b)
    comp = jnp.minimum(jnp.maximum(cnt - 1, 0), ops.kleene_bound)
    # Non-Kleene rules in a fused bucket point kleene_pos at a real
    # row; gating (not just masking padding) is what keeps them exact.
    return jnp.where(ops.has_kleene & completed, comp,
                     0).sum().astype(jnp.int32)


@jax.named_scope(spans.FINALIZE)
def _rule_finalize(bspec: BucketSpec, cfg: EngineConfig, ops: RuleOps,
                   buffers: Buffers, pm: MatchSet, t0, t1):
    """Completion filter + negation veto + Kleene count for one rule.

    Serving semantics (no born split): the rulebook control plane deploys
    plan rows immediately — partial matches rebuild from the rings every
    chunk, so a row swap changes join *work*, never *which* matches are
    counted (same contract as ``serving.MonitoredCEPFleetServingEngine``).
    The negation / Kleene blocks are bucket-static; within a block the
    rule-varying pieces (positions, ops, thetas, the window) are traced.
    Window rows are inlined (the engine's ``_window_rows`` casts the
    window to a Python float, which a traced per-rule window cannot do).
    The two post-blocks are the scopes ``cep.negate`` and ``cep.kleene``.
    """
    completed = pm.valid & (pm.max_ts > t0) & (pm.max_ts <= t1)
    neg_rejected = jnp.int32(0)
    if bspec.has_neg:
        completed, neg_rejected = _rule_negate(bspec, cfg, ops, buffers, pm,
                                               completed, t0)
    closure = jnp.int32(0)
    if bspec.has_kleene:
        closure = _rule_kleene(bspec, cfg, ops, buffers, pm, completed, t0)
    return completed.sum().astype(jnp.int32), neg_rejected, closure


def _observe_one(bspec: BucketSpec, ops: RuleOps, chunk: Chunk):
    """Per-rule monitored observation (``stats.chunk_observations`` with
    the pair structure as data).  Pairs without a predicate contribute
    exactly 0 trials/hits, matching the engine's static skip."""
    n = bspec.n
    masks = [chunk.valid & (chunk.type_id == ops.type_rows[p])
             for p in range(n)]
    counts = jnp.stack([mk.sum().astype(jnp.float32) for mk in masks])
    trials = jnp.zeros((n, n), jnp.float32)
    hits = jnp.zeros((n, n), jnp.float32)
    for p in range(n):
        for q in range(p + 1, n):
            op = ops.op_t[p, q]
            th = ops.theta[p, q]
            a = chunk.attr[:, ops.a_attr[p, q]]
            b = chunk.attr[:, ops.b_attr[p, q]]
            lt = a[:, None] < b[None, :] + th
            gt = a[:, None] > b[None, :] - th
            ab = jnp.abs(a[:, None] - b[None, :]) <= th
            ok = jnp.where(op == _LT, lt,
                           jnp.where(op == _GT, gt, ab))
            pair_mask = masks[p][:, None] & masks[q][None, :]
            has = op != _NONE
            t_pq = jnp.where(has, counts[p] * counts[q], 0.0)
            h_pq = jnp.where(
                has, (ok & pair_mask).sum().astype(jnp.float32), 0.0)
            trials = trials.at[p, q].set(t_pq).at[q, p].set(t_pq)
            hits = hits.at[p, q].set(h_pq).at[q, p].set(h_pq)
    return counts, trials, hits


# ---------------------------------------------------------------------------
# The bucket step: ingest -> shared sub-join lattice -> per-rule post-blocks
# ---------------------------------------------------------------------------


def _make_bucket_step(bspec: BucketSpec, cfg: EngineConfig,
                      monitored: bool, laplace: float):
    """Build the per-partition bucket step (vmapped over K by the plane).

    Plain signature::

        step(state, chunk, ops, share, plans, t0, t1) -> (state, res)

    where ``state`` leads with Qb, ``ops`` is the stacked ``RuleOps``,
    ``share`` routes the sub-join sharing lattice and ``plans`` is the
    (Qb, n) order matrix.  Join work walks the lattice depth by depth —
    each depth extends its parent classes' partial-match sets by one plan
    step, once per class — and only the finalize post-blocks run per rule,
    on the final-depth sets fanned out through ``share.expand``.  The
    monitored variant threads a per-rule ``MonitorState`` and stacked
    ``LoweredInvariants`` and appends (violated, drift, rates, sel) per
    rule.
    """
    from .invariants import eval_lowered
    from .stats import monitor_snapshot, monitor_update

    n = bspec.n

    def open_one(buffers, ops, order, strips, t0):
        """Leaf + opening join — the depth-0 sub-join, once per class."""
        pm = _rule_leaf(bspec, cfg, buffers, order[0], order[0], t0,
                        ops.window, cfg.m_cap)
        total = pm.valid.sum().astype(jnp.int32)
        pm, created, ov = _rule_step(
            bspec, cfg, buffers, ops, pm, order[1], strips.ops8[0],
            strips.lo_idx[0], strips.hi_idx[0], t0)
        return pm, total + created, ov

    def extend_at(d: int):
        """Depth-d extension: one plan step on the parent class's set."""
        def extend_one(buffers, ops, order, strips, pm, total, overflow,
                       t0):
            pm, created, ov = _rule_step(
                bspec, cfg, buffers, ops, pm, order[d + 1], strips.ops8[d],
                strips.lo_idx[d], strips.hi_idx[d], t0)
            return pm, total + created, overflow + ov
        return extend_one

    def finalize_one(buffers, ops, pm, total, overflow, t0, t1):
        """Completion + negation + Kleene — always per rule."""
        full, neg_rej, closure = _rule_finalize(
            bspec, cfg, ops, buffers, pm, t0, t1)
        return RuleStepResult(full, total, overflow, closure, neg_rej)

    def _joins(state, chunk, ops, share, plans, t0, t1):
        buffers = jax.vmap(
            lambda buf, trows: _rule_ingest(bspec, cfg, buf, chunk, trows)
        )(state, ops.type_rows)
        strips = jax.vmap(
            lambda o, r: build_rule_strips(bspec, o, r))(ops, plans)
        take = lambda tree, idx: jax.tree.map(lambda x: x[idx], tree)
        # Depth 0: leaf + opening join once per depth-0 class.
        r0 = share.rep[0]
        pm, tot, ov = jax.vmap(open_one, in_axes=(0, 0, 0, 0, None))(
            take(buffers, r0), take(ops, r0), plans[r0],
            take(strips, r0), t0)
        # Interior depths: extend the parent class's set by one step, once
        # per class (static loop — depths are trace constants).
        for d in range(1, n - 1):
            rd, pd = share.rep[d], share.parent[d]
            pm, tot, ov = jax.vmap(
                extend_at(d), in_axes=(0, 0, 0, 0, 0, 0, 0, None))(
                    take(buffers, rd), take(ops, rd), plans[rd],
                    take(strips, rd), take(pm, pd), tot[pd], ov[pd], t0)
        # Fan the final-depth sets out to rules for the post-blocks.
        ex = share.expand
        res = jax.vmap(
            finalize_one, in_axes=(0, 0, 0, 0, 0, None, None))(
                buffers, ops, take(pm, ex), tot[ex], ov[ex], t0, t1)
        live = ops.valid
        res = RuleStepResult(*(jnp.where(live, x, 0) for x in res))
        return buffers, res

    if not monitored:
        def bucket_step(state, chunk, ops, share, plans, t0, t1):
            return _joins(state, chunk, ops, share, plans, t0, t1)
        return bucket_step

    def mon_one(ops, monitor, lowered, chunk, t0, t1):
        with jax.named_scope(spans.MONITOR):
            counts, trials, hits = _observe_one(bspec, ops, chunk)
            monitor = monitor_update(monitor, counts, t1 - t0, trials, hits)
            rates, sel = monitor_snapshot(monitor, laplace)
        with jax.named_scope(spans.VERIFY):
            violated, drift = eval_lowered(lowered, rates, sel)
        return monitor, violated, drift, rates, sel

    def bucket_step_monitored(state, monitor, chunk, ops, share, plans,
                              lowered, t0, t1):
        buffers, res = _joins(state, chunk, ops, share, plans, t0, t1)
        monitor, violated, drift, rates, sel = jax.vmap(
            mon_one, in_axes=(0, 0, 0, None, None, None))(
                ops, monitor, lowered, chunk, t0, t1)
        violated = violated & ops.valid
        return buffers, monitor, res, violated, drift, rates, sel

    return bucket_step_monitored


# ---------------------------------------------------------------------------
# The compiled plane: jit(vmap over K) with a trace-count probe
# ---------------------------------------------------------------------------


class _Plane:
    """One compiled bucket plane plus its retrace counter.

    ``traces`` increments each time jax (re)traces the wrapped function —
    i.e. once per distinct (K, Qb, chunk-cap) shape signature.  The
    rulebook's zero-recompile hot-add guarantee is asserted against this
    counter: adding a rule into a free slot must leave it unchanged;
    growing the bucket's capacity is the one sanctioned retrace.
    """

    def __init__(self):
        self.fn = None
        self.traces = 0


def make_rulebook_plane(bspec: BucketSpec, cfg: EngineConfig, k: int,
                        monitored: bool, laplace: float = 1.0,
                        mesh=None) -> _Plane:
    """Compile (or fetch from the process-wide trace memo) the K×Qb plane.

    The memo key deliberately excludes the rule capacity Qb: growing a
    bucket re-enters the SAME jitted callable with a new shape — one
    retrace, no new cache entry — and two rulebooks with equal config
    share all compiled code.  Meshed planes are never shared (mesh objects
    pin device orders), mirroring ``FleetEngine``.
    """
    from .fleet import _shared_trace

    key = (None if mesh is not None
           else ("rulebook", bspec, cfg, int(k), bool(monitored),
                 float(laplace)))

    def build() -> _Plane:
        plane = _Plane()
        step = _make_bucket_step(bspec, cfg, monitored, laplace)
        if monitored:
            def fleet_fn(state, monitor, chunk, ops, share, plans,
                         lowered, t0, t1):
                plane.traces += 1  # python side effect: once per (re)trace
                return jax.vmap(
                    step, in_axes=(0, 0, 0, None, None, 0, 0, None, None))(
                        state, monitor, chunk, ops, share, plans, lowered,
                        t0, t1)
        else:
            def fleet_fn(state, chunk, ops, share, plans, t0, t1):
                plane.traces += 1
                return jax.vmap(
                    step, in_axes=(0, 0, None, None, 0, None, None))(
                        state, chunk, ops, share, plans, t0, t1)
        plane.fn = jax.jit(_shard_plane(fleet_fn, mesh, monitored))
        return plane

    return _shared_trace(key, build)


def _shard_plane(fn, mesh, monitored: bool):
    """shard_map the plane over a 1-D "cep" mesh (K leads; rules/share
    replicated).  ``sharding.shard_fleet_fn`` K-leads every argument, which
    the rulebook signature violates (ops/share are fleet-wide), so the
    specs are spelled per argument here."""
    if mesh is None:
        return fn
    from jax.sharding import PartitionSpec

    from ..distributed.sharding import CEP_AXIS

    kl = PartitionSpec(CEP_AXIS)
    rep = PartitionSpec()
    if monitored:
        in_specs = (kl, kl, kl, rep, rep, kl, kl, rep, rep)
        out_specs = (kl, kl, kl, kl, kl, kl, kl)
    else:
        in_specs = (kl, kl, rep, rep, kl, rep, rep)
        out_specs = (kl, kl)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# State constructors
# ---------------------------------------------------------------------------


def init_rule_buffers(bspec: BucketSpec, cfg: EngineConfig, k: int,
                      q_cap: int) -> Buffers:
    """Stacked ring buffers for one bucket: every leaf leads with (K, Qb)."""
    t, b, a = bspec.rows, cfg.b_cap, bspec.n_attrs
    return Buffers(
        ts=jnp.zeros((k, q_cap, t, b), jnp.float32),
        attr=jnp.zeros((k, q_cap, t, b, a), jnp.float32),
        valid=jnp.zeros((k, q_cap, t, b), bool),
        ptr=jnp.zeros((k, q_cap, t), jnp.int32),
    )


def init_rule_monitor(bspec: BucketSpec, k: int, q_cap: int,
                      num_buckets: int = 16):
    """Stacked statistics rings: every leaf leads with (K, Qb)."""
    from .stats import monitor_init

    one = monitor_init(bspec.n, num_buckets)
    return jax.tree.map(
        lambda x: jnp.tile(x[None, None], (k, q_cap) + (1,) * x.ndim), one)
