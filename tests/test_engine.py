"""CEP engine vs the brute-force oracle (``core.ref_engine``) over all
operators and both plan families, plus chunked exactly-once counting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import (Chunk, EngineConfig, MatchSet, OrderEngine,
                               TreeEngine, _compact)
from repro.core.patterns import (
    PRED_ABS_LE, PRED_LT, Predicate, and_pattern, chain_predicates,
    kleene_pattern, neg_pattern, seq_pattern,
)
from repro.core.plans import OrderPlan, TreeNode, TreePlan
from repro.core.ref_engine import brute_force_matches


def gen_stream(rng, n_types, n_events, n_attrs=1, t_end=100.0):
    ts = np.sort(rng.uniform(0, t_end, n_events)).astype(np.float32)
    tid = rng.integers(0, n_types, n_events).astype(np.int32)
    attr = rng.normal(size=(n_events, n_attrs)).astype(np.float32)
    return tid, ts, attr


def as_chunk(tid, ts, attr):
    return Chunk(jnp.asarray(tid), jnp.asarray(ts), jnp.asarray(attr),
                 jnp.ones(len(ts), bool))


def brute_matches(pattern, tid, ts, attr, t0=-np.inf, t1=np.inf):
    return brute_force_matches(pattern, tid, ts, attr, t0, t1).full_matches


@pytest.mark.parametrize("order", [(0, 1, 2), (2, 1, 0), (1, 0, 2)])
def test_order_engine_seq_any_order(order, rng):
    pat = seq_pattern([0, 1, 2], 30.0,
                      chain_predicates([0, 1, 2], theta=0.3))
    tid, ts, attr = gen_stream(rng, 3, 60)
    eng = OrderEngine(pat, EngineConfig(b_cap=64, m_cap=512))
    st, res = eng.process_chunk(
        eng.init_state(), as_chunk(tid, ts, attr), OrderPlan(order),
        0.0, 200.0)
    assert int(res.full_matches) == brute_matches(pat, tid, ts, attr,
                                                  0.0, 200.0)


def test_order_engine_and(rng):
    pat = and_pattern([0, 1, 2], 20.0,
                      chain_predicates([0, 1, 2], theta=0.5))
    tid, ts, attr = gen_stream(rng, 3, 50)
    eng = OrderEngine(pat, EngineConfig(b_cap=64, m_cap=1024))
    st, res = eng.process_chunk(
        eng.init_state(), as_chunk(tid, ts, attr), OrderPlan((2, 0, 1)),
        0.0, 200.0)
    assert int(res.full_matches) == brute_matches(pat, tid, ts, attr,
                                                  0.0, 200.0)


def test_tree_engine_all_shapes(rng):
    pat = seq_pattern([0, 1, 2, 3], 25.0,
                      chain_predicates([0, 1, 2, 3], theta=0.2))
    tid, ts, attr = gen_stream(rng, 4, 48)
    eng = TreeEngine(pat, EngineConfig(b_cap=64, m_cap=1024))
    N = TreeNode
    trees = [
        TreePlan(N(left=N(left=N(leaf=0), right=N(leaf=1)),
                   right=N(left=N(leaf=2), right=N(leaf=3)))),
        TreePlan(N(left=N(leaf=0),
                   right=N(left=N(leaf=1),
                           right=N(left=N(leaf=2), right=N(leaf=3))))),
        TreePlan(N(left=N(left=N(left=N(leaf=0), right=N(leaf=1)),
                          right=N(leaf=2)), right=N(leaf=3))),
    ]
    want = brute_matches(pat, tid, ts, attr, 0.0, 200.0)
    for tp in trees:
        st, res = eng.process_chunk(
            eng.init_state(), as_chunk(tid, ts, attr), tp, 0.0, 200.0)
        assert int(res.full_matches) == want, str(tp)


def test_chunked_counts_each_match_once(rng):
    pat = seq_pattern([0, 1, 2], 15.0,
                      chain_predicates([0, 1, 2], theta=1.0))
    tid, ts, attr = gen_stream(rng, 3, 80)
    eng = OrderEngine(pat, EngineConfig(b_cap=128, m_cap=1024))
    st = eng.init_state()
    total = 0
    edges = [0.0, 25.0, 50.0, 75.0, 100.0]
    for t0, t1 in zip(edges[:-1], edges[1:]):
        m = (ts > t0) & (ts <= t1)
        st, res = eng.process_chunk(
            st, as_chunk(tid[m], ts[m], attr[m]), OrderPlan((2, 1, 0)),
            t0, t1)
        total += int(res.full_matches)
    assert total == brute_matches(pat, tid, ts, attr, 0.0, 100.0)


def test_negation(rng):
    pat = neg_pattern(
        [0, 1], 20.0, negated_type=2, negated_pos=1,
        predicates=(Predicate(0, 1, PRED_LT, 0, 0, 0.5),),
        negated_predicates=(Predicate(2, 0, PRED_ABS_LE, 0, 0, 2.0),))
    tid, ts, attr = gen_stream(rng, 3, 60)
    eng = OrderEngine(pat, EngineConfig(b_cap=64, m_cap=512))
    st, res = eng.process_chunk(
        eng.init_state(), as_chunk(tid, ts, attr), OrderPlan((1, 0)),
        0.0, 200.0)
    assert int(res.full_matches) == brute_matches(pat, tid, ts, attr,
                                                  0.0, 200.0)
    assert int(res.neg_rejected) > 0  # the veto actually exercised


def test_kleene_counts(rng):
    pat = kleene_pattern([0, 1, 2], 30.0, kleene_pos=1)
    tid, ts, attr = gen_stream(rng, 3, 40)
    eng = OrderEngine(pat, EngineConfig(b_cap=64, m_cap=1024))
    st, res = eng.process_chunk(
        eng.init_state(), as_chunk(tid, ts, attr), OrderPlan((0, 1, 2)),
        0.0, 200.0)
    base = brute_matches(pat, tid, ts, attr, 0.0, 200.0)
    assert int(res.full_matches) == base
    assert int(res.closure_expansions) >= 0


def test_order_tree_agree(rng):
    pat = seq_pattern([0, 1, 2, 3], 25.0,
                      chain_predicates([0, 1, 2, 3], theta=0.4))
    tid, ts, attr = gen_stream(rng, 4, 60)
    oe = OrderEngine(pat, EngineConfig(b_cap=64, m_cap=2048))
    te = TreeEngine(pat, EngineConfig(b_cap=64, m_cap=2048))
    _, r1 = oe.process_chunk(oe.init_state(), as_chunk(tid, ts, attr),
                             OrderPlan((3, 2, 1, 0)), 0.0, 200.0)
    N = TreeNode
    tp = TreePlan(N(left=N(left=N(leaf=0), right=N(leaf=1)),
                    right=N(left=N(leaf=2), right=N(leaf=3))))
    _, r2 = te.process_chunk(te.init_state(), as_chunk(tid, ts, attr),
                             tp, 0.0, 200.0)
    assert int(r1.full_matches) == int(r2.full_matches)


def test_overflow_accounting():
    # Tiny caps force overflow; count must be reported, not silently lost.
    rng = np.random.default_rng(1)
    pat = and_pattern([0, 1], 100.0)
    tid, ts, attr = gen_stream(rng, 2, 120)
    eng = OrderEngine(pat, EngineConfig(b_cap=64, m_cap=64))
    _, res = eng.process_chunk(
        eng.init_state(), as_chunk(tid, ts, attr), OrderPlan((0, 1)),
        0.0, 200.0)
    assert int(res.overflow) > 0


def test_pm_created_tracks_plan_quality(rng):
    """The join-work metric must be lower for the rate-sorted order."""
    pat = seq_pattern([0, 1, 2], 10.0)
    # heavily skewed rates: type 0 frequent, type 2 rare
    tid = rng.choice(3, size=300, p=[0.8, 0.15, 0.05]).astype(np.int32)
    ts = np.sort(rng.uniform(0, 100, 300)).astype(np.float32)
    attr = rng.normal(size=(300, 1)).astype(np.float32)
    eng = OrderEngine(pat, EngineConfig(b_cap=256, m_cap=8192))
    _, good = eng.process_chunk(
        eng.init_state(), as_chunk(tid, ts, attr), OrderPlan((2, 1, 0)),
        0.0, 200.0)
    _, bad = eng.process_chunk(
        eng.init_state(), as_chunk(tid, ts, attr), OrderPlan((0, 1, 2)),
        0.0, 200.0)
    assert int(good.full_matches) == int(bad.full_matches)
    assert int(good.pm_created) < int(bad.pm_created)


# Compaction: the rank search selects what jnp.nonzero(size=) selects.

CAP = 64
# Order steps join match sets with one block of events; tree steps join two
# match sets, whose rows span several 128-wide blocks (here padded to 3).
COMPACT_SHAPES = {"order": (32, 128), "tree": (300, 300), "blocks": (24, 256)}
FILLS = {"empty": 0, "below": CAP // 2, "at": CAP, "above": 3 * CAP,
         "full": None}


def _nonzero_compact(L, R, ok, pm_created, out_cap):
    """The compaction as ``jnp.nonzero(size=, fill_value=)`` selects it."""
    m, b = ok.shape
    flat = ok.reshape(-1)
    idx = jnp.nonzero(flat, size=out_cap, fill_value=m * b)[0]
    mi = jnp.clip(idx // b, 0, m - 1)
    bi = jnp.clip(idx % b, 0, b - 1)
    memL = L.member[None, :]
    out = MatchSet(
        ts=jnp.where(memL, L.ts[mi], R.ts[bi]),
        attr=jnp.where(memL[:, :, None], L.attr[mi], R.attr[bi]),
        min_ts=jnp.minimum(L.min_ts[mi], R.min_ts[bi]),
        max_ts=jnp.maximum(L.max_ts[mi], R.max_ts[bi]),
        valid=jnp.take(flat, idx, mode="fill", fill_value=False),
        member=L.member | R.member,
    )
    return out, jnp.maximum(0, pm_created - out_cap).astype(jnp.int32)


def _match_set(rng, rows, member):
    ts = rng.uniform(0, 10, (rows, 3)).astype(np.float32)
    return MatchSet(
        ts=jnp.asarray(ts),
        attr=jnp.asarray(rng.normal(size=(rows, 3, 2)).astype(np.float32)),
        min_ts=jnp.asarray(ts.min(axis=1)),
        max_ts=jnp.asarray(ts.max(axis=1)),
        valid=jnp.asarray(rng.random(rows) < 0.8),
        member=jnp.asarray(member))


def _mask(rng, m, b, n_true):
    ok = np.zeros(m * b, bool)
    n_true = m * b if n_true is None else n_true
    ok[rng.choice(m * b, n_true, replace=False)] = True
    return ok.reshape(m, b)


def _operands(rng, kind, fill):
    m, b = COMPACT_SHAPES[kind]
    L = _match_set(rng, m, [True, False, True])
    R = _match_set(rng, b, [False, True, False])
    ok = jnp.asarray(_mask(rng, m, b, FILLS[fill]))
    return L, R, ok, ok.sum().astype(jnp.int32)


def _assert_same(got, want):
    (out, created, overflow), (ref, ref_overflow) = got, want
    for field in MatchSet._fields:
        np.testing.assert_array_equal(getattr(out, field),
                                      getattr(ref, field), err_msg=field)
    assert int(overflow) == int(ref_overflow)


@pytest.mark.parametrize("fill", list(FILLS))
@pytest.mark.parametrize("kind", list(COMPACT_SHAPES))
def test_compact_selects_as_nonzero(kind, fill, rng):
    L, R, ok, created = _operands(rng, kind, fill)
    got = jax.jit(_compact, static_argnums=4)(L, R, ok, created, CAP)
    _assert_same(got, _nonzero_compact(L, R, ok, created, CAP))
    # The first CAP true pairs in row-major order, in order; then padding.
    out = got[0]
    b = ok.shape[1]
    pos = np.flatnonzero(np.asarray(ok))[:CAP]
    n = len(pos)
    assert np.asarray(out.valid).tolist() == [True] * n + [False] * (CAP - n)
    np.testing.assert_array_equal(out.ts[:n, 0], L.ts[pos // b, 0])
    np.testing.assert_array_equal(out.ts[:n, 1], R.ts[pos % b, 1])
    assert int(got[2]) == max(0, int(created) - CAP)


@pytest.mark.parametrize("kind", list(COMPACT_SHAPES))
def test_compact_selects_as_nonzero_under_vmap(kind, rng):
    parts = [_operands(rng, kind, fill) for fill in FILLS]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *parts)
    got = jax.jit(jax.vmap(_compact, in_axes=(0, 0, 0, 0, None)),
                  static_argnums=4)(*stacked, CAP)
    for k, part in enumerate(parts):
        _assert_same(jax.tree.map(lambda x: x[k], got),
                     _nonzero_compact(*part, CAP))
