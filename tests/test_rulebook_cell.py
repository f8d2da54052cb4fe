"""The benchmark cell ``rulebook_q32_k4.traffic_replay`` at a small size on
the CPU: its plain reference against the program's oracle
(``core/ref_engine.py``) on grid streams with ties, the configuration's
rules against the program's rulebook suite, and the harness's check of the
served rulebook against the reference, with its control.

The benchmark package sits at the checkout's root, beside ``src``.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cepbench import harness, streams  # noqa: E402
from repro.core.ref_engine import RefEngine  # noqa: E402

CELL = "rulebook_q32_k4.traffic_replay"
HERE = os.path.join(ROOT, "cepbench")
SEED = 2**31 + 1616
GRID = 1.0 / 8  # coarse, so equal times, spans and attributes are common


def _reference():
    return harness.reference(HERE, {"kind": "rulebook_mixed"})


def _build(spec):
    return harness._module(os.path.join(HERE, "patterns",
                                        "rulebook_mixed.py")).build(spec)


def _cell():
    return harness.load_cell(ROOT, CELL)


# One rule of each kind, with thresholds and windows on the grid.
KINDS = {
    "seq": {"op": "seq", "elements": [0, 1, 2], "window": 1.5,
            "where": [{"a": [0, 0], "op": "<", "b": [1, 0], "theta": 0.25},
                      {"a": [2, 1], "op": ">", "b": [1, 0],
                       "theta": -0.125}]},
    "and": {"op": "and", "elements": [1, 0, 2], "window": 1.0,
            "where": [{"a": [0, 1], "op": ">", "b": [2, 0],
                       "theta": -0.125}]},
    "neg": {"op": "seq", "elements": [0, {"neg": 3}, 1, 2], "window": 2.0,
            "where": [{"a": [0, 0], "op": "<", "b": [1, 0],
                       "theta": 0.25}]},
    "kleene": {"op": "seq", "elements": [3, {"kleene": 1, "bound": 2}, 2],
               "window": 1.5,
               "where": [{"a": [1, 0], "op": "<", "b": [2, 1],
                          "theta": 0.0}]},
}


def grid_slices(seed, n_slices=12, n=9):
    """Events of 4 types with times and attributes on a 1/8 grid: equal
    timestamps, spans of exactly a window and attribute ties all occur."""
    rng = np.random.default_rng(seed)
    for s in range(n_slices):
        tid = rng.integers(0, 4, n).astype(np.int32)
        ts = np.sort(s + rng.integers(1, 9, n) * GRID).astype(np.float32)
        attr = (rng.integers(-8, 9, (n, 2)) * GRID).astype(np.float32)
        yield tid, ts, attr, float(s), float(s + 1)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_reference_agrees_with_the_oracle(kind):
    """Full-match counts, and a Kleene rule's companion count after it."""
    spec = {"kind": "rulebook_mixed", "n_attrs": 2, "rules": [KINDS[kind]]}
    pattern = _build(spec)[0].build()
    total = np.zeros(2, np.int64)
    for seed in range(3):
        ref, oracle = _reference()(spec), RefEngine(pattern)
        for tid, ts, attr, t0, t1 in grid_slices(seed):
            res = oracle.process_chunk(tid, ts, attr, t0, t1)
            want = [res.full_matches]
            if kind == "kleene":
                want.append(res.closure_expansions)
            got = ref.process(tid, ts, attr, t0, t1)
            assert got.tolist() == want, (seed, t0)
            total[:len(want)] += want
    assert total[0] > 0
    assert (total[1] > 0) == (kind == "kleene")


def keyed_grid_slices(seed, k, n_slices=10, n=24):
    """Keyed batches of the cell's 5 types, times and attributes on the
    1/8 grid, dense enough that Kleene matches have companions."""
    rng = np.random.default_rng(seed)
    for s in range(n_slices):
        tid = rng.integers(0, 5, n).astype(np.int32)
        ts = np.sort(s + rng.integers(1, 9, n) * GRID).astype(np.float32)
        attr = (rng.integers(-8, 9, (n, 2)) * GRID).astype(np.float32)
        yield tid, ts, attr, rng.integers(0, 1000, n) % k, float(s), \
            float(s + 1)


def test_rulebook_closure_matches_the_oracle():
    """The served rulebook's Kleene companion counts on the cell's two
    Kleene rules, per slice and partition and in ``closure_expansions``,
    equal the oracle's."""
    from repro.cep import RuntimeConfig, open_rulebook

    k = 2
    spec = dict(_cell().config["pattern"])
    spec["rules"] = [r for r in spec["rules"]
                     if any(isinstance(e, dict) and "kleene" in e
                            for e in r["elements"])]
    assert len(spec["rules"]) == 2
    rules = _build(spec)
    rb = open_rulebook(rules, partitions=k, monitor=True,
                       config=RuntimeConfig(buffer_capacity=128,
                                            match_capacity=1024,
                                            chunk_capacity=32))
    oracles = [[RefEngine(r.build()) for _ in range(k)] for r in rules]
    want_total = np.zeros(2, np.int64)
    for tid, ts, attr, keys, t0, t1 in keyed_grid_slices(5, k):
        full, closure = rb.process(tid, ts, attr, keys, t0, t1,
                                   closure=True)
        for q in range(2):
            for p in range(k):
                sel = keys == p
                res = oracles[q][p].process_chunk(tid[sel], ts[sel],
                                                  attr[sel], t0, t1)
                assert full[q, p] == res.full_matches, (q, p, t0)
                assert closure[q, p] == res.closure_expansions, (q, p, t0)
                want_total[q] += res.closure_expansions
    assert (want_total > 0).all()
    tel = rb.telemetry()
    assert tel.overflow == 0 and tel.dropped == 0
    for q, rid in enumerate(rb.rules):
        assert rb.telemetry(rule=rid).closure_expansions == want_total[q]


def test_thresholds_are_on_the_grid():
    rules = _cell().config["pattern"]["rules"]
    thetas = [w["theta"] for r in rules for w in r["where"]]
    assert len(thetas) == 30
    for th in thetas:
        assert th * 1024 == int(th * 1024), th


def test_configuration_is_the_rulebook_suite():
    """The configuration writes out ``make_rules(32)`` rule by rule, each
    threshold snapped to the nearest multiple of 2**-10."""
    from benchmarks.rulebook_bench import make_rules

    spec = _cell().config["pattern"]
    got = [b.build() for b in _build(spec)]
    want = [b.build() for b in make_rules(32)]
    assert len(got) == len(want) == 32
    for g, w in zip(got, want):
        assert (g.operator, g.type_ids, g.window, g.negated_type,
                g.negated_pos, g.kleene_pos, g.kleene_bound, g.n_attrs) == (
            w.operator, w.type_ids, w.window, w.negated_type,
            w.negated_pos, w.kleene_pos, w.kleene_bound, w.n_attrs)
        assert len(g.predicates) == len(w.predicates)
        for a, b in zip(g.predicates, w.predicates):
            assert (dataclasses.replace(a, theta=0.0)
                    == dataclasses.replace(b, theta=0.0))
            assert a.theta == round(b.theta * 1024) / 1024


SLICES = 24
# The configuration's match capacity holds the largest join of a whole
# run; these first slices at K = 2 need less, and the check's
# ``overflow`` (limit 0) says whether this one holds them.
MATCH_CAPACITY = 1024


def drive(factory):
    """``SLICES`` slices of the cell's traffic at K = 2 through ``factory``'s
    system, checked by the harness against the reference."""
    cell = _cell()
    cell.config["partitions"] = 2
    cell.config["runtime"]["match_capacity"] = MATCH_CAPACITY
    pool = streams.make_pool(SEED, cell.mix, cell.config, SLICES)
    system = factory(cell.config, cell.here)
    rec = harness.Recorder(annotate=False)
    for s in range(SLICES):
        rec.process(system, pool, s)
    return harness.check(cell, pool, rec.rows, system.counters(), 0)


def test_served_rulebook_matches_the_reference():
    """Every rule's full-match count and both Kleene rules' companion
    counts, per slice and partition."""
    compared, attempted, failed = drive(harness.open_system)
    assert attempted == SLICES * 2 * (32 + 2)
    assert failed == 0
    assert all(v == {"value": 0, "limit": 0} for v in compared.values())


def test_control_is_not_correct():
    compared, _, failed = drive(harness.Control)
    assert compared["count_mismatches"]["value"] == failed > 0
