"""Seeded generators, key routing, the replay shift and the reference."""

import itertools
import json
import os

import numpy as np
import pytest

from cepbench import streams
from cepbench.references.seq_chain import Reference

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = {"partitions": 4, "n_types": 4, "n_attrs": 1}
SEED = 2**31 + 977  # the driver's seeds pass 32 signed bits


def mix(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as fh:
        return json.load(fh)


def same(a, b):
    return all(np.array_equal(x, y) for x, y in zip(a, b))


@pytest.mark.parametrize("name", ["traffic_replay", "stocks_live"])
def test_same_seed_same_events(name):
    a = streams.make_pool(SEED, mix(name), CONFIG, 12)
    b = streams.make_pool(SEED, mix(name), CONFIG, 12)
    c = streams.make_pool(SEED + 1, mix(name), CONFIG, 12)
    assert all(same(x, y) for x, y in zip(a.batches, b.batches))
    assert not all(same(x, y) for x, y in zip(a.batches, c.batches))


@pytest.mark.parametrize("name", ["traffic_replay", "stocks_live"])
def test_keys_route_each_event_to_its_partition(name):
    pool = streams.make_pool(SEED, mix(name), CONFIG, 6)
    k = CONFIG["partitions"]
    for s in range(pool.n_slices):
        tid, ts, attr, keys = pool.batches[s]
        assert np.all(np.diff(ts) >= 0)
        for p in range(k):
            mine = keys % k == p
            part = pool.parts[s][p]
            # Stream order inside a partition is kept.
            assert np.array_equal(ts[mine], part.ts)
            assert np.array_equal(tid[mine], part.type_id)
            assert np.array_equal(attr[mine], part.attr)
        # Keys spread over the partitions, not one key per partition.
        assert len(np.unique(keys)) > k


def test_events_lie_on_the_grid_inside_their_slice():
    m = mix("traffic_replay")
    pool = streams.make_pool(SEED, m, CONFIG, 8)
    for s in range(pool.n_slices):
        _, ts, attr, _ = pool.batches[s]
        assert np.all(ts > s * m["slice_s"])
        assert np.all(ts <= (s + 1) * m["slice_s"])
        for x in (ts, attr):
            q = x.astype(np.float64) / m["quantum"]
            assert np.array_equal(q, np.round(q))


def _traffic_inputs(m, n_slices, part, parts, seed=SEED):
    """The rates and attribute means the traffic regime emits each slice
    of one partition with."""
    seen = []
    orig = streams.emit_slice

    def spy(rng_, rates, attr_mean, *a, **kw):
        seen.append((np.array(rates), np.array(attr_mean)))
        return orig(rng_, rates, attr_mean, *a, **kw)

    streams.emit_slice = spy
    try:
        streams.traffic_partition(streams.partition_rng(seed, part), m, 4,
                                  1, n_slices, part=part, parts=parts)
    finally:
        streams.emit_slice = orig
    return seen


def test_shocks_follow_the_staggered_schedule():
    m = dict(mix("traffic_replay"), shift_period=4)
    seen = _traffic_inputs(m, 10, part=1, parts=4)
    changed = [s for s in range(1, 10)
               if not np.allclose(seen[s][0], seen[s - 1][0])]
    assert changed == [3, 7]


def test_shocks_are_drawn_from_the_base_and_do_not_add_up():
    m = dict(mix("traffic_replay"), shift_period=2)
    seen = _traffic_inputs(m, 200, part=0, parts=1)
    base_rates, base_attr = seen[0]
    mag = m["shift_magnitude"]
    one_shock = [base_rates]
    for i in range(4):
        for j in range(4):
            if i != j:
                r = base_rates.copy()
                r[i], r[j] = base_rates[j] * mag, base_rates[i] / mag
                one_shock.append(r / r.sum() * base_rates.sum())
    for rates, attr in seen:
        # One shock away from the base: two rates swapped and scaled, one
        # attribute mean moved.
        assert any(np.allclose(rates, r) for r in one_shock)
        assert np.sum(np.any(attr != base_attr, axis=1)) <= 1
        assert np.all(np.abs(attr - base_attr) < 5 * m["attr_shift_sigma"])


def test_replay_shift_moves_by_whole_periods():
    pool = streams.make_pool(SEED, mix("traffic_replay"), CONFIG, 5)
    (tid, ts, _, _), t0, t1 = streams.shifted(pool, 7)
    assert (t0, t1) == (7.0, 8.0)
    assert np.array_equal(ts, pool.batches[2][1] + 5.0)
    part = streams.partition_slice(pool, 7, 1)
    assert np.array_equal(part.ts, pool.parts[2][1].ts + 5.0)


def brute(spec, tid, ts, attr, t0, t1):
    """Enumerate every combination (test scale only)."""
    types, w, th = spec["types"], spec["window"], np.float32(spec["theta"])
    idx = [np.nonzero(tid == t)[0] for t in types]
    n = 0
    for combo in itertools.product(*idx):
        t = ts[list(combo)]
        a = attr[list(combo), 0]
        if not (t0 < t[-1] <= t1 and t[-1] - t[0] < w):
            continue
        if all(t[i] < t[i + 1] and a[i] < a[i + 1] + th
               for i in range(len(types) - 1)):
            n += 1
    return n


@pytest.mark.parametrize("carry", [True, False])
def test_reference_equals_enumeration(carry):
    spec = {"types": [0, 1, 2], "window": 2.0, "theta": -0.3}
    rng = np.random.default_rng(5)
    ref = Reference(spec, carry=carry)
    hist = []
    total = 0
    for s in range(8):
        n = 12
        tid = rng.integers(0, 3, n)
        ts = np.sort(s + rng.integers(1, 65, n) / 64.0)
        attr = np.round(rng.normal(0, 1, (n, 1)) * 64) / 64
        hist.append((tid, ts, attr))
        got = ref.process(tid, ts, attr, float(s), float(s + 1))
        keep = hist if carry else hist[-1:]
        want = brute(spec, *(np.concatenate(x) for x in zip(*keep)),
                     float(s), float(s + 1))
        assert got == want
        total += got
    assert total > 0


@pytest.mark.parametrize("name", ["traffic_replay", "stocks_live"])
def test_fixed_total_gives_every_seed_the_same_slice_sizes(name):
    m = dict(mix(name), fixed_total=True, profile_seed=7)
    a, b = (streams.make_pool(seed, m, CONFIG, 40)
            for seed in (SEED, SEED + 1))
    sizes = [a.events(s) for s in range(40)]
    assert sizes == [b.events(s) for s in range(40)]
    if name == "traffic_replay":
        want = round(m["base_rate"] * m["slice_s"]) * CONFIG["partitions"]
        assert sizes == [want] * 40


def test_profile_seed_fixes_partitions_and_drift_across_seeds():
    m = dict(mix("traffic_replay"), shift_period=2)
    assert "profile_seed" in m
    a = _traffic_inputs(m, 20, part=3, parts=4)
    b = _traffic_inputs(m, 20, part=3, parts=4, seed=SEED + 1)
    for (ra, ma), (rb, mb) in zip(a, b):
        assert np.array_equal(ra, rb) and np.array_equal(ma, mb)
    assert not np.array_equal(_traffic_inputs(m, 20, part=2, parts=4)[0][0],
                              a[0][0])
    pa = streams.make_pool(SEED, m, CONFIG, 4)
    pb = streams.make_pool(SEED + 1, m, CONFIG, 4)
    assert not all(same(x, y) for x, y in zip(pa.batches, pb.batches))
