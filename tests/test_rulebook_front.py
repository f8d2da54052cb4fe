"""The rulebook's served front, ``Rulebook.process``: keyed routing as the
session routes, drops over the chunk capacity, the span tree and the
``readbacks`` counter, and the named scopes of the rulebook plane.

Spans are read back from a real profiler trace on the CPU; the scopes from
the lowered plane's debug info, where XLA takes its ``op_name`` metadata.
"""

import glob
import re
from collections import Counter

import jax
import numpy as np
import pytest

from repro.cep import P, RuntimeConfig, open_rulebook
from repro.core import spans
from repro.core.fleet import route_events

K = 2
SLICES = 8
CFG = RuntimeConfig(buffer_capacity=32, match_capacity=1024,
                    chunk_capacity=32, max_invariants=8, max_terms=16)


def rules():
    """One fused n=3 bucket with both post-blocks, and an n=2 bucket."""
    return [
        P.seq(0, 1, 2).where(P.attr(0) < P.attr(1) - 0.3).within(2.0),
        P.seq(0, P.neg(3), 1, 2).within(2.0),
        P.seq(0, P.kleene(1, 2), 2).within(2.0),
        P.and_(0, 1, 2).where(P.attr(0) > P.attr(2) - 0.2).within(1.0),
        P.seq(1, 2).within(1.0),
    ]


def batches(seed=3, n=24):
    """Keyed event batches, one per 1-s slice; the type mix drifts halfway
    through, so the invariants fire."""
    rng = np.random.default_rng(seed)
    for s in range(SLICES):
        p = ([0.5, 0.3, 0.1, 0.1] if s < SLICES // 2
             else [0.1, 0.2, 0.6, 0.1])
        ts = np.sort(rng.uniform(s, s + 1, n)).astype(np.float32)
        tid = rng.choice(4, n, p=p).astype(np.int32)
        attr = rng.normal(size=(n, 1)).astype(np.float32)
        keys = rng.integers(0, 100, n)
        yield tid, ts, attr, keys, float(s), float(s + 1)


def test_process_equals_step_on_the_routed_chunk():
    served = open_rulebook(rules(), partitions=K, monitor=True, config=CFG)
    stepped = open_rulebook(rules(), partitions=K, monitor=True, config=CFG)
    total = 0
    for tid, ts, attr, keys, t0, t1 in batches():
        got = served.process(tid, ts, attr, keys, t0, t1)
        chunk, dropped = route_events(tid, ts, attr, keys, K,
                                      CFG.chunk_capacity)
        assert dropped == 0
        want = stepped.step(chunk, t0, t1)
        assert got.shape == (len(rules()), K)
        np.testing.assert_array_equal(got, want)
        total += got.sum()
    assert total > 0
    np.testing.assert_array_equal(served.match_counts, stepped.match_counts)
    tel = served.telemetry()
    assert tel.chunks == SLICES and tel.events == SLICES * 24
    assert tel.dropped == 0 and tel.overflow == 0
    assert tel.replans == stepped.telemetry().replans > 0


def test_drops_over_the_chunk_capacity_are_counted():
    cap = 4
    cfg = RuntimeConfig(buffer_capacity=16, match_capacity=128,
                        chunk_capacity=cap)
    served = open_rulebook(rules(), partitions=K, monitor=True, config=cfg)
    stepped = open_rulebook(rules(), partitions=K, monitor=True, config=cfg)
    want_dropped = 0
    for tid, ts, attr, keys, t0, t1 in batches(n=12):
        part = keys % K
        want_dropped += sum(max(0, int((part == p).sum()) - cap)
                            for p in range(K))
        chunk, _ = route_events(tid, ts, attr, keys, K, cap)
        np.testing.assert_array_equal(
            served.process(tid, ts, attr, keys, t0, t1),
            stepped.step(chunk, t0, t1))
    assert want_dropped > 0
    assert served.telemetry().dropped == want_dropped
    assert stepped.telemetry().dropped == 0


def host_spans(trace_dir):
    """``(name, start, end, args)`` of every ``cep.*`` span, by line."""
    from jax.profiler import ProfileData

    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("cep.")]
            if evs:
                lines.append(evs)
    return lines


def test_served_slice_writes_one_span_tree(tmp_path):
    rb = open_rulebook(rules(), partitions=K, monitor=True, config=CFG)
    feed = list(batches())
    rb.process(*feed[0])  # compiles outside the trace
    before = rb.telemetry()
    with jax.profiler.trace(str(tmp_path)):
        for b in feed[1:]:
            rb.process(*b)
    tel = rb.telemetry()
    lines = host_spans(tmp_path)
    assert len(lines) == 1  # one driving thread
    events = lines[0]
    names = Counter(name for name, *_ in events)
    assert names[spans.PROCESS] == names[spans.ROUTE] == SLICES - 1
    # One dispatch and one flag follow-up per bucket and slice.
    for name in (spans.STEP, spans.CONTROL):
        assert names[name] == (SLICES - 1) * rb.n_buckets, name
    processes = [e for e in events if e[0] == spans.PROCESS]
    assert sorted(e[3]["chunk"] for e in processes) == list(
        range(1, SLICES))
    for _, s0, s1, args in processes:
        inside = [e for e in events if s0 <= e[1] and e[2] <= s1
                  and e[0] != spans.PROCESS]
        assert {e[0] for e in inside} >= {spans.ROUTE, spans.STEP,
                                          spans.READBACK, spans.CONTROL}
        assert {e[3]["chunk"] for e in inside} == {args["chunk"]}
    # One replan span per violation, inside a control span, with its
    # partition and rule as arguments.
    replans = [e for e in events if e[0] == spans.REPLAN]
    controls = [e for e in events if e[0] == spans.CONTROL]
    assert len(replans) == tel.violations - before.violations > 0
    for _, s0, s1, args in replans:
        assert 0 <= args["partition"] < K
        assert args["rule"] in rb.rules
        assert any(c0 <= s0 and s1 <= c1 for _, c0, c1, _a in controls)
    assert {e[3]["rule"] for e in replans} <= set(range(len(rules())))
    # One readback span per counted read.
    assert names[spans.READBACK] == tel.readbacks - before.readbacks


def test_readbacks_count_every_blocking_read():
    rb = open_rulebook(rules(), partitions=K, monitor=True, config=CFG)
    rids = rb.rules

    def flagged_buckets(before, after):
        return len({id(rb._rules[r].bucket) for r in rids
                    if after[r] > before[r]})

    pulls = 0
    for b in batches():
        before = [rb.telemetry(rule=r).violations for r in rids]
        rb.process(*b)
        pulls += flagged_buckets(
            before, [rb.telemetry(rule=r).violations for r in rids])
    # Counters and flags of each bucket and slice, and one statistics
    # pull per bucket and slice that flagged a cell.
    assert pulls > 0
    assert rb.telemetry().readbacks == 2 * SLICES * rb.n_buckets + pulls


@pytest.fixture(scope="module")
def lowered_plane():
    """Debug-info text of the monitored fused n=3 plane of ``rules()``."""
    rb = open_rulebook(rules(), partitions=K, monitor=True, config=CFG)
    bucket, = [b for b in rb._buckets if b.bspec.n == 3]
    assert bucket.bspec.has_neg and bucket.bspec.has_kleene
    chunk, _ = route_events(np.zeros(0, np.int32), np.zeros(0, np.float32),
                            np.zeros((0, 1), np.float32),
                            np.zeros(0, np.int64), K, CFG.chunk_capacity)
    return bucket.plane.fn.lower(
        bucket.state, bucket.monitor, chunk, bucket.ops_device(),
        bucket.share_d, bucket.plans_device(), bucket.lowered.device(),
        np.float32(0.0), np.float32(1.0)).as_text(debug_info=True)


@pytest.mark.parametrize("name", [
    spans.INGEST, spans.JOIN, spans.COMPACT, spans.FINALIZE, spans.NEGATE,
    spans.KLEENE, spans.MONITOR, spans.VERIFY])
def test_rulebook_plane_names_its_parts(lowered_plane, name):
    # A scope under vmap reads ``vmap(cep.join)``, nested in another
    # scope ``.../cep.negate/...``.
    assert re.search(rf"[(/]{re.escape(name)}[)/]", lowered_plane), name
