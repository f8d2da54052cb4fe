"""The program's own trace: host spans of the served session, the
``readbacks`` counter, and the named scopes of the compiled step.

Spans are read back from a real profiler trace on the CPU; the scopes from
the lowered step's debug info, where XLA takes its ``op_name`` metadata.
"""

import glob
import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import cep
from repro.cep import P, RuntimeConfig
from repro.core import spans

K = 2
SLICES = 8
PATTERN = (P.seq(0, 1, 2)
           .where(P.attr(0) < P.attr(1) - 0.3,
                  P.attr(1) < P.attr(2) - 0.3)
           .within(2.0))
CONFIG = RuntimeConfig(buffer_capacity=16, match_capacity=64,
                       chunk_capacity=32, max_invariants=8, max_terms=16)
SLICE_SPANS = (spans.ROUTE, spans.STEP, spans.READBACK, spans.CONTROL)


def batches(seed=3, n=24):
    """Keyed event batches, one per 1-s slice; the type mix drifts halfway
    through, so the invariants fire."""
    rng = np.random.default_rng(seed)
    for s in range(SLICES):
        p = [0.6, 0.3, 0.1] if s < SLICES // 2 else [0.1, 0.3, 0.6]
        ts = np.sort(rng.uniform(s, s + 1, n)).astype(np.float32)
        tid = rng.choice(3, n, p=p).astype(np.int32)
        attr = rng.normal(size=(n, 1)).astype(np.float32)
        keys = rng.integers(0, 100, n)
        yield tid, ts, attr, keys, float(s), float(s + 1)


def served(plan="order"):
    sess = cep.open(PATTERN, partitions=K, plan=plan, monitor=True,
                    config=CONFIG)
    for b in batches():
        sess.process(*b)
    return sess


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
    sess = cep.open(PATTERN, partitions=K, plan="order", monitor=True,
                    config=CONFIG)
    feed = list(batches())
    sess.process(*feed[0])  # compiles outside the trace
    before = sess.telemetry()
    with jax.profiler.trace(str(tmp_path)):
        for b in feed[1:]:
            sess.process(*b)
    tel = sess.telemetry()
    lines = host_spans(tmp_path)
    assert len(lines) == 1  # one driving thread
    events = lines[0]
    names = Counter(name for name, *_ in events)
    for name in (spans.PROCESS, spans.ROUTE, spans.STEP, spans.CONTROL):
        assert names[name] == SLICES - 1, name
    processes = [e for e in events if e[0] == spans.PROCESS]
    assert sorted(e[3]["chunk"] for e in processes) == list(
        range(1, SLICES))
    for _, s0, s1, args in processes:
        inside = [e for e in events if s0 <= e[1] and e[2] <= s1
                  and e[0] != spans.PROCESS]
        assert {e[0] for e in inside} >= set(SLICE_SPANS)
        assert {e[3]["chunk"] for e in inside} == {args["chunk"]}
    # One replan span per flag, inside a control span, with its
    # partition as argument.
    replans = [e for e in events if e[0] == spans.REPLAN]
    controls = [e for e in events if e[0] == spans.CONTROL]
    assert len(replans) == tel.violations - before.violations > 0
    for _, s0, s1, args in replans:
        assert 0 <= args["partition"] < K
        assert any(c0 <= s0 and s1 <= c1 for _, c0, c1, _a in controls)
    # One readback span per counted read.
    assert names[spans.READBACK] == tel.readbacks - before.readbacks


@pytest.mark.parametrize("plan", ["order", "tree"])
def test_readbacks_count_every_blocking_read(plan):
    tel = served(plan).telemetry()
    assert tel.chunks == SLICES and tel.violations > 0
    assert tel.readbacks == 2 * tel.chunks + 2 * tel.violations
    assert tel.host_syncs == tel.violations


def _lowered_step(kind):
    from repro.core.adaptation import make_planner
    from repro.core.decision import InvariantPolicy
    from repro.core.engine import Chunk, EngineConfig, make_monitored_process
    from repro.core.fleet import FleetEngine, prime_invariant_policies

    pattern = PATTERN.build()
    fleet = FleetEngine(kind, pattern, K, EngineConfig(b_cap=8, m_cap=16))
    planner = make_planner("greedy" if kind == "order" else "zstream")
    plan0, low, _ = prime_invariant_policies(
        pattern, planner, [InvariantPolicy(k=1, d=0.0) for _ in range(K)],
        (None, None))
    S = jax.ShapeDtypeStruct
    cap = 16
    chunk = Chunk(type_id=S((K, cap), jnp.int32), ts=S((K, cap), jnp.float32),
                  attr=S((K, cap, pattern.n_attrs), jnp.float32),
                  valid=S((K, cap), jnp.bool_))
    kvec = S((K,), jnp.float32)
    step = jax.jit(jax.vmap(make_monitored_process(fleet.base.process_fn,
                                                   fleet.base.spec)))
    return step.lower(
        jax.eval_shape(fleet.init_state), jax.eval_shape(fleet.init_monitor),
        chunk, np.asarray(fleet.plans_to_array(plan0)), low.device(),
        kvec, kvec, kvec, kvec).as_text(debug_info=True)


@pytest.mark.parametrize("kind", ["order", "tree"])
def test_compiled_step_names_its_parts(kind):
    text = _lowered_step(kind)
    for name in (spans.INGEST, spans.JOIN, spans.COMPACT, spans.FINALIZE,
                 spans.MONITOR, spans.VERIFY):
        assert f"({name})/" in text, name


@pytest.mark.parametrize("kind", ["order", "tree"])
def test_compaction_lowers_without_a_scatter(kind):
    """The compaction selects by a gather-only rank search; a scatter under
    ``cep.compact`` (as ``jnp.nonzero(size=)`` lowers to) runs serially
    over every mask element on a TPU."""
    text = _lowered_step(kind)
    scopes = set()
    for name in re.findall(r'loc\("([^"]*/scatter[^"/]*)"', text):
        scopes.update(re.findall(r"cep\.\w+", name))
    assert spans.INGEST in scopes  # the buffers' writes are scatters
    assert spans.COMPACT not in scopes
