"""Rulebook bench: one compiled data plane vs Q independent Sessions.

Five self-gates, all load-bearing for the multi-pattern story:

  1. Throughput — at Q=32 the rulebook must clear >= 2x the wall-clock
     throughput of stepping Q monitored Sessions over the same chunks.
     The win is structural: one dispatch per bucket instead of Q.
  2. Equivalence — per-rule match counts must be *bit-identical* to the
     Q independent Sessions.  This only holds with zero overflow (match
     truncation makes counts plan-dependent), so both sides assert
     overflow == 0; a capacity bump, not a tolerance, is the fix if
     this ever fires.
  3. Hot-add — adding a rule into a spare slot must not retrace any
     bucket plane (trace-count probe across the add *and* the next
     dispatch) and must land far under a cold rulebook compile.
  4. Superchunk — at Q=32 rolling config.superchunk = 8 chunks per
     scanned dispatch must clear >= 1.5x the per-chunk rulebook on the
     same stream, with per-rule counts *bit-identical* (the optimistic
     window re-run makes host syncs per-window without changing a
     single counter).
  5. Lattice — full sub-join sharing must beat opening-prefix-only
     sharing on the mixed-prefix suite: ``sharing_ratio()`` under
     ``sharing="lattice"`` strictly above ``sharing="prefix"`` (the
     4-arity families share a 3-position sub-join only the lattice
     can deduplicate).

Emits BENCH_rulebook.json for CI upload + `run.py --summary`.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

HEADER = "q,k,config,seconds,events,events_per_s,speedup"

_A = 2          # attribute width shared by every generated rule
_N_TYPES = 5
_CAP = 32       # event slots per chunk per partition


def make_rules(q: int):
    """Deterministic mixed rulebook: shared-prefix SEQ families, AND
    triples, bare pairs, plus NEG and Kleene representatives.

    The first 12 rules form four 3-member families sharing a first
    join (same leading pair + predicate), so prefix sharing is
    measurable at every Q >= 2.
    """
    from repro.cep.dsl import P

    rng = np.random.default_rng(11)
    rules = []
    for p0, p1 in ((0, 1), (2, 3), (1, 4), (3, 0)):
        th = round(float(rng.uniform(0.2, 0.6)), 3)
        for x in range(_N_TYPES):
            if x in (p0, p1):
                continue
            rules.append(P.seq(p0, p1, x)
                         .where(P.attr(0, 0) < P.attr(1, 0) + th)
                         .within(2.0).attrs(_A))
    rules.append(P.seq(0, P.neg(3), 1, 2)
                 .where(P.attr(0, 0) < P.attr(1, 0) + 0.3)
                 .within(3.0).attrs(_A))
    rules.append(P.seq(2, P.neg(0), 4, 1)
                 .where(P.attr(0, 1) < P.attr(1, 0) + 0.2)
                 .within(3.0).attrs(_A))
    rules.append(P.seq(3, P.kleene(4, 2), 1).within(2.5).attrs(_A))
    rules.append(P.seq(1, P.kleene(0, 2), 2).within(2.5).attrs(_A))
    # Two 4-arity families whose members agree on the first THREE
    # positions, types and both predicates: prefix-only sharing merges
    # just their opening pair-join, the full lattice also merges the
    # 3-position sub-join — the structural gap gate 5 measures.
    for p0, p1, p2 in ((0, 1, 2), (3, 4, 0)):
        th = round(float(rng.uniform(0.1, 0.4)), 3)
        for x in range(_N_TYPES):
            if x in (p0, p1, p2):
                continue
            rules.append(P.seq(p0, p1, p2, x)
                         .where(P.attr(0, 0) < P.attr(1, 0) + th,
                                P.attr(1, 1) < P.attr(2, 0) + th)
                         .within(3.0).attrs(_A))
    while len(rules) < q:
        kind = len(rules) % 3
        types = rng.choice(_N_TYPES, size=3, replace=False).tolist()
        th = round(float(rng.uniform(-0.2, 0.5)), 3)
        if kind == 0:
            rules.append(P.seq(*types)
                         .where(P.attr(0, 0) < P.attr(1, 1) + th)
                         .within(2.0).attrs(_A))
        elif kind == 1:
            rules.append(P.and_(*types)
                         .where(P.attr(0, 1) > P.attr(2, 0) - th)
                         .within(1.5).attrs(_A))
        else:
            rules.append(P.seq(types[0], types[1])
                         .within(1.5).attrs(_A))
    return rules[:q]


def make_chunks(n_chunks: int, k: int, seed: int = 7, cap: int = _CAP,
                lo: int = 4, hi: int = 10):
    """Pre-generated stacked (K-axis) chunks, identical for both sides.

    ``cap``/``lo``/``hi`` size the per-chunk event micro-batch: the
    defaults are the throughput suite's, the superchunk section shrinks
    them to the dispatch-bound regime."""
    import jax
    import jax.numpy as jnp

    from repro.core.engine import Chunk

    rng = np.random.default_rng(seed)
    out, events = [], 0

    def one(t0, t1):
        nonlocal events
        n = int(rng.integers(lo, hi))
        events += n
        tid = rng.integers(0, _N_TYPES, size=n).astype(np.int32)
        ts = np.sort(rng.uniform(t0, t1, size=n)).astype(np.float32)
        attr = rng.normal(size=(n, _A)).astype(np.float32)
        pad = cap - n
        return Chunk(
            type_id=jnp.asarray(np.pad(tid, (0, pad), constant_values=-1)),
            ts=jnp.asarray(np.pad(ts, (0, pad))),
            attr=jnp.asarray(np.pad(attr, ((0, pad), (0, 0)))),
            valid=jnp.asarray(np.arange(cap) < n))

    for step in range(n_chunks):
        t0, t1 = float(step), float(step + 1)
        parts = [one(t0, t1) for _ in range(k)]
        out.append((jax.tree.map(lambda *xs: jnp.stack(xs), *parts),
                    t0, t1))
    return out, events


def bench_q(q: int, k: int, n_chunks: int):
    import repro.cep as cep
    from repro.cep.config import RuntimeConfig
    from repro.cep.rulebook import open_rulebook

    # match_capacity is sized so overflow stays 0 — the equivalence
    # gate is only meaningful without truncation.
    cfg = RuntimeConfig(buffer_capacity=32, match_capacity=128,
                        estimator_buckets=8)
    rules = make_rules(q)
    chunks, events = make_chunks(n_chunks, k)

    t = time.time()
    rb = open_rulebook(rules, partitions=k, monitor=True, config=cfg,
                       spare_slots=1)
    rb.step(*chunks[0])
    cold_s = time.time() - t

    sessions = [cep.open(r, partitions=k, monitor=True, config=cfg)
                for r in rules]
    sess_counts = np.zeros((q, k), np.int64)
    for i, s in enumerate(sessions):
        sess_counts[i] += np.asarray(s.step(*chunks[0]))

    # timed region: identical chunk stream through both fronts
    t = time.time()
    for chunk, t0, t1 in chunks[1:]:
        rb.step(chunk, t0, t1)
    rb_s = time.time() - t

    t = time.time()
    for chunk, t0, t1 in chunks[1:]:
        for i, s in enumerate(sessions):
            sess_counts[i] += np.asarray(s.step(chunk, t0, t1))
    loop_s = time.time() - t

    tel = rb.telemetry()
    assert tel.overflow == 0, (
        f"rulebook overflow {tel.overflow} — counts are plan-dependent "
        "under truncation; raise match_capacity")
    for s in sessions:
        assert s.telemetry().overflow == 0, "session side overflowed"
    assert np.array_equal(rb.match_counts, sess_counts), (
        "per-rule counts diverge from Q independent Sessions:\n"
        f"{rb.match_counts}\nvs\n{sess_counts}")

    # Structural sharing comparison: building a prefix-mode rulebook is
    # pure host work (planning + layout, no dispatch), so reading its
    # sharing_ratio() costs no compile.
    prefix_ratio = open_rulebook(
        rules, partitions=k, monitor=False,
        config=RuntimeConfig(buffer_capacity=32, match_capacity=128,
                             estimator_buckets=8, sharing="prefix"),
        spare_slots=1).sharing_ratio()

    ev = events * 1  # per-partition streams are independent draws
    speedup = loop_s / max(rb_s, 1e-9)
    rows = [
        {"q": q, "k": k, "config": "rulebook", "seconds": round(rb_s, 4),
         "events": ev, "events_per_s": round(ev / max(rb_s, 1e-9), 1)},
        {"q": q, "k": k, "config": "session_loop",
         "seconds": round(loop_s, 4),
         "events": ev, "events_per_s": round(ev / max(loop_s, 1e-9), 1)},
    ]
    print(f"{q},{k},rulebook,{rb_s:.3f},{ev},{ev / max(rb_s, 1e-9):.1f},"
          f"{speedup:.2f}", flush=True)
    print(f"{q},{k},session_loop,{loop_s:.3f},{ev},"
          f"{ev / max(loop_s, 1e-9):.1f},1.00", flush=True)
    return rb, chunks, rows, {
        "q": q, "k": k, "events": ev, "rulebook_s": round(rb_s, 4),
        "session_loop_s": round(loop_s, 4), "speedup": round(speedup, 3),
        "cold_compile_s": round(cold_s, 4),
        "sharing_ratio": round(rb.sharing_ratio(), 3),
        "prefix_sharing_ratio": round(prefix_ratio, 3),
        "n_buckets": rb.n_buckets,
        "replans": tel.replans, "violations": tel.violations,
    }


def bench_superchunk(q: int, k: int, s_cap: int, warm: int, tail: int):
    """Superchunk gate: S chunks per scanned dispatch vs per-chunk
    stepping over the SAME stream and config — >= 1.5x on the timed
    tail, per-rule counts, overflow and violation flags bit-identical
    over the whole stream (warm region, flags and replans included, via
    the optimistic window re-run).

    Like the fleet bench's superchunk section this measures the
    dispatch-bound regime superchunking exists for: high-frequency
    micro-batch ticks (8-event chunks, minimal ring capacities) where
    per-chunk compute is small against the dispatch + host round-trip,
    and a statistically stable stream with the paper's §3.4 invariant
    distance d = 2 so steady-state flags are rare.  Each flag costs the
    scan a window split + prefix re-run, so a flag-dense regime (d = 0
    on a 128-cell plane flags every chunk) belongs on per-chunk
    stepping — that trade is the point of the distance knob (Fig. 5),
    not a superchunk regression.
    """
    from repro.cep.config import RuntimeConfig
    from repro.cep.rulebook import open_rulebook

    # A 128-cell plane needs more per-cell distance slack than a single
    # session for the same PLANE-level flag rate (any of K*Q cells
    # splits the window), hence d = 5 where the fleet bench uses d = 2.
    cfg_kw = dict(buffer_capacity=8, match_capacity=16,
                  estimator_buckets=32, policy_kw={"k": 1, "d": 5.0})
    rules = make_rules(q)
    chunks, _ = make_chunks(warm + tail, k, seed=9, cap=8, lo=3, hi=8)
    cs = [c for c, _, _ in chunks]
    edges = [(t0, t1) for _, t0, t1 in chunks]

    rb_pc = open_rulebook(rules, partitions=k, monitor=True,
                          config=RuntimeConfig(**cfg_kw), spare_slots=1)
    rb_sc = open_rulebook(rules, partitions=k, monitor=True,
                          config=RuntimeConfig(superchunk=s_cap, **cfg_kw),
                          spare_slots=1)
    # Pass 1 (untimed): the full cold trajectory on both sides — warm
    # region flags, replans and all.  This is the bit-identity evidence.
    for c, t0, t1 in chunks:
        rb_pc.step(c, t0, t1)
    rb_sc.step_superchunk(cs, edges)
    tel_pc, tel_sc = rb_pc.telemetry(), rb_sc.telemetry()

    def time_pc():
        rb_pc.reset()
        for c, t0, t1 in chunks[:warm]:
            rb_pc.step(c, t0, t1)
        t = time.time()
        for c, t0, t1 in chunks[warm:]:
            rb_pc.step(c, t0, t1)
        return time.time() - t

    def time_sc():
        rb_sc.reset()
        rb_sc.step_superchunk(cs[:warm], edges[:warm])
        t = time.time()
        rb_sc.step_superchunk(cs[warm:], edges[warm:])
        return time.time() - t

    # Two alternating timed replays per side over the adapted plans
    # (reset clears stream state but keeps deployments), min-time ratio:
    # each replay issues identical dispatches, so min wall time is the
    # structural cost and the rest is scheduler noise.
    pc_s = min(time_pc(), time_pc())
    sc_s = min(time_sc(), time_sc())
    # Bit-identity over the FULL stream, counters and control decisions
    # alike (overflow is deterministic truncation here, identical on
    # both sides, so it needs equality rather than zero).
    assert np.array_equal(rb_sc.match_counts, rb_pc.match_counts), (
        "superchunk counts diverge from per-chunk stepping:\n"
        f"{rb_sc.match_counts}\nvs\n{rb_pc.match_counts}")
    assert tel_sc.overflow == tel_pc.overflow, (
        f"overflow diverges: {tel_sc.overflow} vs {tel_pc.overflow}")
    assert tel_sc.violations == tel_pc.violations, (
        f"violation flags diverge: {tel_sc.violations} "
        f"vs {tel_pc.violations}")
    speedup = pc_s / max(sc_s, 1e-9)
    print(f"superchunk,s={s_cap},{sc_s:.3f}s,per_chunk,{pc_s:.3f}s,"
          f"speedup,{speedup:.2f},readbacks,{tel_sc.readbacks}vs"
          f"{tel_pc.readbacks},replans,{tel_sc.replans}", flush=True)
    return {"superchunk": s_cap, "superchunk_s": round(sc_s, 4),
            "superchunk_per_chunk_s": round(pc_s, 4),
            "superchunk_speedup": round(speedup, 3),
            "superchunk_readbacks": tel_sc.readbacks,
            "per_chunk_readbacks": tel_pc.readbacks,
            "superchunk_replans": tel_sc.replans}


def bench_hot_add(rb, chunks, cold_s: float):
    """Hot-add gate: zero retraces across add + next dispatch, and the
    wall time (including that dispatch) lands far under a cold compile."""
    from repro.cep.dsl import P

    new_rule = (P.seq(4, 2, 0)
                .where(P.attr(0, 1) < P.attr(1, 0) + 0.5)
                .within(1.5).attrs(_A))
    pre = rb.trace_count()
    chunk, t0, t1 = chunks[-1]
    t = time.time()
    rid = rb.add_rule(new_rule)
    rb.step(chunk, t0 + 1.0, t1 + 1.0)
    hot_s = time.time() - t
    retraces = rb.trace_count() - pre
    assert retraces == 0, (
        f"hot-add retraced {retraces} plane(s) — spare-slot writes must "
        "not change any traced shape")
    assert hot_s < cold_s / 5.0, (
        f"hot-add {hot_s:.3f}s is not << cold compile {cold_s:.3f}s")
    assert rid in rb.rules
    print(f"hot_add,{hot_s:.4f}s,cold_compile,{cold_s:.3f}s,"
          f"retraces,{retraces}", flush=True)
    return {"hot_add_s": round(hot_s, 4), "cold_compile_s": round(cold_s, 4),
            "retraces": retraces}


def main(argv=None, quick: bool = True) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="bounded scale (the default); explicit flag for CI")
    ap.add_argument("--json", default="BENCH_rulebook.json",
                    help="machine-readable output path ('' disables)")
    args = ap.parse_args(argv)
    if args.full:
        quick = False
    k = 4
    qs = (8, 32)
    n_chunks = 12 if quick else 30

    all_rows, summaries = [], []
    print(HEADER)
    hot = None
    for q in qs:
        rb, chunks, rows, summary = bench_q(q, k, n_chunks)
        all_rows.extend(rows)
        if q == max(qs):
            hot = bench_hot_add(rb, chunks, summary["cold_compile_s"])
            summary.update(bench_superchunk(
                q, k, 8, warm=40 if quick else 60,
                tail=120 if quick else 240))
            # The headline gate: amortizing Q rules into per-bucket
            # dispatches must at least double throughput at Q=32.
            assert summary["speedup"] >= 2.0, (
                f"rulebook speedup {summary['speedup']:.2f}x at q={q} "
                "under the 2x bar")
            assert summary["sharing_ratio"] > 1.0, (
                "shared-prefix families failed to group")
            # Absolute slack absorbs scheduler noise on shared runners
            # (the fleet bench's superchunk gate does the same); a
            # structural regression lands far outside it.
            assert (summary["superchunk_s"]
                    <= summary["superchunk_per_chunk_s"] / 1.5 + 0.2), (
                f"superchunk speedup {summary['superchunk_speedup']:.2f}x "
                f"at q={q} under the 1.5x bar")
            assert (summary["sharing_ratio"]
                    > summary["prefix_sharing_ratio"]), (
                "lattice sharing no better than opening-prefix sharing "
                "on the mixed-prefix suite: "
                f"{summary['sharing_ratio']} vs "
                f"{summary['prefix_sharing_ratio']}")
        summaries.append(summary)

    if args.json:
        payload = {
            "schema": "rulebook_bench/v1",
            "quick": quick,
            "rows": all_rows,
            "summaries": summaries,
            "hot_add": hot,
        }
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=2)
            fh.write("\n")
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
