"""Smoke run of the CEP data plane on one TPU: public entry points, oracle
agreement, and proof that the Pallas join kernels ran.

    python chip_smoke.py               # one chip: session + rulebook phases
    python chip_smoke.py --four-chips  # four chips: K-axis mesh vs no mesh

Phases (one chip):

* session, twice: ``cep.open`` on the quickstart pattern (SEQ of four types,
  chained predicates, window 4.0) over K = 16 traffic partitions of 512-slot
  chunks, ``monitor=True``, once with ``plan="order"`` (packed join kernel)
  and once with ``plan="tree"`` (unpacked join kernel).  Per-partition
  match counts must equal ``RefEngine``'s, and at least one invariant
  violation must have replanned a partition.
* rulebook: ``cep.open_rulebook`` over the 32-rule mixed rulebook of
  ``benchmarks/rulebook_bench.py`` on K = 4 partitions, at capacities where
  the join runs as a Pallas kernel.  Per-rule, per-partition counts must
  equal ``RefEngine``'s with zero overflow.

Each phase prints the compile-inclusive time of its first step, the wall
time of the rest (information, not a metric), the device's peak memory,
the oracle check and the number of Pallas kernels (``tpu_custom_call``) in
its compiled steps.  The last line of a passing run is one JSON object
naming the device; any failure exits non-zero without printing it.  There
is no CPU fallback: without a TPU the script fails.

Everything runs in this one process, which holds the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

K_SESSION = 16
K_RULEBOOK = 4
N_CHUNKS = 24
RULEBOOK_CHUNKS = 8


def log(msg: str) -> None:
    print(msg, flush=True)


def quickstart_pattern():
    from repro.core.patterns import chain_predicates, seq_pattern

    return seq_pattern([0, 1, 2, 3], window=4.0,
                       predicates=chain_predicates([0, 1, 2, 3],
                                                   theta=-0.3))


def quickstart_streams(k: int, seed: int):
    """One traffic stream per partition, materialized (set-up, untimed)."""
    from repro.data.cep_streams import StreamConfig, make_stream

    return [list(make_stream("traffic", StreamConfig(
        n_types=4, n_chunks=N_CHUNKS, chunk_cap=512, base_rate=15.0,
        seed=seed + p))) for p in range(k)]


def session_oracle(streams):
    """Per-partition ``RefEngine`` full-match counts."""
    from repro.cep import RefEngine

    t = time.perf_counter()
    pattern = quickstart_pattern()
    counts = [RefEngine(pattern).run(recs).full_matches for recs in streams]
    log(f"[oracle] RefEngine over {len(streams)} partitions: "
        f"{time.perf_counter() - t:.1f}s")
    return counts


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def session_step_hlo(session, streams) -> str:
    """Compiled text of the session's monitored fleet step, lowered with
    the state it ends on and its first chunk."""
    import jax.numpy as jnp

    from repro.core.engine import NEG_INF, POS_INF
    from repro.core.fleet import stack_chunks

    runner = session._runner
    fleet = runner.fleet
    chunk = stack_chunks([recs[0].chunk for recs in streams])
    kv = lambda v: jnp.full((fleet.k,), v, jnp.float32)  # noqa: E731
    return fleet._mprocess.lower(
        runner._state, runner._monitor, chunk,
        jnp.asarray(runner._cur_rows), runner._low.device(),
        kv(streams[0][0].t0), kv(streams[0][0].t1), kv(NEG_INF),
        kv(POS_INF)).compile().as_text()


def rulebook_step_hlos(rb, chunk, t0, t1):
    """Compiled text of each bucket's monitored plane step."""
    import jax.numpy as jnp

    return [b.plane.fn.lower(
        b.state, b.monitor, chunk, b.ops_device(), b.share_d,
        b.plans_device(), b.lowered.device(), jnp.float32(t0),
        jnp.float32(t1)).compile().as_text() for b in rb._buckets]


def run_session(plan: str, streams, mesh=None):
    """First chunk (compile included) then the rest, resumed."""
    from repro import cep
    from repro.cep import RuntimeConfig

    session = cep.open(
        quickstart_pattern(), partitions=len(streams), plan=plan,
        monitor=True, mesh=mesh,
        config=RuntimeConfig(buffer_capacity=128, match_capacity=2048))
    t = time.perf_counter()
    session.run([recs[:1] for recs in streams])
    first_s = time.perf_counter() - t
    t = time.perf_counter()
    session.run([recs[1:] for recs in streams], resume=True)
    rest_s = time.perf_counter() - t
    return session, first_s, rest_s


def session_phase(plan: str, streams, oracle) -> None:
    """``oracle()`` returns the per-partition ``RefEngine`` counts."""
    session, first_s, rest_s = run_session(plan, streams)
    tel = session.telemetry()
    counts = tel.per_partition_matches.tolist()
    hlo = session_step_hlo(session, streams)
    n_kernels = hlo.count("tpu_custom_call")
    log(f"[session plan={plan}] K={len(streams)} chunks={tel.chunks} "
        f"events={tel.events} matches={tel.matches} replans={tel.replans} "
        f"violations={tel.violations} overflow={tel.overflow} "
        f"escalations={tel.escalations}")
    log(f"[session plan={plan}] first_step_s={first_s:.3f} (compile "
        f"included) rest_s={rest_s:.3f} peak_bytes={peak_bytes()} "
        f"pallas_kernels_in_step={n_kernels}")
    log(f"[session plan={plan}] per_partition={counts}")
    want = oracle()
    agree = counts == want
    log(f"[session plan={plan}] oracle_agreement={agree}")
    if not agree:
        raise SystemExit(f"plan={plan}: counts {counts} != oracle {want}")
    if tel.replans < 1:
        raise SystemExit(f"plan={plan}: no invariant-triggered replan")
    if n_kernels < 1:
        raise SystemExit(f"plan={plan}: no Pallas kernel in the step")


def rulebook_phase(seed: int) -> None:
    import numpy as np

    from benchmarks.rulebook_bench import make_chunks, make_rules
    from repro import cep
    from repro.cep import RefEngine, RuntimeConfig
    from repro.cep.dsl import as_pattern

    rules = make_rules(32)
    chunks, events = make_chunks(RULEBOOK_CHUNKS, K_RULEBOOK, seed=seed)
    # 256 x 128 join tiles: above the jnp-reference cutoff of the kernels.
    rb = cep.open_rulebook(
        rules, partitions=K_RULEBOOK, monitor=True,
        config=RuntimeConfig(buffer_capacity=128, match_capacity=256,
                             estimator_buckets=8))
    t = time.perf_counter()
    rb.step(*chunks[0])
    first_s = time.perf_counter() - t
    t = time.perf_counter()
    for c in chunks[1:]:
        rb.step(*c)
    rest_s = time.perf_counter() - t
    tel = rb.telemetry()
    got = rb.match_counts

    want = np.zeros_like(got)
    for i, rule in enumerate(rules):
        pat = as_pattern(rule)
        for k in range(K_RULEBOOK):
            ref = RefEngine(pat)
            for chunk, t0, t1 in chunks:
                want[i, k] += ref.process_chunk(
                    np.asarray(chunk.type_id[k]), np.asarray(chunk.ts[k]),
                    np.asarray(chunk.attr[k]), t0, t1,
                    valid=np.asarray(chunk.valid[k])).full_matches

    kernels = [h.count("tpu_custom_call")
               for h in rulebook_step_hlos(rb, *chunks[0])]
    log(f"[rulebook] rules={len(rules)} K={K_RULEBOOK} chunks={tel.chunks} "
        f"events={events} buckets={rb.n_buckets} matches={tel.matches} "
        f"overflow={tel.overflow} replans={tel.replans}")
    log(f"[rulebook] first_step_s={first_s:.3f} (compile included) "
        f"rest_s={rest_s:.3f} peak_bytes={peak_bytes()} "
        f"pallas_kernels_per_bucket_step={kernels}")
    agree = bool(np.array_equal(got, want))
    log(f"[rulebook] per_rule_totals={got.sum(axis=1).tolist()}")
    log(f"[rulebook] oracle_agreement={agree}")
    if not agree:
        bad = np.argwhere(got != want)
        raise SystemExit(f"rulebook counts differ from the oracle at "
                         f"(rule, partition) {bad.tolist()}")
    if tel.overflow != 0:
        raise SystemExit(f"rulebook overflow {tel.overflow}")
    if min(kernels) < 1:
        raise SystemExit("a rulebook bucket step has no Pallas kernel")


def four_chip_phase(seed: int) -> None:
    """K = 16 sessions sharded over a 4-device mesh vs unsharded."""
    import jax

    streams = quickstart_streams(K_SESSION, seed)
    for plan in ("order", "tree"):
        runs = {}
        for mesh in (4, None):
            session, first_s, rest_s = run_session(plan, streams, mesh=mesh)
            tel = session.telemetry()
            runs[mesh] = tel.per_partition_matches.tolist()
            log(f"[four-chip plan={plan} mesh={mesh}] first_step_s="
                f"{first_s:.3f} rest_s={rest_s:.3f} matches={tel.matches} "
                f"replans={tel.replans} per_partition={runs[mesh]}")
            if mesh is not None:
                ts = session._runner._state.ts
                held = {s.device.id: s.data.shape[0]
                        for s in ts.addressable_shards}
                log(f"[four-chip plan={plan}] partitions per device: "
                    f"{held}")
                if len(held) != 4 or set(held.values()) != {K_SESSION // 4}:
                    raise SystemExit("state is not spread over 4 devices")
        agree = runs[4] == runs[None]
        log(f"[four-chip plan={plan}] mesh_matches_unsharded={agree}")
        if not agree:
            raise SystemExit(f"plan={plan}: sharded counts differ")
    if len(jax.devices()) != 4:
        raise SystemExit("expected 4 devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device mesh comparison")
    ap.add_argument("--seed", type=int, default=100)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (platform "
                         f"{dev.platform!r}); this run needs the chip")
    from benchmarks.common import enable_compile_cache
    from repro.kernels import ops

    cache = enable_compile_cache()
    backend = ops.get_backend()
    log(f"jax={jax.__version__} device_kind={dev.device_kind} "
        f"count={len(devices)} kernel_backend={backend} cache={cache}")
    if backend != "pallas":
        raise SystemExit(f"kernel backend is {backend!r}, not 'pallas'")

    if args.four_chips:
        if len(devices) < 4:
            raise SystemExit(f"--four-chips needs 4 devices, "
                             f"found {len(devices)}")
        four_chip_phase(args.seed)
    else:
        streams = quickstart_streams(K_SESSION, args.seed)
        # The host oracle is slow Python; a thread of this process runs it
        # while the chip compiles and steps.
        with ThreadPoolExecutor(max_workers=1) as pool:
            oracle = pool.submit(session_oracle, streams)
            for plan in ("order", "tree"):
                session_phase(plan, streams, oracle.result)
        rulebook_phase(args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
