"""Partitioned fleet demo: K tenants, one compiled data plane, one facade.

Each tenant (stream partition) has its own statistical regime, its own
invariant monitor and its own evaluation plan; all K advance through ONE
vmapped ``process_chunk`` per tick.  The whole runtime is driven through
``repro.cep``: the pattern is built with the fluent DSL, the fleet is a
``Session`` (partitions/plan/monitoring are configuration, not classes),
and every partition's match count is cross-checked against the
brute-force oracle.

    PYTHONPATH=src python examples/fleet_demo.py
"""

import dataclasses
import sys

sys.path.insert(0, "src")

from repro import cep
from repro.cep import P, RefEngine, RuntimeConfig
from repro.data.cep_streams import StreamConfig, make_stream

K = 8
pattern = (P.seq(0, 1, 2)
           .where(P.attr(0) < P.attr(1) - 0.3,
                  P.attr(1) < P.attr(2) - 0.3)
           .within(4.0))
scfg = StreamConfig(n_types=3, n_chunks=60, chunk_cap=256,
                    base_rate=12.0, seed=17)


def tenant_streams():
    # Alternate regimes: even tenants see skewed traffic with rare shocks,
    # odd tenants see near-uniform drifting stocks.
    return [
        make_stream("traffic" if p % 2 == 0 else "stocks",
                    dataclasses.replace(scfg, seed=17 + p))
        for p in range(K)
    ]


session = cep.open(
    pattern, partitions=K, plan="order",
    config=RuntimeConfig(buffer_capacity=128, match_capacity=1024,
                         policy="invariant", policy_kw={"k": 1, "d": 0.0}))
tel = session.run(tenant_streams())

print(f"== fleet of {K} tenants, {tel.chunks} chunks, "
      f"{tel.events} events ==")
print(f"matches={tel.matches}  replans={tel.replans}  "
      f"deployments={tel.deployments}  "
      f"migrating-partition-chunks={tel.migration_partition_chunks}")

print(f"\n{'tenant':>6s} {'regime':>8s} {'matches':>8s} {'oracle':>8s}")
oracle = [RefEngine(pattern.build()).run(s).full_matches
          for s in tenant_streams()]
for p in range(K):
    got = int(tel.per_partition_matches[p])
    mark = "ok" if got == oracle[p] else "MISMATCH"
    print(f"{p:6d} {'traffic' if p % 2 == 0 else 'stocks':>8s} "
          f"{got:8d} {oracle[p]:8d}  {mark}")
assert tel.per_partition_matches.tolist() == oracle
print("\nfleet == oracle on every partition")
