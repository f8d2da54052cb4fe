"""The program's pattern for a ``seq_chain`` spec: SEQ over ``types`` with
an ``attr < next attr + theta`` predicate on each adjacent pair."""

from __future__ import annotations


def build(spec: dict):
    from repro.core.patterns import chain_predicates, seq_pattern

    types = [int(t) for t in spec["types"]]
    return seq_pattern(types, window=float(spec["window"]),
                       predicates=chain_predicates(
                           types, attr=int(spec.get("attr", 0)),
                           theta=float(spec["theta"])))
