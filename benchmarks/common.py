"""Shared benchmark harness: the paper's five pattern sets, both data
regimes, all four decision policies, wall-clock throughput measurement.

Throughput methodology (EXPERIMENTS.md §Benchmarks): runs use
``adaptive_caps`` — the engine's match-set capacity is the pow2 bucket of
the deployed plan's own expected partial-match count, so *real wall time*
tracks plan quality exactly the way the paper's Java engine does (fewer
partial matches => smaller joins => faster chunks).  Decision (D) and
plan-generation (A) host time is measured and included; migration chunks
run both plans, charging deployment cost to the policy that caused it.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.core.adaptation import AdaptiveRunner, RunMetrics
from repro.core.decision import make_policy
from repro.core.engine import EngineConfig
from repro.core.patterns import (CompositePattern, Pattern, Predicate,
                                 PRED_LT, and_pattern, chain_predicates,
                                 kleene_pattern, neg_pattern, seq_pattern)
from repro.data.cep_streams import StreamConfig, make_stream

PATTERN_SETS = ["seq", "conj", "neg", "kleene", "composite"]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone.  Otherwise the cache lives at ``<repo>/.jax_cache``: a
    fixed path, since a cache whose directory moves never hits.  Returns
    the directory in use.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_pattern(set_name: str, size: int, window: float = 4.0,
                  theta: float = -0.3):
    """The paper's five pattern sets (§5.1), parametrized by size."""
    ids = list(range(size))
    preds = chain_predicates(ids, theta=theta)
    if set_name == "seq":
        return seq_pattern(ids, window, preds)
    if set_name == "conj":
        return and_pattern(ids, window, preds)
    if set_name == "neg":
        # negated event = extra type `size`, absence between pos 0 and 1.
        return neg_pattern(
            ids, window, negated_type=size, negated_pos=1,
            predicates=preds,
            negated_predicates=(Predicate(size, 0, PRED_LT, 0, 0, 0.0),))
    if set_name == "kleene":
        return kleene_pattern(ids, window, kleene_pos=size // 2,
                              predicates=preds)
    if set_name == "composite":
        # disjunction of three independent sequences of `size` events
        return CompositePattern(tuple(
            seq_pattern(list(range(b * size, (b + 1) * size)), window,
                        chain_predicates(
                            list(range(b * size, (b + 1) * size)),
                            theta=theta))
            for b in range(3)))
    raise ValueError(set_name)


def stream_types_needed(set_name: str, size: int) -> int:
    if set_name == "neg":
        return size + 1
    if set_name == "composite":
        return 3 * size
    return size


POLICIES = {
    "static": dict(),
    "unconditional": dict(),
    "threshold": dict(t=0.4),
    "invariant": dict(k=1, d=0.0),
}


@dataclasses.dataclass
class BenchResult:
    dataset: str
    algo: str
    pattern_set: str
    size: int
    policy: str
    d: float
    throughput: float          # events / s (wall)
    events: int
    matches: int
    pm_created: int
    replans: int
    deployments: int
    false_positives: int
    overhead: float            # (D+A time) / total
    wall_s: float

    def row(self) -> str:
        return (f"{self.dataset},{self.algo},{self.pattern_set},"
                f"{self.size},{self.policy},{self.d:g},"
                f"{self.throughput:.0f},{self.events},{self.matches},"
                f"{self.pm_created},{self.replans},{self.deployments},"
                f"{self.false_positives},{self.overhead:.4f},"
                f"{self.wall_s:.2f}")


HEADER = ("dataset,algo,set,size,policy,d,throughput_ev_s,events,matches,"
          "pm,replans,deploys,fp,overhead,wall_s")


def run_one(dataset: str, algo: str, set_name: str, size: int,
            policy: str, d: Optional[float] = None, n_chunks: int = 120,
            base_rate: float = 15.0, seed: int = 3,
            policy_kw: Optional[dict] = None) -> BenchResult:
    pat = build_pattern(set_name, size)
    kw = dict(POLICIES[policy])
    if policy_kw:
        kw.update(policy_kw)
    if d is not None and policy == "invariant":
        kw["d"] = d
    scfg = StreamConfig(
        n_types=stream_types_needed(set_name, size), n_attrs=1,
        n_chunks=n_chunks, chunk_cap=512, base_rate=base_rate, seed=seed,
        # ~4 regime shifts per traffic run regardless of run length
        shift_every=max(n_chunks / 4.0, 10.0))
    ecfg = EngineConfig(b_cap=128, m_cap=512)

    def make_runner(p):
        return AdaptiveRunner(
            p, planner=algo, policy=make_policy(policy, **kw),
            engine_cfg=ecfg, adaptive_caps=True, cap_bounds=(256, 8192))

    t0 = time.perf_counter()
    if isinstance(pat, CompositePattern):
        metrics = RunMetrics()
        from repro.core.adaptation import merge_metrics
        ms = []
        for bi, branch in enumerate(pat.branches):
            r = make_runner(branch)
            ms.append(r.run(make_stream(
                dataset, dataclasses.replace(scfg, seed=seed + bi))))
        metrics = merge_metrics(ms)
    else:
        runner = make_runner(pat)
        metrics = runner.run(make_stream(dataset, scfg))
    wall = time.perf_counter() - t0

    return BenchResult(
        dataset=dataset, algo=algo, pattern_set=set_name, size=size,
        policy=policy, d=kw.get("d", 0.0),
        throughput=metrics.events / max(wall, 1e-9),
        events=metrics.events, matches=metrics.full_matches,
        pm_created=metrics.pm_created, replans=metrics.replans,
        deployments=metrics.deployments,
        false_positives=metrics.false_positives,
        overhead=metrics.adaptation_overhead, wall_s=wall)
