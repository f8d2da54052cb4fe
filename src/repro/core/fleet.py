"""Partitioned fleet executor: K independent streams, one compiled plane.

The paper's adaptation loop (§2.2, Algorithm 1) is formulated for a single
stream.  Production traffic is *many* independent stream partitions
(tenants, symbols, sensor groups), each with its own statistical regime —
partition-parallel CEP in the spirit of Xiao & Aritsugi (2018).  Because
this engine's plans are **data, not code** (an order vector / slot
program), the whole data plane can be ``vmap``-ped over a leading
partition axis without recompilation:

* ``Buffers`` gains a leading ``K`` axis — stacked per-partition ring
  buffers;
* every partition carries its **own plan array** and its own
  ``born_lo/born_hi`` migration window, so partitions replan and migrate
  independently while sharing the single compiled ``process_chunk``;
* monitoring runs in either of two control planes: ``FleetRunner`` keeps
  statistics (``FleetEstimator``) and invariant monitors (one
  ``DecisionPolicy`` per partition) on the host, as in the single-stream
  loop; ``MonitoredFleetRunner`` keeps the statistics rings **on device**
  and verifies each partition's lowered invariant set inside the same
  jitted/vmapped step (§3.3-§3.5's low-overhead monitoring at fleet
  scale), so the host sees only a ``(K,)`` violation-flag vector and
  syncs/replans flagged partitions alone — O(violations) host work per
  chunk instead of O(K·stats).

This is the §2.2 cheap-deployment property at fleet scale: deploying a new
plan for partition ``p`` writes one row of the stacked plan matrix (and,
when device-monitored, one row of the stacked invariant tensors).

Differential guarantees: ``FleetEngine`` must return bit-identical match
counts to a Python loop of K single-partition engines and to the
brute-force oracle (``ref_engine``); the device-evaluated violation flags
must agree with the host ``InvariantPolicy`` decisions on the synced
statistics; see ``tests/test_fleet.py`` and ``tests/test_monitor.py``.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Iterable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import spans
from .decision import DecisionPolicy, InvariantPolicy
from .engine import (NEG_INF, POS_INF, Buffers, Chunk, EngineConfig,
                     OrderEngine, StepResult, TreeEngine,
                     make_monitored_process, tree_plan_to_slots)
from .invariants import LoweredInvariants, StackedLowered
from .patterns import Pattern
from .plans import OrderPlan, TreePlan
from .stats import (MonitorState, Stat, fleet_monitor_init,
                    sample_selectivities, uniform_stat)

_NEG_INF = NEG_INF
_POS_INF = POS_INF


# ---------------------------------------------------------------------------
# Chunk routing / stacking
# ---------------------------------------------------------------------------


class FleetChunk(NamedTuple):
    """A stacked chunk: every field carries a leading partition axis."""

    chunk: Chunk          # (K, cap) / (K, cap, A) fields
    t0: float
    t1: float
    dropped: int = 0      # events dropped by per-partition capacity


def stack_chunks(chunks: Sequence[Chunk]) -> Chunk:
    """Stack K equally-shaped chunks along a new leading partition axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *chunks)


def route_events(
    type_id: np.ndarray,
    ts: np.ndarray,
    attr: np.ndarray,
    keys: np.ndarray,
    k: int,
    cap: int,
) -> Tuple[Chunk, int]:
    """Scatter one keyed event stream into K per-partition padded chunks.

    ``keys`` are arbitrary integer routing keys (tenant/symbol ids); events
    land in partition ``key % k``.  Per-partition overflow beyond ``cap``
    is dropped and counted (the serving layer surfaces it as back-pressure).
    Events within a partition keep their stream order.
    """
    n_attrs = attr.shape[1]
    out_tid = np.full((k, cap), -1, np.int32)
    out_ts = np.zeros((k, cap), np.float32)
    out_attr = np.zeros((k, cap, n_attrs), np.float32)
    out_valid = np.zeros((k, cap), bool)
    part = np.asarray(keys) % k
    dropped = 0
    for p in range(k):
        idx = np.nonzero(part == p)[0]
        m = len(idx)
        if m > cap:
            dropped += m - cap
            idx = idx[:cap]
            m = cap
        out_tid[p, :m] = type_id[idx]
        out_ts[p, :m] = ts[idx]
        out_attr[p, :m] = attr[idx]
        out_valid[p, :m] = True
    chunk = Chunk(jnp.asarray(out_tid), jnp.asarray(out_ts),
                  jnp.asarray(out_attr), jnp.asarray(out_valid))
    return chunk, dropped


def stacked_streams(streams: Sequence[Iterable]) -> Iterable[FleetChunk]:
    """Zip K ``ChunkRecord`` streams (shared chunk clock) into FleetChunks.

    All streams must tick with the same ``(t0, t1]`` edges (true for
    ``data.cep_streams`` generators built from one ``StreamConfig``).
    """
    for recs in zip(*streams):
        t0s = {r.t0 for r in recs}
        t1s = {r.t1 for r in recs}
        if len(t0s) != 1 or len(t1s) != 1:
            raise ValueError("partition streams disagree on chunk edges")
        yield FleetChunk(stack_chunks([r.chunk for r in recs]),
                         recs[0].t0, recs[0].t1)


# ---------------------------------------------------------------------------
# Fleet engine (vmapped data plane)
# ---------------------------------------------------------------------------

# Process-wide trace memo.  FleetEngine instances are cheap and plentiful —
# escalation ladders, replays, and benchmarks build one per (capacity,
# monitored) rung — but instances with equal (kind, pattern, k, cfg,
# monitor_laplace) lower to identical programs, and jax's trace/compile
# cache hangs off the *callable*, so per-instance ``jax.jit`` pays the
# multi-second trace again for every rung.  Sharing the jitted callable
# shares the cache.  Meshed engines are excluded: mesh objects are not
# value-hashable and shard_map closures pin device orders.
#
# The memo is LRU-bounded: long-lived processes that churn configurations
# (capacity sweeps, many-tenant rulebooks, test suites) would otherwise
# pin every jitted program they ever built.  Eviction drops our reference
# to the callable — jax's compile cache entries die with it once callers
# let go too.
_TRACE_MEMO: "OrderedDict" = OrderedDict()
_TRACE_MEMO_CAP = 64


def _shared_trace(key, build):
    if key is None:
        return build()
    fn = _TRACE_MEMO.get(key)
    if fn is None:
        fn = _TRACE_MEMO[key] = build()
        while len(_TRACE_MEMO) > _TRACE_MEMO_CAP:
            _TRACE_MEMO.popitem(last=False)
    else:
        _TRACE_MEMO.move_to_end(key)
    return fn


def clear_trace_memo() -> None:
    """Drop every memoized jitted fleet/rulebook program.

    Existing engines keep working (they hold their own references); new
    equal-config engines re-trace once.  Useful to release compile-cache
    memory in long-lived processes, and in tests that assert tracing
    behavior from a clean slate.
    """
    _TRACE_MEMO.clear()


class FleetEngine:
    """K partitions through one ``jit(vmap(process))`` of the base engine.

    ``kind`` selects the plan family ("order" | "tree"); plans may differ
    per partition (they are stacked plan arrays), the pattern and engine
    capacities are shared — that is what makes the single compiled program
    possible.
    """

    def __init__(self, kind: str, pattern: Pattern, k: int,
                 cfg: EngineConfig = EngineConfig(),
                 monitor_laplace: float = 1.0, mesh=None):
        if kind == "order":
            self.base = OrderEngine(pattern, cfg)
        elif kind == "tree":
            self.base = TreeEngine(pattern, cfg)
        else:
            raise ValueError(f"unknown engine kind {kind!r}")
        self.kind = kind
        self.pattern = pattern
        self.cfg = cfg
        self.k = int(k)
        self.monitor_laplace = monitor_laplace
        # Optional 1-D device mesh: the K-partition axis is split over the
        # mesh's "cep" axis (see distributed.sharding).  Partitions are
        # independent, so sharding never changes semantics — D=1 meshes
        # exercise the identical code path on a single device.
        from ..distributed.sharding import resolve_cep_mesh
        self.mesh = resolve_cep_mesh(mesh, self.k)
        self._process = _shared_trace(
            self._trace_key("plain"),
            lambda: jax.jit(self._wrap(jax.vmap(self.base.process_fn))))
        self._mprocess = None  # monitored variant, compiled on first use
        self._scans = {}       # superchunk scans keyed by `monitored`

    def _trace_key(self, flavor):
        """Memo key for the process-wide trace cache; None = don't share."""
        if self.mesh is not None:
            return None
        return (self.kind, self.pattern, self.k, self.cfg,
                self.monitor_laplace, flavor)

    def _wrap(self, fn):
        """shard_map the vmapped step over the fleet mesh, if any."""
        if self.mesh is None:
            return fn
        from ..distributed.sharding import shard_fleet_fn
        return shard_fleet_fn(fn, self.mesh)

    # -- state -------------------------------------------------------------

    def init_state(self) -> Buffers:
        one = self.base.init_state()
        return jax.tree.map(
            lambda x: jnp.tile(x[None], (self.k,) + (1,) * x.ndim), one)

    def init_monitor(self, num_buckets: int = 16) -> MonitorState:
        """Stacked per-partition statistics rings, device-resident."""
        return fleet_monitor_init(self.k, self.pattern.n, num_buckets)

    # -- plan stacking -----------------------------------------------------

    def plan_row(self, plan) -> np.ndarray:
        """A single plan as its row of the stacked plan matrix."""
        if self.kind == "order":
            return np.asarray(plan.order, np.int32)
        return tree_plan_to_slots(plan)

    def plans_to_array(self, plans) -> jnp.ndarray:
        """One plan (broadcast) or a length-K sequence -> stacked array."""
        if isinstance(plans, (OrderPlan, TreePlan)):
            plans = [plans] * self.k
        if len(plans) != self.k:
            raise ValueError(f"expected {self.k} plans, got {len(plans)}")
        return jnp.asarray(np.stack([self.plan_row(p) for p in plans]))

    # -- execution ---------------------------------------------------------

    def _bcast(self, v, dtype=jnp.float32) -> jnp.ndarray:
        arr = jnp.asarray(v, dtype)
        if arr.ndim == 0:
            arr = jnp.broadcast_to(arr, (self.k,))
        return arr

    def process_chunk(self, state: Buffers, chunks: Chunk, plans,
                      t0, t1, born_lo=_NEG_INF, born_hi=_POS_INF
                      ) -> Tuple[Buffers, StepResult]:
        """One chunk tick for the whole fleet.

        ``chunks`` fields carry a leading K axis; ``t0/t1/born_*`` may be
        scalars (shared clock) or per-partition ``(K,)`` vectors.  Returns
        the stacked state and a ``StepResult`` of ``(K,)`` counters.
        """
        plan_arr = (jnp.asarray(plans)
                    if isinstance(plans, (np.ndarray, jnp.ndarray))
                    else self.plans_to_array(plans))
        return self._process(
            state, chunks, plan_arr,
            self._bcast(t0), self._bcast(t1),
            self._bcast(born_lo), self._bcast(born_hi))

    def process_chunk_monitored(self, state: Buffers, monitor: MonitorState,
                                chunks: Chunk, plans,
                                lowered: LoweredInvariants,
                                t0, t1, born_lo=_NEG_INF, born_hi=_POS_INF):
        """One fused chunk tick: joins + statistics rings + invariants.

        ``lowered`` carries a leading K axis (one ``LoweredInvariants`` row
        per partition, see ``invariants.stack_lowered``).  Returns
        ``(state, monitor, StepResult, violated (K,), drift (K,),
        rates (K, n), sel (K, n, n))``.  ``rates``/``sel`` are device
        arrays — index a single partition before ``np.asarray`` so host
        syncs stay proportional to violations, not to K.
        """
        if self._mprocess is None:
            self._mprocess = _shared_trace(
                self._trace_key("monitored"),
                lambda: jax.jit(self._wrap(jax.vmap(
                    make_monitored_process(self.base.process_fn,
                                           self.base.spec,
                                           self.monitor_laplace)))))
        plan_arr = (jnp.asarray(plans)
                    if isinstance(plans, (np.ndarray, jnp.ndarray))
                    else self.plans_to_array(plans))
        lowered = jax.tree.map(jnp.asarray, lowered)
        return self._mprocess(
            state, monitor, chunks, plan_arr, lowered,
            self._bcast(t0), self._bcast(t1),
            self._bcast(born_lo), self._bcast(born_hi))

    def superchunk_scan(self, monitored: bool):
        """The compiled S-chunks-per-dispatch scan (see ``core.scan``).

        One cached compile per (engine config, monitored) pair — like the
        per-chunk step, it is plan- and invariant-agnostic (both enter as
        data), so replans and invariant redeployments never recompile.
        """
        from .scan import make_superchunk_scan

        if monitored not in self._scans:
            self._scans[monitored] = _shared_trace(
                self._trace_key(("scan", monitored)),
                lambda: make_superchunk_scan(
                    self.base.process_fn, self.base.spec, monitored,
                    self.monitor_laplace, mesh=self.mesh,
                    plan_operands=getattr(self.base, "plan_operands", None)))
        return self._scans[monitored]


# ---------------------------------------------------------------------------
# Per-partition statistics
# ---------------------------------------------------------------------------


class FleetEstimator:
    """Vectorized per-partition sliding-window estimator.

    The single-stream ``SlidingWindowEstimator`` keeps ring arrays of shape
    ``(buckets, n)``; the fleet version prepends the partition axis so one
    numpy update serves all K partitions.  Snapshots are per-partition
    ``Stat`` views, which the planners and invariant monitors consume
    unchanged.
    """

    def __init__(self, k: int, n: int, num_buckets: int = 16,
                 laplace: float = 1.0):
        self.k, self.n = k, n
        self.num_buckets = num_buckets
        self.laplace = float(laplace)
        self._counts = np.zeros((k, num_buckets, n), np.float64)
        self._durations = np.zeros((k, num_buckets), np.float64)
        self._sel_trials = np.zeros((k, num_buckets, n, n), np.float64)
        self._sel_hits = np.zeros((k, num_buckets, n, n), np.float64)
        self._head = 0
        self._filled = 0

    def update(self, counts: np.ndarray, duration: float,
               sel_trials: Optional[np.ndarray] = None,
               sel_hits: Optional[np.ndarray] = None) -> None:
        """Push one chunk of per-partition observations ((K, n) counts)."""
        h = self._head
        self._counts[:, h] = counts
        self._durations[:, h] = max(float(duration), 1e-9)
        self._sel_trials[:, h] = 0.0 if sel_trials is None else sel_trials
        self._sel_hits[:, h] = 0.0 if sel_hits is None else sel_hits
        self._head = (h + 1) % self.num_buckets
        self._filled = min(self._filled + 1, self.num_buckets)

    def snapshot(self, p: int) -> Stat:
        total_t = self._durations[p].sum() if self._filled else 1.0
        rates = self._counts[p].sum(axis=0) / max(total_t, 1e-9)
        trials = self._sel_trials[p].sum(axis=0)
        hits = self._sel_hits[p].sum(axis=0)
        lp = self.laplace
        sel = (hits + lp) / (trials + 2.0 * lp)
        sel = np.where(trials > 0, sel, 1.0)
        return Stat(rates, sel)

    def snapshots(self) -> List[Stat]:
        return [self.snapshot(p) for p in range(self.k)]


# ---------------------------------------------------------------------------
# Fleet adaptation loop (per-partition control plane)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FleetMetrics:
    """Aggregated fleet counters plus the per-partition breakdown."""

    chunks: int = 0
    events: int = 0
    full_matches: int = 0
    pm_created: int = 0
    overflow: int = 0
    closure_expansions: int = 0
    neg_rejected: int = 0
    replans: int = 0
    deployments: int = 0
    escalations: int = 0
    migration_partition_chunks: int = 0
    violations: int = 0            # device invariant flags fired
    host_syncs: int = 0            # per-partition statistic pulls
    per_partition_matches: Optional[np.ndarray] = None
    per_partition_deployments: Optional[np.ndarray] = None
    last_drift: Optional[np.ndarray] = None  # (K,) §3.4-style margins


class FleetRunner:
    """Algorithm 1 replicated per partition over one vmapped data plane.

    Each partition owns its statistics window, its decision policy
    (invariant monitor), its current/old plan rows and its [36] migration
    split; every chunk tick runs ONE compiled fleet call (two while any
    partition is migrating — the doubled pass is the fleet-level deployment
    cost, charged only when at least one partition is mid-migration).
    """

    def __init__(
        self,
        pattern: Pattern,
        k: int,
        planner=None,
        policy_factory=None,
        engine_cfg: EngineConfig = EngineConfig(),
        estimator_buckets: int = 16,
        sel_samples: int = 64,
        laplace: float = 1.0,
        escalate_on_overflow: bool = True,
        max_escalations: int = 4,
        seed: int = 0,
        mesh=None,
    ):
        from .adaptation import make_planner
        from .compat import warn_legacy

        if type(self) is FleetRunner:
            warn_legacy("FleetRunner")
        self.pattern = pattern
        self.k = int(k)
        planner = planner or "greedy"
        self.planner_kind = planner
        self.planner = make_planner(planner)
        kind = "order" if planner == "greedy" else "tree"
        self.engine_cfg = engine_cfg
        self.laplace = float(laplace)
        self.mesh = mesh
        self.fleet = FleetEngine(kind, pattern, k, engine_cfg,
                                 monitor_laplace=laplace, mesh=mesh)
        # Overflow escalation mirrors AdaptiveRunner: a truncated join may
        # have dropped matches, so the chunk is re-evaluated with the next
        # pow2 match-set capacity (shared by the whole fleet — the stacked
        # plane has one m_cap).  Escalated engines are cached and persist.
        self.escalate_on_overflow = escalate_on_overflow
        self.max_escalations = max_escalations
        self._fleets = {engine_cfg.m_cap: self.fleet}
        self._active_fleet = self.fleet
        self.estimator = FleetEstimator(
            k, pattern.n, num_buckets=estimator_buckets, laplace=laplace)
        self.policies: List[Optional[DecisionPolicy]] = [
            policy_factory() if policy_factory else None for _ in range(k)]
        self.sel_samples = sel_samples
        self._rng = np.random.default_rng(seed)
        self._pred_tensors = pattern.pred_tensors()
        self._pos_of_type = {t: p for p, t in enumerate(pattern.type_ids)}
        # Per-partition control state.
        self.cur_plans: List[Optional[object]] = [None] * k
        self.old_plans: List[Optional[object]] = [None] * k
        self._replan_t = np.full(k, _NEG_INF, np.float64)
        self._migration_until = np.full(k, _NEG_INF, np.float64)
        self._cur_rows: Optional[np.ndarray] = None
        self._old_rows: Optional[np.ndarray] = None
        # Stream carry for run(..., resume=True): ring-buffer state (and,
        # for the monitored subclass, monitor rings + deferred flags)
        # persists across run calls so segmented replays are one
        # continuous stream.
        self._state = None

    # -- statistics --------------------------------------------------------

    def _observe(self, fc: FleetChunk) -> None:
        chunk = fc.chunk
        tid_all = np.asarray(chunk.type_id)
        attr_all = np.asarray(chunk.attr)
        valid_all = np.asarray(chunk.valid)
        n = self.pattern.n
        counts = np.zeros((self.k, n))
        trials = np.zeros((self.k, n, n))
        hits = np.zeros((self.k, n, n))
        for p in range(self.k):
            v = valid_all[p]
            tid = tid_all[p][v]
            attrs = attr_all[p][v]
            for pos, t in enumerate(self.pattern.type_ids):
                counts[p, pos] = float((tid == t).sum())
            trials[p], hits[p] = sample_selectivities(
                self._rng, tid, attrs, self._pred_tensors,
                self._pos_of_type, n, self.sel_samples)
        self.estimator.update(counts, fc.t1 - fc.t0, trials, hits)

    # -- plan bookkeeping --------------------------------------------------

    def _plan_row(self, plan) -> np.ndarray:
        return self.fleet.plan_row(plan)

    def _escalated_fleet(self) -> FleetEngine:
        cap = self._active_fleet.cfg.m_cap * 2
        if cap not in self._fleets:
            self._fleets[cap] = FleetEngine(
                self.fleet.kind, self.pattern, self.k,
                EngineConfig(b_cap=self.engine_cfg.b_cap, m_cap=cap,
                             backend=self.engine_cfg.backend),
                monitor_laplace=self.laplace, mesh=self.mesh)
        return self._fleets[cap]

    def _deploy(self, p: int, new_plan, t0: float, m: FleetMetrics) -> None:
        """Deploy with the [36] migration split: the old plan row keeps
        serving matches born before ``t0``, the new row everything after.

        Deployment also retires any capacity escalation: the blown-up
        match sets belonged to the plan era being replaced — the planner
        just chose a plan to shrink them — so the fleet drops back to its
        base match capacity.  If the new plan still overflows, the
        per-chunk recovery loop re-escalates; a pinned-plan run never
        deploys, so it keeps paying the escalated-shape join cost — that
        asymmetry *is* the adaptivity win the replay harness gates on."""
        self.old_plans[p] = self.cur_plans[p]
        self._old_rows[p] = self._cur_rows[p]
        self.cur_plans[p] = new_plan
        self._cur_rows[p] = self._plan_row(new_plan)
        self._replan_t[p] = t0
        self._migration_until[p] = t0 + self.pattern.window
        self._active_fleet = self.fleet
        m.deployments += 1
        m.per_partition_deployments[p] += 1

    def _fold_lapsed(self, t0: float) -> np.ndarray:
        """Fold partitions whose migration window lapsed back to one row;
        returns the still-migrating mask."""
        lapsed = (self._replan_t > _NEG_INF) & (t0 >= self._migration_until)
        for p in np.nonzero(lapsed)[0]:
            self.old_plans[p] = None
            self._old_rows[p] = self._cur_rows[p]
            self._replan_t[p] = _NEG_INF
        return self._replan_t > _NEG_INF

    def _replan_partition(self, p: int, stat: Stat, t0: float,
                          m: FleetMetrics) -> None:
        policy = self.policies[p]
        if self.cur_plans[p] is None:
            plan, dcs = self.planner(self.pattern, stat)
            self.cur_plans[p] = plan
            self._cur_rows[p] = self._plan_row(plan)
            self._old_rows[p] = self._cur_rows[p]
            if policy is not None:
                policy.on_replan(plan, dcs, stat)
            return
        if policy is None or not policy.decide(stat):
            return
        new_plan, dcs = self.planner(self.pattern, stat)
        m.replans += 1
        if new_plan != self.cur_plans[p]:
            self._deploy(p, new_plan, t0, m)
        policy.on_replan(self.cur_plans[p], dcs, stat)

    # -- engine passes -----------------------------------------------------

    def _counters(self, res: StepResult) -> List[np.ndarray]:
        return [np.asarray(x, np.int64)
                for x in (res.full_matches, res.pm_created, res.overflow,
                          res.closure_expansions, res.neg_rejected)]

    def _pass_b(self, state, fc, out, migrating, chunk):
        """Pass B: old plans over an empty chunk (events already ingested)
        pick up matches born before each partition's replan.  Non-migrating
        partitions have an empty born-window (born_hi = -inf) and
        contribute zero matches; their pm/overflow measure join work
        regardless of the born filter, so they are masked out to avoid
        double-charging the fleet counters."""
        if migrating.any():
            empty = chunk._replace(valid=jnp.zeros_like(chunk.valid))
            state, res_b = self._active_fleet.process_chunk(
                state, empty, jnp.asarray(self._old_rows), fc.t0, fc.t1,
                born_lo=_NEG_INF,
                born_hi=self._replan_t.astype(np.float32))
            for i, x in enumerate(self._counters(res_b)):
                out[i] += np.where(migrating, x, 0)
        return state, out

    def _plain_passes(self, state, fc, chunk, migrating):
        """Pass A (current plans ingest the chunk; completed matches are
        restricted to those born at/after each partition's replan time, no
        restriction at -inf) followed by pass B while migrating."""
        state, res = self._active_fleet.process_chunk(
            state, chunk, jnp.asarray(self._cur_rows), fc.t0, fc.t1,
            born_lo=self._replan_t.astype(np.float32), born_hi=_POS_INF)
        return self._pass_b(state, fc, self._counters(res), migrating,
                            chunk)

    # -- main loop ---------------------------------------------------------

    def run(self, fleet_stream: Iterable[FleetChunk],
            resume: bool = False) -> FleetMetrics:
        """Consume a fleet stream through the adaptive loop.

        ``resume=True`` continues the previous ``run``'s stream instead of
        starting a fresh one: ring buffers, estimator windows, deployed
        plans and escalated capacities all carry over, so running a stream
        in segments is equivalent to running it in one call (metrics are
        still per-call).
        """
        m = FleetMetrics(
            per_partition_matches=np.zeros(self.k, np.int64),
            per_partition_deployments=np.zeros(self.k, np.int64))
        state = (self._state if resume and self._state is not None
                 else self.fleet.init_state())
        if self._cur_rows is None:
            probe = self._plan_row(
                self.planner(self.pattern,
                             self.estimator.snapshot(0))[0])
            self._cur_rows = np.tile(probe, (self.k,) + (1,) * probe.ndim)
            self._old_rows = self._cur_rows.copy()
            self.cur_plans = [None] * self.k  # real plans set per partition
        # A policy-free runner is a *pinned-plan* baseline: nothing
        # consumes the statistics, so the per-chunk host Monte-Carlo
        # selectivity sampling would be pure overhead charged to a run
        # that cannot adapt — skip it once the cold plans are planted.
        adaptive = any(pol is not None for pol in self.policies)

        for fc in fleet_stream:
            with jax.profiler.TraceAnnotation(spans.CONTROL, chunk=m.chunks):
                if adaptive or any(pl is None for pl in self.cur_plans):
                    if adaptive:
                        self._observe(fc)
                    for p in range(self.k):
                        self._replan_partition(
                            p, self.estimator.snapshot(p), fc.t0, m)
                migrating = self._fold_lapsed(fc.t0)

            with jax.profiler.TraceAnnotation(spans.STEP, chunk=m.chunks):
                pre_fleet = self._active_fleet
                state, (full, pm, ov, cl, ng) = self._plain_passes(
                    state, fc, fc.chunk, migrating)
                # Overflow recovery: a truncated join may have dropped
                # matches, so re-evaluate the window at the next pow2
                # capacity (events already ingested; the recount replaces
                # the truncated one and the duplicate join work is charged
                # to pm).
                tries = 0
                while (ov.sum() > 0 and self.escalate_on_overflow
                       and tries < self.max_escalations):
                    self._active_fleet = self._escalated_fleet()
                    m.escalations += 1
                    tries += 1
                    empty = fc.chunk._replace(
                        valid=jnp.zeros_like(fc.chunk.valid))
                    pm_so_far = pm
                    state, (full, pm, ov, cl, ng) = self._plain_passes(
                        state, fc, empty, migrating)
                    pm = pm + pm_so_far
                if migrating.any():
                    # A mid-migration overflow is the retiring plan's:
                    # recount at escalated capacity, but don't let the old
                    # era's shape outlive its migration window.
                    self._active_fleet = pre_fleet
                    m.migration_partition_chunks += int(migrating.sum())

            m.chunks += 1
            m.events += int(np.asarray(fc.chunk.valid).sum())
            m.full_matches += int(full.sum())
            m.pm_created += int(pm.sum())
            m.overflow += int(ov.sum())
            m.closure_expansions += int(cl.sum())
            m.neg_rejected += int(ng.sum())
            m.per_partition_matches += full
        self._state = state
        return m


# ---------------------------------------------------------------------------
# Device-monitored fleet loop
# ---------------------------------------------------------------------------


def prime_invariant_policies(pattern: Pattern, planner, policies,
                             caps: Tuple[Optional[int], Optional[int]]):
    """Cold start shared by the monitored runner and the serving front.

    Plans once from the uniform prior, installs that plan's invariant set
    into every partition's policy, and compiles the lowered rows.  Caps
    left as ``None`` default to the cold-start set's exact sizes (stat-
    independent for the greedy planner).  Returns
    ``(plan0, StackedLowered, caps)``.
    """
    stat0 = uniform_stat(pattern.n)
    plan0, dcs0 = planner(pattern, stat0)
    lows = []
    for pol in policies:
        pol.on_replan(plan0, dcs0, stat0)
        lows.append(pol.compile(pattern.n, *caps))
    if caps[0] is None or caps[1] is None:
        caps = (lows[0].active.shape[0], lows[0].scale.shape[-1])
    return plan0, StackedLowered(lows), caps


def replan_flagged_partition(pattern: Pattern, planner, policy,
                             low: StackedLowered, p: int, stat: Stat,
                             caps) -> object:
    """Violation follow-up for one flagged partition: re-run ``A`` on the
    synced statistics, rebase the policy on the fresh DCSs, and redeploy
    the partition's lowered invariant row.  Returns the new plan (the
    caller decides how to deploy it — migration split vs immediate swap).
    """
    new_plan, dcs = planner(pattern, stat)
    policy.on_replan(new_plan, dcs, stat)
    low.write_row(p, policy.compile(pattern.n, *caps))
    return new_plan


class MonitoredFleetRunner(FleetRunner):
    """FleetRunner with §3 invariant verification fused into the data plane.

    The host ``FleetRunner`` evaluates every partition's ``DecisionPolicy``
    in Python each chunk, which requires a device→host sync of the full
    statistics windows for all K partitions.  This runner instead:

    * keeps the statistics rings **on device** (``FleetEngine.init_monitor``
      — exhaustive, RNG-free selectivity observation, see
      ``stats.chunk_observations``);
    * lowers each partition's invariant set into stacked
      ``LoweredInvariants`` tensors (``InvariantPolicy.compile``), so the
      deciding conditions are verified inside the same jitted/vmapped step
      that joins the chunk;
    * pulls only the ``(K,)`` violation-flag vector (plus drift telemetry)
      per chunk and syncs a partition's ``(rates, sel)`` snapshot **only
      when its flag fired** — per-chunk host work is O(violations), not
      O(K·stats).

    Violation-flag contract: flags computed over chunk ``c`` trigger a
    replan that deploys at chunk ``c+1``'s ``t0`` (a *deferred* replan).
    Exactly-once detection is unaffected: deployment still uses the [36]
    born-time migration split at the deployment chunk's ``t0``, and plan
    choice never changes *which* matches exist, only the join work to find
    them.  A deployment remains a plan-matrix row write plus an
    invariant-matrix row write — never a recompile.

    ``max_inv`` / ``max_terms`` fix the stacked invariant tensor shape.
    They default to the sizes of the cold-start (uniform-prior) invariant
    set, which is exact for the greedy planner (its DCS structure is
    stat-independent); for tree planners pass explicit worst-case caps —
    an overflowing replan raises rather than silently truncating.
    """

    def __init__(self, pattern: Pattern, k: int, planner=None,
                 policy_factory=None,
                 engine_cfg: EngineConfig = EngineConfig(),
                 estimator_buckets: int = 16,
                 max_inv: Optional[int] = None,
                 max_terms: Optional[int] = None,
                 laplace: float = 1.0,
                 escalate_on_overflow: bool = True,
                 max_escalations: int = 4, seed: int = 0,
                 superchunk: int = 1, mesh=None):
        from .compat import warn_legacy

        warn_legacy("MonitoredFleetRunner")
        policy_factory = policy_factory or (
            lambda: InvariantPolicy(k=1, d=0.0))
        super().__init__(pattern, k, planner=planner,
                         policy_factory=policy_factory,
                         engine_cfg=engine_cfg,
                         estimator_buckets=estimator_buckets,
                         laplace=laplace,
                         escalate_on_overflow=escalate_on_overflow,
                         max_escalations=max_escalations, seed=seed,
                         mesh=mesh)
        for pol in self.policies:
            if not isinstance(pol, InvariantPolicy):
                raise TypeError(
                    "device monitoring verifies lowered invariant sets; "
                    "policy_factory must produce InvariantPolicy")
        if superchunk < 1:
            raise ValueError("superchunk must be >= 1")
        self.superchunk = int(superchunk)
        self.monitor_buckets = estimator_buckets
        self._caps = (max_inv, max_terms)
        self._low: Optional[StackedLowered] = None
        # resume carry (alongside FleetRunner._state): monitor rings and
        # the deferred flag from the previous run's final chunk — which a
        # single-call run can never apply, but a resumed continuation
        # must, to stay equivalent to one continuous stream.
        self._monitor = None
        self._pending: Optional[np.ndarray] = None
        self._pend_rates = None
        self._pend_sel = None

    # -- invariant deployment ---------------------------------------------

    def _prime(self) -> None:
        """Cold start: plan every partition from the uniform prior; real
        statistics arrive with the first chunks and fire the invariants."""
        plan0, self._low, self._caps = prime_invariant_policies(
            self.pattern, self.planner, self.policies, self._caps)
        row0 = self._plan_row(plan0)
        self._cur_rows = np.tile(row0, (self.k,) + (1,) * row0.ndim)
        self._old_rows = self._cur_rows.copy()
        self.cur_plans = [plan0] * self.k

    # -- main loop ---------------------------------------------------------

    def _apply_pending(self, pending, rates, sel, t0: float,
                       m: FleetMetrics) -> None:
        """Deferred flag-triggered replans: the planner runs only for
        partitions whose device flag fired on the last processed chunk,
        each costing exactly one statistics sync.  Violations are counted
        here, at application time, so ``violations == host_syncs ==
        replans`` holds by construction (a flag on the stream's final
        chunk never gets applied and is not counted)."""
        for p in np.nonzero(pending)[0]:
            stat = Stat(np.asarray(rates[p], np.float64),
                        np.asarray(sel[p], np.float64))
            m.violations += 1
            m.host_syncs += 1
            new_plan = replan_flagged_partition(
                self.pattern, self.planner, self.policies[p],
                self._low, p, stat, self._caps)
            m.replans += 1
            if new_plan != self.cur_plans[p]:
                self._deploy(p, new_plan, t0, m)

    def _carry(self, resume: bool):
        """Stream carry shared by both monitored loops: either the
        previous run's (state, monitor, pending flags + statistic slices)
        or a fresh stream."""
        if resume and self._state is not None:
            return (self._state, self._monitor, self._pending,
                    self._pend_rates, self._pend_sel)
        return (self.fleet.init_state(),
                self.fleet.init_monitor(self.monitor_buckets),
                np.zeros(self.k, bool), None, None)

    def _save_carry(self, state, monitor, pending, rates, sel) -> None:
        self._state, self._monitor = state, monitor
        self._pending = pending
        self._pend_rates, self._pend_sel = rates, sel

    def run(self, fleet_stream: Iterable[FleetChunk],
            resume: bool = False) -> FleetMetrics:
        if self.superchunk > 1:
            return self._run_scanned(fleet_stream, resume)
        m = FleetMetrics(
            per_partition_matches=np.zeros(self.k, np.int64),
            per_partition_deployments=np.zeros(self.k, np.int64))
        state, monitor, pending, rates_dev, sel_dev = self._carry(resume)
        if self._low is None:
            self._prime()

        for fc in fleet_stream:
            with jax.profiler.TraceAnnotation(spans.CONTROL, chunk=m.chunks):
                self._apply_pending(pending, rates_dev, sel_dev, fc.t0, m)
                pending[:] = False
                migrating = self._fold_lapsed(fc.t0)

            with jax.profiler.TraceAnnotation(spans.STEP, chunk=m.chunks):
                # Pass A, fused: joins + ring update + invariant
                # verification in ONE compiled vmapped call.
                state, monitor, res, violated, drift, rates_dev, sel_dev = \
                    self._active_fleet.process_chunk_monitored(
                        state, monitor, fc.chunk,
                        jnp.asarray(self._cur_rows), self._low.device(),
                        fc.t0, fc.t1,
                        born_lo=self._replan_t.astype(np.float32),
                        born_hi=_POS_INF)
                state, out = self._pass_b(state, fc, self._counters(res),
                                          migrating, fc.chunk)
                full, pm, ov, cl, ng = out
                # Overflow-escalation recounts run the *plain* passes so
                # the statistics ring is updated exactly once per chunk (by
                # the monitored pass above) and flags are never
                # double-observed.
                pre_fleet = self._active_fleet
                tries = 0
                while (ov.sum() > 0 and self.escalate_on_overflow
                       and tries < self.max_escalations):
                    self._active_fleet = self._escalated_fleet()
                    m.escalations += 1
                    tries += 1
                    empty = fc.chunk._replace(
                        valid=jnp.zeros_like(fc.chunk.valid))
                    pm_so_far = pm
                    state, (full, pm, ov, cl, ng) = self._plain_passes(
                        state, fc, empty, migrating)
                    pm = pm + pm_so_far
                if migrating.any():
                    # Mid-migration overflow: transient recount, not a
                    # regime.
                    self._active_fleet = pre_fleet
                    m.migration_partition_chunks += int(migrating.sum())

                # The entire per-chunk host round-trip: one (K,) bool
                # vector.
                pending = np.asarray(violated).copy()
                m.last_drift = np.asarray(drift, np.float32)

            m.chunks += 1
            m.events += int(np.asarray(fc.chunk.valid).sum())
            m.full_matches += int(full.sum())
            m.pm_created += int(pm.sum())
            m.overflow += int(ov.sum())
            m.closure_expansions += int(cl.sum())
            m.neg_rejected += int(ng.sum())
            m.per_partition_matches += full
        self._save_carry(state, monitor, pending, rates_dev, sel_dev)
        return m

    # -- superchunk (scanned) loop -----------------------------------------

    def _run_scanned(self, fleet_stream: Iterable[FleetChunk],
                     resume: bool = False) -> FleetMetrics:
        """The per-chunk loop above with the host taken out of it.

        ``lax.scan`` rolls up to ``superchunk`` chunks per dispatch; flags,
        drift and counters accumulate on device (``core.scan``).  The host
        surfaces only at window boundaries — or, via the optimistic prefix
        re-run, immediately after an in-window invariant flag / overflow,
        so deferred-replan and escalation semantics stay **bit-identical**
        to per-chunk stepping (asserted by ``tests/test_superchunk.py``).
        """
        from .scan import first_event, stack_window, window_control

        s_cap = self.superchunk
        m = FleetMetrics(
            per_partition_matches=np.zeros(self.k, np.int64),
            per_partition_deployments=np.zeros(self.k, np.int64))
        state, monitor, pending, pend_rates, pend_sel = self._carry(resume)
        if self._low is None:
            self._prime()
        it = iter(fleet_stream)
        buf: List[FleetChunk] = []
        exhausted = False

        while True:
            while len(buf) < s_cap and not exhausted:
                try:
                    buf.append(next(it))
                except StopIteration:
                    exhausted = True
            if not buf:
                break
            with jax.profiler.TraceAnnotation(spans.CONTROL, chunk=m.chunks):
                self._apply_pending(pending, pend_rates, pend_sel,
                                    buf[0].t0, m)
                pending[:] = False
                n_en = len(buf)
                ctl = window_control(self._replan_t, self._migration_until,
                                     [fc.t0 for fc in buf], s_cap)
                xs = stack_window([fc.chunk for fc in buf],
                                  [fc.t0 for fc in buf],
                                  [fc.t1 for fc in buf], ctl, s_cap)
                cur_rows = jnp.asarray(self._cur_rows)
                old_rows = jnp.asarray(self._old_rows)

            with jax.profiler.TraceAnnotation(spans.STEP, chunk=m.chunks):
                scan = self._active_fleet.superchunk_scan(monitored=True)
                low_dev = self._low.device()
                state2, monitor2, ys = scan(state, monitor, cur_rows, old_rows,
                                            low_dev, xs)
                # Eager readback is counters + flags + drift only; the (S, K,
                # n[, n]) statistic stacks stay on device and are pulled
                # per-partition at application time — host traffic stays
                # O(violations), not O(S·K·stats), exactly as per-chunk.
                (full_h, pm_h, ov_h, cl_h, ng_h, violated_h, drift_h) = \
                    jax.device_get((ys.full, ys.pm, ys.overflow, ys.closure,
                                    ys.neg, ys.violated, ys.drift))
                f = first_event(violated_h, ov_h, n_en,
                                self.escalate_on_overflow)
                if f is not None and f < n_en - 1:
                    # In-window event: replay the prefix [0..f] from the saved
                    # pre-window carry (bitwise-identical compute) so the host
                    # can replan / escalate before chunk f+1 runs — exactly
                    # the per-chunk contract.  Costs one extra dispatch, only
                    # when an event actually fired.
                    en = np.zeros(s_cap, bool)
                    en[:f + 1] = True
                    xs_pre = xs._replace(enabled=jnp.asarray(en))
                    state2, monitor2, _ = scan(state, monitor, cur_rows,
                                               old_rows, low_dev, xs_pre)
                accept = n_en if f is None else f + 1
                last = accept - 1
                state, monitor = state2, monitor2

                # Commit host mirrors to the fold state at the last accepted
                # chunk (float64, same trajectory the per-chunk loop walks —
                # including retiring the lapsed partitions' old plans).
                self._replan_t = ctl.replan_seq[last].copy()
                lapsed = ctl.old_sel[last]
                self._old_rows[lapsed] = self._cur_rows[lapsed]
                for p in np.nonzero(lapsed)[0]:
                    self.old_plans[p] = None

                counters = [np.asarray(c, np.int64)
                            for c in (full_h, pm_h, ov_h, cl_h, ng_h)]
                full_l, pm_l, ov_l, cl_l, ng_l = (c[last].copy()
                                                  for c in counters)
                pre_fleet = self._active_fleet
                if (self.escalate_on_overflow and ov_l.sum() > 0):
                    # Overflow recovery for the event chunk, identical to the
                    # per-chunk loop: re-evaluate at the next pow2 match
                    # capacity from the post-chunk state (events are already
                    # ingested); the escalated fleet persists for the
                    # following windows.
                    migrating_l = ctl.migrating[last]
                    tries = 0
                    while ov_l.sum() > 0 and tries < self.max_escalations:
                        self._active_fleet = self._escalated_fleet()
                        m.escalations += 1
                        tries += 1
                        empty = buf[last].chunk._replace(
                            valid=jnp.zeros_like(buf[last].chunk.valid))
                        pm_so_far = pm_l
                        state, (full_l, pm_l, ov_l, cl_l, ng_l) = \
                            self._plain_passes(state, buf[last], empty,
                                               migrating_l)
                        pm_l = pm_l + pm_so_far
                if ctl.migrating[last].any():
                    # Mid-migration overflow: transient recount, not a regime
                    # (mirrors the per-chunk loop chunk-for-chunk).
                    self._active_fleet = pre_fleet

                for s in range(accept):
                    m.chunks += 1
                    m.events += int(np.asarray(buf[s].chunk.valid).sum())
                    row = ((full_l, pm_l, ov_l, cl_l, ng_l) if s == last
                           else tuple(c[s] for c in counters))
                    full, pm, ov, cl, ng = row
                    m.full_matches += int(full.sum())
                    m.pm_created += int(pm.sum())
                    m.overflow += int(ov.sum())
                    m.closure_expansions += int(cl.sum())
                    m.neg_rejected += int(ng.sum())
                    m.per_partition_matches += np.asarray(full, np.int64)
                m.migration_partition_chunks += int(
                    ctl.migrating[:accept].sum())
                m.last_drift = np.asarray(drift_h[last], np.float32)
                pending = np.asarray(violated_h[last]).copy()
                # Device slices: _apply_pending materializes row p only for
                # partitions whose flag actually fired.
                pend_rates = ys.rates[last]
                pend_sel = ys.sel[last]
            buf = buf[accept:]
        self._save_carry(state, monitor, pending, pend_rates, pend_sel)
        return m
