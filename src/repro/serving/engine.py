"""Batched serving engines: LM prefill/decode and the CEP fleet front.

``ServingEngine`` wraps ``Model.prefill`` / ``Model.decode_step`` into
jitted entry points with a fixed batch capacity.  Requests occupy batch
*slots*; finished slots are refilled by the scheduler without recompiling
(slot state is data).  Per-request cache write indices support
heterogeneous positions in one batch — the decode step is one compiled
program regardless of the request mix, mirroring the CEP engine's
plans-are-data design.

``CEPFleetServingEngine`` is the same idea for event streams: K stream
partitions occupy fleet *rows*; a keyed event batch is routed by
``key % K`` into stacked per-partition chunks and the whole fleet advances
with ONE compiled vmapped ``process_chunk``.  Deploying a new plan for a
partition writes one row of the stacked plan matrix — never a recompile.

``MonitoredCEPFleetServingEngine`` adds the device-resident control loop:
per-partition statistics rings and lowered invariant sets ride inside the
same compiled call, the host reads back only a ``(K,)`` violation-flag
vector, and a flagged partition is re-planned from its synced device
statistics before the next batch — per-batch host work is O(violations),
not O(K·stats).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import spans
from ..core.adaptation import make_planner
from ..core.decision import InvariantPolicy
from ..core.engine import EngineConfig
from ..core.fleet import (FleetEngine, prime_invariant_policies,
                          replan_flagged_partition, route_events)
from ..core.patterns import Pattern
from ..core.stats import Stat
from ..models.config import ModelConfig
from ..models.model import Cache, Model


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, batch_slots: int,
                 cache_len: int):
        self.cfg = cfg
        self.model = Model(cfg, remat="none")
        self.params = params
        self.batch_slots = batch_slots
        self.cache_len = cache_len
        self._decode = jax.jit(self.model.decode_step)
        self._prefill_cache: Dict[int, object] = {}
        self.cache: Cache = self.model.init_cache(batch_slots, cache_len)

    def prefill_one(self, tokens: np.ndarray, slot: int) -> int:
        """Prefill a single request's prompt into ``slot``.

        Prompt lengths are bucketed to powers of two so each bucket
        compiles once (static shapes; the adaptive batch planner keeps the
        hot buckets warm).  Returns the first generated token.
        """
        plen = len(tokens)
        bucket = 1 << max(4, (plen - 1).bit_length())
        if self.cfg.family in ("ssm", "hybrid") and bucket != plen:
            raise ValueError(
                "SSM-state prefill needs exact-length prompts; generate "
                f"prompts at bucket sizes (got {plen}, bucket {bucket})")
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :plen] = tokens
        if bucket not in self._prefill_cache:
            self._prefill_cache[bucket] = jax.jit(
                functools.partial(self.model.prefill,
                                  cache_len=self.cache_len))
        tl = (None if self.cfg.family in ("ssm", "hybrid")
              else jnp.asarray([plen], jnp.int32))
        logits, one_cache = self._prefill_cache[bucket](
            self.params, {"tokens": jnp.asarray(padded)}, true_lens=tl)
        # Merge the single-request cache into the batch cache at `slot`:
        # kv leaves (L, B, T, K, hd); ssm conv (L, B, W, CH); ssd
        # (L, B, H, P, N); index (B,).
        def set_slot(big, small):
            return big.at[:, slot].set(small[:, 0]) if big.ndim >= 2 \
                else big.at[slot].set(small[0])
        kv = (jax.tree.map(set_slot, self.cache.kv, one_cache.kv)
              if self.cache.kv != () else ())
        ssm = (jax.tree.map(set_slot, self.cache.ssm, one_cache.ssm)
               if self.cache.ssm != () else ())
        index = self.cache.index.at[slot].set(plen)
        self.cache = Cache(kv=kv, ssm=ssm, index=index)
        return int(jnp.argmax(logits[0, 0]))

    def decode(self, tokens: np.ndarray) -> np.ndarray:
        """One decode step for the whole batch; tokens: (slots,) i32."""
        logits, self.cache = self._decode(
            self.params, self.cache, jnp.asarray(tokens)[:, None])
        return np.asarray(jnp.argmax(logits[:, 0], axis=-1))

    def reset_slot(self, slot: int) -> None:
        self.cache = self.cache._replace(
            index=self.cache.index.at[slot].set(0))


class CEPFleetServingEngine:
    """Serving front for the partitioned CEP fleet.

    Owns the stacked ring-buffer state and the per-partition plan rows;
    ``process_batch`` takes one keyed event batch covering the time slice
    ``(t0, t1]``, routes it to partitions and advances all K partitions in
    one compiled call.  Per-partition cumulative match counts and
    capacity-drop back-pressure are exposed for the scheduler.

    Each tick writes the host spans of ``core.spans`` with the tick index
    ``ticks`` as argument; ``readbacks`` counts its blocking device→host
    reads.
    """

    def __init__(self, pattern: Pattern, k: int, plans,
                 engine_cfg: EngineConfig = EngineConfig(),
                 kind: str = "order", chunk_cap: int = 512,
                 laplace: float = 1.0, superchunk: int = 1, mesh=None):
        from ..core.compat import warn_legacy

        if type(self) is CEPFleetServingEngine:
            warn_legacy("CEPFleetServingEngine")
        self.fleet = FleetEngine(kind, pattern, k, engine_cfg,
                                 monitor_laplace=laplace, mesh=mesh)
        self.k = k
        self.chunk_cap = chunk_cap
        if superchunk < 1:
            raise ValueError("superchunk must be >= 1")
        self.superchunk = int(superchunk)
        self.state = self.fleet.init_state()
        # Host-owned copy: plan rows must stay writable for deploy_plan
        # (np.asarray of a jax array is a read-only view).
        self._rows = np.array(self.fleet.plans_to_array(plans))
        self.matches = np.zeros(k, np.int64)
        self.neg_rejected = np.zeros(k, np.int64)
        self.closure_expansions = np.zeros(k, np.int64)
        self.overflow = np.zeros(k, np.int64)
        self.dropped = 0
        self.ticks = 0
        self.readbacks = 0

    def reset(self) -> None:
        """Clear stream state and counters; compiled programs and deployed
        plan rows survive (a reset is a fresh stream, not a fresh fleet)."""
        self.state = self.fleet.init_state()
        for arr in (self.matches, self.neg_rejected,
                    self.closure_expansions, self.overflow):
            arr[:] = 0
        self.dropped = 0
        self.ticks = 0
        self.readbacks = 0

    def deploy_plan(self, partition: int, plan) -> None:
        """Cheap deployment (§2.2): rewrite one stacked plan row."""
        self._rows[partition] = self.fleet.plan_row(plan)

    def route(self, type_id, ts, attr, keys):
        """Route one keyed event batch to a stacked per-partition chunk.

        Capacity-clipped events accumulate in ``dropped`` — the only
        engine-side drop channel; the router's ``late_dropped`` is the
        only other one, so ``submitted == reached-engine + late_dropped +
        dropped + pending`` is checkable end to end."""
        with jax.profiler.TraceAnnotation(spans.ROUTE, chunk=self.ticks):
            chunk, dropped = route_events(
                np.asarray(type_id), np.asarray(ts), np.asarray(attr),
                np.asarray(keys), self.k, self.chunk_cap)
        self.dropped += dropped
        return chunk

    @contextlib.contextmanager
    def _readback(self):
        """One blocking device→host read: a ``cep.readback`` span and a
        count in ``readbacks``."""
        with jax.profiler.TraceAnnotation(spans.READBACK, chunk=self.ticks):
            yield
        self.readbacks += 1

    def _accumulate(self, res) -> np.ndarray:
        # One device→host transfer for all four counters: per-array
        # fetches cost a dispatch + transfer each and dominate the serving
        # tick at small chunk sizes (the facade-overhead budget in
        # benchmarks/fleet_bench.py watches this path).
        with self._readback():
            full, neg, clo, ov = np.asarray(jnp.stack(
                [res.full_matches, res.neg_rejected,
                 res.closure_expansions, res.overflow]), np.int64)
        self.matches += full
        self.neg_rejected += neg
        self.closure_expansions += clo
        # Match-set truncation undercounts matches; surface it per
        # partition so undercounting is never silent.
        self.overflow += ov
        return full

    def process_chunk(self, chunk, t0: float, t1: float) -> np.ndarray:
        """Tick the fleet once over an already-routed stacked chunk."""
        with jax.profiler.TraceAnnotation(spans.STEP, chunk=self.ticks):
            self.state, res = self.fleet.process_chunk(
                self.state, chunk, self._rows, t0, t1)
        full = self._accumulate(res)
        self.ticks += 1
        return full

    def process_batch(self, type_id, ts, attr, keys,
                      t0: float, t1: float) -> np.ndarray:
        """Route one keyed event batch and tick the fleet once.

        Returns the per-partition full-match counts for this slice.
        """
        return self.process_chunk(self.route(type_id, ts, attr, keys),
                                  t0, t1)

    # -- superchunk control plane ------------------------------------------

    def _accumulate_rows(self, counters, n_rows: int) -> np.ndarray:
        """Fold accepted rows of host (full, neg, closure, overflow)
        counter stacks into the cumulative per-partition totals."""
        full_h, neg_h, cl_h, ov_h = counters
        full = np.asarray(full_h[:n_rows], np.int64)
        self.matches += full.sum(axis=0)
        self.neg_rejected += np.asarray(neg_h[:n_rows],
                                        np.int64).sum(axis=0)
        self.closure_expansions += np.asarray(cl_h[:n_rows],
                                              np.int64).sum(axis=0)
        self.overflow += np.asarray(ov_h[:n_rows], np.int64).sum(axis=0)
        return full

    def process_superchunk(self, chunks, edges) -> np.ndarray:
        """Roll a sequence of already-routed stacked chunks through the
        fleet, ``superchunk`` chunks per compiled dispatch (``core.scan``).

        ``chunks``: stacked ``Chunk``s (leading K axis); ``edges``: their
        ``(t0, t1]`` slices.  Plans are static between ``deploy_plan``
        calls, so the host never needs to surface mid-window — every
        window is exactly one dispatch.  Returns the per-chunk ``(S, K)``
        full-match counts; cumulative counters update as in
        ``process_chunk``.
        """
        from ..core.scan import stack_window, static_control

        s_cap = self.superchunk
        n = len(chunks)
        if n != len(edges):
            raise ValueError(f"{n} chunks vs {len(edges)} edges")
        out = np.zeros((n, self.k), np.int64)
        scan = self.fleet.superchunk_scan(monitored=False)
        ctl = static_control(self.k, s_cap)
        i = 0
        while i < n:
            win = chunks[i:i + s_cap]
            t0s = [e[0] for e in edges[i:i + len(win)]]
            t1s = [e[1] for e in edges[i:i + len(win)]]
            xs = stack_window(win, t0s, t1s, ctl, s_cap)
            rows = jnp.asarray(self._rows)
            self.state, _, ys = scan(self.state, None, rows, rows,
                                     None, xs)
            with self._readback():
                ys_h = jax.device_get((ys.full, ys.neg, ys.closure,
                                       ys.overflow))
            out[i:i + len(win)] = self._accumulate_rows(ys_h, len(win))
            i += len(win)
            self.ticks += len(win)
        return out


class MonitoredCEPFleetServingEngine(CEPFleetServingEngine):
    """Serving fleet with on-device invariant monitoring (§3.3-§3.5).

    Partitions start on a plan generated from the uniform prior; real
    per-partition statistics accumulate in device-resident rings inside
    the compiled batch call.  When a partition's lowered invariant set
    flags a violation, the host syncs that partition's ``(rates, sel)``
    snapshot, re-runs the planner, and deploys the new plan row and the
    freshly compiled invariant row — all array writes, never a recompile.

    The serving front deploys immediately (no [36] migration split):
    partial matches are rebuilt from the ring buffers every slice, so a
    row swap between batches changes only join *work*, never *which*
    matches are counted — exactly-once detection is preserved (see
    DESIGN.md §7).

    Telemetry: ``violations`` / ``replans`` (per partition),
    ``host_syncs`` (total statistic pulls — ∝ violations, not K·batches),
    and ``last_drift`` (the §3.4-style relative margin of each
    partition's tightest invariant after the latest batch).
    """

    def __init__(self, pattern: Pattern, k: int,
                 engine_cfg: EngineConfig = EngineConfig(),
                 kind: Optional[str] = None, chunk_cap: int = 512,
                 planner: str = "greedy", policy_kw: Optional[dict] = None,
                 monitor_buckets: int = 16,
                 max_inv: Optional[int] = None,
                 max_terms: Optional[int] = None,
                 laplace: float = 1.0, superchunk: int = 1, mesh=None):
        from ..core.compat import warn_legacy

        warn_legacy("MonitoredCEPFleetServingEngine")
        self.pattern = pattern
        self.planner = make_planner(planner)
        # The plan family must match the planner's output (an order vector
        # vs a slot-join program); derive it unless explicitly overridden.
        kind = kind or ("order" if planner == "greedy" else "tree")
        self.policies = [InvariantPolicy(**(policy_kw or {}))
                         for _ in range(k)]
        plan0, self._low, self._caps = prime_invariant_policies(
            pattern, self.planner, self.policies, (max_inv, max_terms))
        super().__init__(pattern, k, plan0, engine_cfg, kind, chunk_cap,
                         laplace=laplace, superchunk=superchunk, mesh=mesh)
        self.plans = [plan0] * k
        self.monitor = self.fleet.init_monitor(monitor_buckets)
        self.violations = np.zeros(k, np.int64)
        self.replans = np.zeros(k, np.int64)
        self.host_syncs = 0
        self.last_drift = np.full(k, -np.inf, np.float32)

    def reset(self) -> None:
        """Clear stream state, monitor rings and counters; deployed plan
        rows and the compiled invariant rows survive."""
        super().reset()
        self.monitor = self.fleet.init_monitor(self.monitor.counts.shape[1])
        self.violations[:] = 0
        self.replans[:] = 0
        self.host_syncs = 0
        self.last_drift = np.full(self.k, -np.inf, np.float32)

    def deploy_plan(self, partition: int, plan) -> None:
        """Manually deploy a plan row for one partition.

        The partition's *invariant* row is intentionally left as the last
        planner output's: deciding-condition sets exist only for plans the
        instrumented planner generated, so the monitor keeps answering the
        §3 question — "would re-running ``A`` change its choice?" — and a
        violation re-establishes planner control (overwriting the manual
        plan via the flag-triggered replan)."""
        super().deploy_plan(partition, plan)
        self.plans[partition] = plan

    def _apply_flags(self, fired_mask, rates, sel) -> None:
        """The O(violations) control plane: sync + replan flagged rows only.

        ``rates``/``sel`` may be device or host arrays; a partition's
        snapshot is materialized only when its flag fired.  One
        ``cep.control`` span on every tick, one ``cep.replan`` inside it
        per flagged partition.
        """
        with jax.profiler.TraceAnnotation(spans.CONTROL, chunk=self.ticks):
            for p in np.nonzero(np.asarray(fired_mask))[0]:
                with jax.profiler.TraceAnnotation(
                        spans.REPLAN, chunk=self.ticks, partition=int(p)):
                    self._replan(int(p), rates, sel)

    def _replan(self, p: int, rates, sel) -> None:
        self.violations[p] += 1
        self.host_syncs += 1
        with self._readback():
            rates_p = np.asarray(rates[p], np.float64)
        with self._readback():
            sel_p = np.asarray(sel[p], np.float64)
        new_plan = replan_flagged_partition(
            self.pattern, self.planner, self.policies[p],
            self._low, p, Stat(rates_p, sel_p), self._caps)
        if new_plan != self.plans[p]:
            self.deploy_plan(p, new_plan)  # also records self.plans[p]
            self.replans[p] += 1

    def process_chunk(self, chunk, t0: float, t1: float) -> np.ndarray:
        """Tick the fused monitored fleet over an already-routed chunk and
        replan any partition whose invariant flag fired."""
        with jax.profiler.TraceAnnotation(spans.STEP, chunk=self.ticks):
            self.state, self.monitor, res, violated, drift, rates, sel = \
                self.fleet.process_chunk_monitored(
                    self.state, self.monitor, chunk, self._rows,
                    self._low.device(), t0, t1)
        full = self._accumulate(res)
        # Coalesce the flag + drift readback into one transfer (the only
        # extra per-tick host traffic device monitoring costs).
        with self._readback():
            vd = np.asarray(jnp.stack([violated.astype(jnp.float32),
                                       drift]))
        self.last_drift = vd[1].astype(np.float32)
        self._apply_flags(vd[0] > 0.5, rates, sel)
        self.ticks += 1
        return full

    def process_superchunk(self, chunks, edges) -> np.ndarray:
        """Monitored superchunk ticks: S chunks per dispatch, flags and
        telemetry accumulated on device, host control only at boundaries.

        Bit-identical to looping ``process_chunk``: the scan is run
        optimistically, and when a flag fires at in-window chunk ``f`` the
        prefix ``[0..f]`` is re-run from the pre-window state so the
        replanned rows deploy before chunk ``f+1`` — exactly the per-tick
        contract (see ``core.scan``).  Violation-free windows cost one
        dispatch; host work stays O(violations).
        """
        from ..core.scan import first_event, stack_window, static_control

        s_cap = self.superchunk
        n = len(chunks)
        if n != len(edges):
            raise ValueError(f"{n} chunks vs {len(edges)} edges")
        out = np.zeros((n, self.k), np.int64)
        scan = self.fleet.superchunk_scan(monitored=True)
        ctl = static_control(self.k, s_cap)
        i = 0
        while i < n:
            win = chunks[i:i + s_cap]
            n_en = len(win)
            t0s = [e[0] for e in edges[i:i + n_en]]
            t1s = [e[1] for e in edges[i:i + n_en]]
            xs = stack_window(win, t0s, t1s, ctl, s_cap)
            rows = jnp.asarray(self._rows)
            low_dev = self._low.device()
            state2, mon2, ys = scan(self.state, self.monitor, rows, rows,
                                    low_dev, xs)
            # Counters + flags + drift come back eagerly; the statistic
            # stacks stay device-resident and are materialized
            # per-partition only when a flag fired (O(violations) host
            # traffic, as in the per-tick path).
            with self._readback():
                ys_h = jax.device_get(
                    (ys.full, ys.pm, ys.overflow, ys.closure, ys.neg,
                     ys.violated, ys.drift))
            (full_h, pm_h, ov_h, cl_h, ng_h, violated_h, drift_h) = ys_h
            f = first_event(violated_h, ov_h, n_en, escalate=False)
            if f is not None and f < n_en - 1:
                en = np.zeros(s_cap, bool)
                en[:f + 1] = True
                state2, mon2, _ = scan(
                    self.state, self.monitor, rows, rows, low_dev,
                    xs._replace(enabled=jnp.asarray(en)))
            accept = n_en if f is None else f + 1
            self.state, self.monitor = state2, mon2
            out[i:i + accept] = self._accumulate_rows(
                (full_h, ng_h, cl_h, ov_h), accept)
            last = accept - 1
            self.last_drift = np.asarray(drift_h[last], np.float32)
            self._apply_flags(violated_h[last], ys.rates[last],
                              ys.sel[last])
            i += accept
            self.ticks += accept
        return out
