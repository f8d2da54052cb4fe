"""Superchunk data plane: roll S chunks through one compiled ``lax.scan``.

The per-chunk runners (``FleetRunner`` / ``MonitoredFleetRunner`` and the
serving fronts) cross the host↔device boundary once per chunk: dispatch a
compiled step, read back a ``(K,)`` flag vector, decide, repeat.  At fleet
scale the Python dispatch loop — not the join kernel — becomes the
bottleneck, inverting the paper's §2.2 premise that adaptation decisions
are cheap relative to detection.  This module removes the host from the
per-chunk loop:

* the fused process(+monitor) step is re-expressed as a **pure scan step**
  ``body(carry, x) -> (carry, out)`` with ``carry = (Buffers,
  MonitorState)`` — exactly the state the per-chunk loop threads by hand;
* ``lax.scan`` rolls ``S`` chunks ("a superchunk") through ONE dispatch;
  violation flags, drift telemetry and per-chunk counters accumulate on
  device as stacked ``(S, K, ...)`` outputs;
* the host syncs, replans, and deploys only at superchunk boundaries.

Per-chunk control that the runners used to do on the host *between* steps
is split in two:

* **Precomputed control (host, exact)** — migration folding depends only
  on ``replan_t`` / ``migration_until`` and each chunk's ``t0``, all known
  before the window is dispatched.  The host precomputes, in float64
  (bit-identical to the per-chunk runner's ``_fold_lapsed``), the per-chunk
  ``born_lo`` vectors, migrating masks and old-row selectors and feeds
  them to the scan as inputs (``SuperchunkXs``).  Plan rows and lowered
  invariant tensors are window-constant arguments — they change only at
  boundaries, which is what makes the scan legal.
* **Reactive control (optimistic restart)** — an invariant violation (or
  an overflow needing escalation) at in-window chunk ``f`` must surface to
  the host so the replan deploys at chunk ``f+1``, exactly as in the
  per-chunk loop.  The scan cannot early-exit, so the driver runs the
  window optimistically, inspects the stacked flags, and — in the rare
  event case — re-runs the *prefix* ``[0..f]`` from the saved pre-window
  carry with the remaining chunks disabled (the ``enabled`` input;
  deterministic compute makes the prefix bitwise identical), then resumes
  from ``f+1`` after replanning.  Violation-free windows (the common case,
  by §3's low-violation-rate design) cost exactly one dispatch for S
  chunks; each event costs one extra dispatch.  Semantics are therefore
  **bit-identical** to per-chunk stepping for every superchunk size.

Sharding: every carry/row/lowered leaf carries a leading K axis and every
scan input/output a leading (S, K), so the whole scanned function maps
onto a 1-D device mesh with ``shard_map`` under a single partition rule
(K split over the ``cep`` axis, everything else replicated).  Partitions
are independent — the sharded scan needs **zero** cross-device
collectives; see ``distributed.sharding.fleet_pspec``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .engine import NEG_INF, POS_INF, Chunk, make_monitored_process


class SuperchunkXs(NamedTuple):
    """Per-chunk scan inputs; every leaf has a leading ``S`` axis.

    ``enabled`` gates the whole step (disabled chunks pass the carry
    through untouched) — it implements both tail padding of a short final
    window and the prefix re-run after an in-window event.  ``born_lo`` /
    ``migrating`` / ``old_sel`` are the host-precomputed migration fold
    (see module docstring); for control planes without the [36] migration
    split they are just ``-inf`` / ``False`` / ``False``.
    """

    chunk: Chunk          # (S, K, cap) / (S, K, cap, A) fields
    t0: jax.Array         # (S,) f32 shared chunk clock
    t1: jax.Array         # (S,) f32
    enabled: jax.Array    # (S,) bool
    born_lo: jax.Array    # (S, K) f32 — post-fold replan_t per chunk
    migrating: jax.Array  # (S, K) bool — partition mid-migration this chunk
    old_sel: jax.Array    # (S, K) bool — migration lapsed: old row := cur row


class SuperchunkOut(NamedTuple):
    """Per-chunk scan outputs; every leaf has a leading ``(S, K)``."""

    full: jax.Array       # i32 full matches (pass A + masked pass B)
    pm: jax.Array         # i32 partial matches materialized
    overflow: jax.Array   # i32 candidates dropped by capacity
    closure: jax.Array    # i32 Kleene companion count
    neg: jax.Array        # i32 negation vetoes
    violated: jax.Array   # bool invariant flags (monitored; else False)
    drift: jax.Array      # f32 §3.4 relative margins (monitored; else -inf)
    rates: jax.Array      # (S, K, n) f32 monitor snapshot at each chunk
    sel: jax.Array        # (S, K, n, n) f32


def make_superchunk_scan(process_fn, spec, monitored: bool,
                         laplace: float = 1.0, mesh=None,
                         plan_operands=None):
    """Build the compiled superchunk scan for one engine configuration.

    Returns ``scan(buffers, monitor, cur_rows, old_rows, lowered, xs) ->
    (buffers, monitor, SuperchunkOut)`` where state/rows/lowered carry a
    leading K axis and ``xs`` is a :class:`SuperchunkXs`.  ``monitored``
    fuses the statistics rings + lowered-invariant verification into each
    step (``monitor``/``lowered`` may be ``None`` otherwise).  With
    ``mesh`` the whole scan is ``shard_map``-ped over the mesh's ``cep``
    axis — one dispatch drives D devices for S chunks with no collectives.

    ``plan_operands`` (engines that support it) maps stacked plan rows to
    their precomputed join operands (e.g. ``OrderEngine.plan_operands``);
    it runs inside the compiled scan but OUTSIDE the ``lax.scan`` body, so
    the plan-constant operand strips are derived once per dispatch and the
    per-chunk step is reduced to gather + kernel.  Strips are a per-row
    function, so blending cur/old per chunk leaf-wise commutes with the
    derivation — per-chunk semantics stay bit-identical.
    """
    n = spec.n
    process = jax.vmap(process_fn)
    mprocess = (jax.vmap(make_monitored_process(process_fn, spec, laplace))
                if monitored else None)

    def body(cur_rows, old_rows, lowered, carry, x: SuperchunkXs):
        def run(carry):
            buffers, monitor = carry
            kk = x.born_lo.shape[0]
            t0v = jnp.broadcast_to(x.t0.astype(jnp.float32), (kk,))
            t1v = jnp.broadcast_to(x.t1.astype(jnp.float32), (kk,))
            neg_v = jnp.full((kk,), NEG_INF, jnp.float32)
            pos_v = jnp.full((kk,), POS_INF, jnp.float32)

            def blend(c, o):  # per-partition row select (pytree-safe)
                sel = x.old_sel.reshape((kk,) + (1,) * (c.ndim - 1))
                return jnp.where(sel, c, o)

            old_eff = jax.tree.map(blend, cur_rows, old_rows)

            # Pass A: current plans ingest the chunk; completed matches
            # restricted to those born at/after each partition's replan.
            if monitored:
                buffers, monitor, res, violated, drift, rates, sel = \
                    mprocess(buffers, monitor, x.chunk, cur_rows, lowered,
                             t0v, t1v, x.born_lo, pos_v)
            else:
                buffers, res = process(buffers, x.chunk, cur_rows,
                                       t0v, t1v, x.born_lo, pos_v)
                violated = jnp.zeros((kk,), bool)
                drift = jnp.full((kk,), NEG_INF, jnp.float32)
                rates = jnp.zeros((kk, n), jnp.float32)
                sel = jnp.zeros((kk, n, n), jnp.float32)
            counters = tuple(
                jnp.asarray(c, jnp.int32)
                for c in (res.full_matches, res.pm_created, res.overflow,
                          res.closure_expansions, res.neg_rejected))

            # Pass B: old plans over an empty chunk pick up matches born
            # before each partition's replan; non-migrating partitions are
            # masked out of the counters (their born-window is empty but
            # pm/overflow measure join work regardless).
            def with_pass_b(args):
                buffers, counters = args
                empty = x.chunk._replace(
                    valid=jnp.zeros_like(x.chunk.valid))
                buffers, res_b = process(buffers, empty, old_eff,
                                         t0v, t1v, neg_v, x.born_lo)
                extra = (res_b.full_matches, res_b.pm_created,
                         res_b.overflow, res_b.closure_expansions,
                         res_b.neg_rejected)
                counters = tuple(
                    c + jnp.where(x.migrating, e.astype(jnp.int32), 0)
                    for c, e in zip(counters, extra))
                return buffers, counters

            buffers, counters = jax.lax.cond(
                x.migrating.any(), with_pass_b, lambda a: a,
                (buffers, counters))
            out = SuperchunkOut(*counters, violated, drift, rates, sel)
            return (buffers, monitor), out

        def skip(carry):
            kk = x.born_lo.shape[0]
            out = SuperchunkOut(
                *(jnp.zeros((kk,), jnp.int32) for _ in range(5)),
                jnp.zeros((kk,), bool),
                jnp.full((kk,), NEG_INF, jnp.float32),
                jnp.zeros((kk, n), jnp.float32),
                jnp.zeros((kk, n, n), jnp.float32))
            return carry, out

        return jax.lax.cond(x.enabled, run, skip, carry)

    def scan_fn(buffers, monitor, cur_rows, old_rows, lowered, xs):
        if plan_operands is not None:
            # Hoisted: once per superchunk dispatch, not once per chunk.
            cur_rows = plan_operands(cur_rows)
            old_rows = plan_operands(old_rows)
        carry, ys = jax.lax.scan(
            functools.partial(body, cur_rows, old_rows, lowered),
            (buffers, monitor), xs)
        return carry[0], carry[1], ys

    if mesh is not None:
        from ..distributed.sharding import shard_fleet_scan
        scan_fn = shard_fleet_scan(scan_fn, mesh)
    return jax.jit(scan_fn)


# ---------------------------------------------------------------------------
# Host-side window control (exact float64 twin of the per-chunk fold)
# ---------------------------------------------------------------------------


class WindowControl(NamedTuple):
    """Precomputed per-chunk migration control for one superchunk window.

    ``replan_seq[s]`` is the float64 ``replan_t`` state *after* the fold at
    chunk ``s`` — the host rolls its mirrors forward to row ``f`` once the
    window's first ``f+1`` chunks are accepted.
    """

    born_lo: np.ndarray     # (S, K) f32 — pass-A born_lo / pass-B born_hi
    migrating: np.ndarray   # (S, K) bool
    old_sel: np.ndarray     # (S, K) bool — cumulative "old row := cur row"
    replan_seq: np.ndarray  # (S, K) f64


def window_control(replan_t: np.ndarray, migration_until: np.ndarray,
                   t0s: Sequence[float], s_pad: int) -> WindowControl:
    """Roll the [36] migration fold over a window of chunk starts.

    Bit-identical to ``FleetRunner._fold_lapsed`` applied per chunk: all
    comparisons in float64 on the host, only the final ``born_lo`` cast to
    f32 (exactly what the per-chunk runner feeds the device).  Does NOT
    mutate its inputs — the caller commits row ``f`` after acceptance.
    ``s_pad`` rows beyond ``len(t0s)`` are emitted disabled-shaped (zeros).
    """
    k = replan_t.shape[0]
    s = len(t0s)
    rt = np.asarray(replan_t, np.float64).copy()
    born_lo = np.full((s_pad, k), NEG_INF, np.float32)
    migrating = np.zeros((s_pad, k), bool)
    old_sel = np.zeros((s_pad, k), bool)
    replan_seq = np.full((s_pad, k), NEG_INF, np.float64)
    folded = np.zeros(k, bool)
    for i, t0 in enumerate(t0s):
        lapsed = (rt > NEG_INF) & (t0 >= migration_until)
        rt[lapsed] = NEG_INF
        folded |= lapsed
        born_lo[i] = rt.astype(np.float32)
        migrating[i] = rt > NEG_INF
        old_sel[i] = folded
        replan_seq[i] = rt
    return WindowControl(born_lo, migrating, old_sel, replan_seq)


def static_control(k: int, s_pad: int) -> WindowControl:
    """No-migration window control (the serving fronts deploy immediately,
    so born-windows are unbounded and pass B never runs)."""
    return WindowControl(
        born_lo=np.full((s_pad, k), NEG_INF, np.float32),
        migrating=np.zeros((s_pad, k), bool),
        old_sel=np.zeros((s_pad, k), bool),
        replan_seq=np.full((s_pad, k), NEG_INF, np.float64))


def stack_window(chunks: Sequence[Chunk], t0s, t1s, ctl: WindowControl,
                 s_pad: int) -> SuperchunkXs:
    """Stack a window of stacked ``(K, ...)`` chunks into scan inputs.

    Short windows (stream tail, prefix re-runs) are padded to ``s_pad``
    with disabled repeats of the last chunk so one compiled scan serves
    every window length.
    """
    s = len(chunks)
    if s == 0:
        raise ValueError("empty superchunk window")
    padded = list(chunks) + [chunks[-1]] * (s_pad - s)
    chunk = jax.tree.map(lambda *xs: jnp.stack(xs), *padded)
    t0a = np.zeros(s_pad, np.float32)
    t1a = np.zeros(s_pad, np.float32)
    t0a[:s] = np.asarray(t0s, np.float32)
    t1a[:s] = np.asarray(t1s, np.float32)
    enabled = np.zeros(s_pad, bool)
    enabled[:s] = True
    return SuperchunkXs(
        chunk=chunk,
        t0=jnp.asarray(t0a),
        t1=jnp.asarray(t1a),
        enabled=jnp.asarray(enabled),
        born_lo=jnp.asarray(ctl.born_lo),
        migrating=jnp.asarray(ctl.migrating),
        old_sel=jnp.asarray(ctl.old_sel),
    )


def first_event(violated: np.ndarray, overflow: np.ndarray,
                n_enabled: int, escalate: bool) -> Optional[int]:
    """Index of the first in-window chunk needing host attention.

    An *event* is an invariant flag on any partition, or (when escalation
    is on) a truncated join — both require the host before the *next*
    chunk runs.  Returns None when the window is event-free.  Flags may
    carry any trailing shape after the leading chunk axis — ``(S, K)`` for
    the single-pattern fleet, ``(S, K, Qb)`` for the rulebook plane.
    """
    ev = violated[:n_enabled].reshape(n_enabled, -1).any(axis=1)
    if escalate:
        ev = ev | (overflow[:n_enabled].reshape(n_enabled, -1).sum(axis=1)
                   > 0)
    idx = np.nonzero(ev)[0]
    return int(idx[0]) if idx.size else None


# ---------------------------------------------------------------------------
# The scanned rulebook plane: S chunks × K partitions × Qb rules / dispatch
# ---------------------------------------------------------------------------


class RulebookXs(NamedTuple):
    """Rulebook scan inputs; every leaf has a leading ``S`` axis.

    The rulebook control plane deploys plan rows immediately (serving
    semantics: no [36] migration split), so the only reactive control is
    the invariant flag — ``enabled`` implements tail padding and the
    optimistic prefix re-run exactly as on the single-pattern plane.
    """

    chunk: Chunk        # (S, K, cap) / (S, K, cap, A) fields
    t0: jax.Array       # (S,) f32
    t1: jax.Array       # (S,) f32
    enabled: jax.Array  # (S,) bool


class RulebookOut(NamedTuple):
    """Rulebook scan outputs; every leaf has a leading ``(S, K, Qb)``."""

    full: jax.Array      # i32 full matches per rule
    pm: jax.Array        # i32 partial matches materialized
    overflow: jax.Array  # i32 candidates dropped by capacity
    closure: jax.Array   # i32 Kleene companion count
    neg: jax.Array       # i32 negation vetoes
    violated: jax.Array  # bool per-(q, k) invariant flags
    drift: jax.Array     # f32 relative margins (monitored; else -inf)
    rates: jax.Array     # (S, K, Qb, n) f32 monitor snapshot per chunk
    sel: jax.Array       # (S, K, Qb, n, n) f32


def stack_rulebook_window(chunks: Sequence[Chunk], t0s, t1s,
                          s_pad: int) -> RulebookXs:
    """Stack a window of stacked ``(K, ...)`` chunks into rulebook scan
    inputs, padding short windows with disabled repeats of the last chunk
    (one compiled scan serves every window length)."""
    s = len(chunks)
    if s == 0:
        raise ValueError("empty superchunk window")
    padded = list(chunks) + [chunks[-1]] * (s_pad - s)
    chunk = jax.tree.map(lambda *xs: jnp.stack(xs), *padded)
    t0a = np.zeros(s_pad, np.float32)
    t1a = np.zeros(s_pad, np.float32)
    t0a[:s] = np.asarray(t0s, np.float32)
    t1a[:s] = np.asarray(t1s, np.float32)
    enabled = np.zeros(s_pad, bool)
    enabled[:s] = True
    return RulebookXs(chunk=chunk, t0=jnp.asarray(t0a),
                      t1=jnp.asarray(t1a), enabled=jnp.asarray(enabled))


def make_rulebook_scan(bspec, cfg, k: int, monitored: bool,
                       laplace: float = 1.0, mesh=None):
    """Compile (or fetch from the trace memo) the scanned rulebook plane.

    Returns a ``multipattern._Plane`` whose ``fn`` has signature::

        scan(state, monitor, ops, share, plans, lowered, xs)
            -> (state, monitor, RulebookOut)

    with ``state``/``monitor``/``plans``/``lowered`` leading with K,
    ``ops``/``share`` fleet-wide, and ``xs`` a :class:`RulebookXs`.
    ``monitor``/``lowered`` are ``None`` when unmonitored.  Like the
    per-chunk rulebook plane, the memo key excludes every capacity (Qb,
    lattice class counts, S): growing a bucket under superchunk re-enters
    the SAME jitted callable with a new shape — one retrace, no new memo
    entry.  Meshed planes are never shared (mesh objects pin device
    orders).
    """
    from .fleet import _shared_trace
    from .multipattern import _Plane, _make_bucket_step

    key = (None if mesh is not None
           else ("rulebook-scan", bspec, cfg, int(k), bool(monitored),
                 float(laplace)))

    def build() -> _Plane:
        plane = _Plane()
        step = _make_bucket_step(bspec, cfg, monitored, laplace)
        n = bspec.n
        if monitored:
            kstep = jax.vmap(
                step, in_axes=(0, 0, 0, None, None, 0, 0, None, None))
        else:
            kstep = jax.vmap(step, in_axes=(0, 0, None, None, 0, None, None))

        def body(ops, share, plans, lowered, carry, x: RulebookXs):
            def run(carry):
                state, monitor = carry
                kk, qb = state.ts.shape[:2]
                if monitored:
                    state, monitor, res, violated, drift, rates, sel = \
                        kstep(state, monitor, x.chunk, ops, share, plans,
                              lowered, x.t0, x.t1)
                else:
                    state, res = kstep(state, x.chunk, ops, share, plans,
                                       x.t0, x.t1)
                    violated = jnp.zeros((kk, qb), bool)
                    drift = jnp.full((kk, qb), NEG_INF, jnp.float32)
                    rates = jnp.zeros((kk, qb, n), jnp.float32)
                    sel = jnp.zeros((kk, qb, n, n), jnp.float32)
                out = RulebookOut(res.full, res.pm, res.overflow,
                                  res.closure, res.neg, violated, drift,
                                  rates, sel)
                return (state, monitor), out

            def skip(carry):
                state, _ = carry
                kk, qb = state.ts.shape[:2]
                out = RulebookOut(
                    *(jnp.zeros((kk, qb), jnp.int32) for _ in range(5)),
                    jnp.zeros((kk, qb), bool),
                    jnp.full((kk, qb), NEG_INF, jnp.float32),
                    jnp.zeros((kk, qb, n), jnp.float32),
                    jnp.zeros((kk, qb, n, n), jnp.float32))
                return carry, out

            return jax.lax.cond(x.enabled, run, skip, carry)

        def scan_fn(state, monitor, ops, share, plans, lowered, xs):
            plane.traces += 1  # python side effect: once per (re)trace
            carry, ys = jax.lax.scan(
                functools.partial(body, ops, share, plans, lowered),
                (state, monitor), xs)
            return carry[0], carry[1], ys

        plane.fn = jax.jit(_shard_rulebook_scan(scan_fn, mesh))
        return plane

    return _shared_trace(key, build)


def _shard_rulebook_scan(fn, mesh):
    """shard_map the rulebook scan over the 1-D "cep" mesh: state and
    per-partition control K-lead, ops/share are fleet-wide (replicated),
    xs chunks lead with (S, K).  Partitions stay independent — zero
    collectives, sharding never changes semantics."""
    if mesh is None:
        return fn
    from jax.sharding import PartitionSpec

    from ..distributed.sharding import CEP_AXIS

    kl = PartitionSpec(CEP_AXIS)
    skl = PartitionSpec(None, CEP_AXIS)
    rep = PartitionSpec()
    xs_spec = RulebookXs(chunk=skl, t0=rep, t1=rep, enabled=rep)
    in_specs = (kl, kl, rep, rep, kl, kl, xs_spec)
    out_specs = (kl, kl, skl)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
