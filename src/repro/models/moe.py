"""Fine-grained mixture-of-experts FFN (DeepSeekMoE / DBRX style).

Shared experts (always active) + top-k routed experts with sort-based
capacity dispatch:

1. router logits -> fp32 softmax -> top-k (weight renormalized);
2. flatten the (token, slot) assignments, sort by expert id, rank within
   each expert group and drop overflow beyond capacity ``C`` (static shape);
3. gather tokens into an ``(E, C, D)`` buffer;
4. batched per-expert SwiGLU via ``(E, C, D) x (E, D, F)`` einsums;
5. weighted scatter-add back to token order.

Two execution paths:

* **dense/pjit** (no mesh, or no expert-parallel axis): the steps above as
  plain jnp — used by CPU smoke tests and single-device runs.
* **explicit expert parallelism** (`shard_map`): XLA's SPMD partitioner
  cannot shard a *global* sort/scatter dispatch — left to pjit it
  all-gathers the token stream per shard (the dry-run measured a 3.7
  TB/device program for deepseek-moe train_4k).  Under ``shard_map`` each
  data shard dispatches its LOCAL tokens into per-expert buffers and a
  single ``all_to_all`` over the ``model`` axis routes them to their
  expert's owner — the canonical GShard pattern, with wire cost
  ``T_local · top_k · D`` per direction per layer.

The layer returns the per-expert token load — the "arrival rate" statistic
that the adaptive placement governor (``repro.adaptive``) monitors with the
paper's invariant machinery — plus the Switch-style load-balance auxiliary
loss.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed.sharding import current_rules, logical_constraint as lc
from .config import ModelConfig
from .layers import ffn_defs, swiglu
from .params import ParamDef


def moe_defs(cfg: ModelConfig) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    out = {
        "router": ParamDef((d, e), ("embed", "experts"), scale=0.02),
        "w_gate": ParamDef((e, d, f), ("experts", "embed", "ff")),
        "w_up": ParamDef((e, d, f), ("experts", "embed", "ff")),
        "w_down": ParamDef((e, f, d), ("experts", "ff", "embed")),
    }
    if cfg.n_shared_experts > 0:
        # Shared experts fused into one wide SwiGLU.
        out["shared"] = ffn_defs(cfg, d_ff=cfg.n_shared_experts * f)
    return out


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = math.ceil(n_tokens * cfg.top_k / cfg.n_experts
                  * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)  # round up to 8 for lane alignment


def moe_ffn(x: jax.Array, p: dict, cfg: ModelConfig,
            expert_perm: jax.Array | None = None
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, D) -> (out, aux_loss, expert_load (E,)).

    ``expert_perm`` (optional, (E,) i32) applies a logical->physical expert
    relabeling before dispatch — the adaptive placement governor's output.
    Routing decisions are unaffected (weights follow the permutation); only
    *where* each expert's tokens land changes.
    """
    rules = current_rules()
    if (rules is not None and rules.mesh is not None
            and rules.mesh.shape.get("model", 1) > 1
            and cfg.n_experts % rules.mesh.shape["model"] == 0):
        mesh = rules.mesh
        n_dp = 1
        for a in ("pod", "data"):
            n_dp *= mesh.shape.get(a, 1)
        if x.shape[0] % n_dp == 0:
            return _moe_ffn_ep(x, p, cfg, mesh, expert_perm)
    return _moe_ffn_dense(x, p, cfg, expert_perm)


def _moe_ffn_dense(x, p, cfg, expert_perm=None):
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    C = capacity(cfg, T)
    xt = x.reshape(T, D)

    logits = jnp.einsum("td,de->te", xt, p["router"].astype(x.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # (T, E)
    top_w, top_e = jax.lax.top_k(probs, K)                        # (T, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    if expert_perm is not None:
        top_e = jnp.take(expert_perm, top_e)

    # Load-balance auxiliary loss (Switch): E * sum_e f_e * P_e.
    mean_probs = probs.mean(axis=0)                               # (E,)
    frac = jnp.zeros(E, jnp.float32).at[top_e.reshape(-1)].add(
        1.0 / (T * K))
    aux = E * jnp.sum(frac * mean_probs)
    expert_load = frac * T * K                                    # tokens/e

    # ---- sort-based dispatch -------------------------------------------
    flat_e = top_e.reshape(-1)                                    # (T*K,)
    flat_w = top_w.reshape(-1).astype(x.dtype)
    flat_t = jnp.repeat(jnp.arange(T), K)

    order = jnp.argsort(flat_e)                                   # stable
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    starts = jnp.searchsorted(se, jnp.arange(E))                  # (E,)
    rank = jnp.arange(T * K) - starts[se]
    keep = rank < C
    dest = jnp.where(keep, se * C + rank, E * C)                  # drop slot

    buf = jnp.zeros((E * C, D), x.dtype).at[dest].set(
        xt[st], mode="drop").reshape(E, C, D)
    buf = lc(buf, "experts", "expert_cap", "act_embed")

    # ---- per-expert SwiGLU ---------------------------------------------
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(x.dtype))
    h = jax.nn.silu(g) * u
    h = lc(h, "experts", "expert_cap", "ff")
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(x.dtype))
    out_buf = lc(out_buf, "experts", "expert_cap", "act_embed")

    # ---- weighted combine ----------------------------------------------
    flat_out = out_buf.reshape(E * C, D)
    vals = jnp.take(flat_out, jnp.minimum(dest, E * C - 1), axis=0)
    vals = jnp.where(keep[:, None], vals, 0.0) * sw[:, None]
    out = jnp.zeros((T, D), x.dtype).at[st].add(vals)

    if cfg.n_shared_experts > 0:
        out = out + swiglu(x, p["shared"]).reshape(T, D)

    return (lc(out.reshape(B, S, D), "batch", "seq", "act_embed"),
            aux.astype(jnp.float32), expert_load)


# ---------------------------------------------------------------------------
# Explicit expert parallelism (shard_map) — see module docstring.
# ---------------------------------------------------------------------------


def _local_dispatch(xt, probs, top_w, top_e, E, K, C, dtype):
    """Sort-based dispatch of LOCAL tokens into (E, C, D) buffers."""
    T, D = xt.shape
    flat_e = top_e.reshape(-1)
    flat_w = top_w.reshape(-1).astype(dtype)
    flat_t = jnp.repeat(jnp.arange(T), K)
    order = jnp.argsort(flat_e)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    starts = jnp.searchsorted(se, jnp.arange(E))
    rank = jnp.arange(T * K) - starts[se]
    keep = rank < C
    dest = jnp.where(keep, se * C + rank, E * C)
    buf = jnp.zeros((E * C, D), dtype).at[dest].set(
        xt[st], mode="drop").reshape(E, C, D)
    return buf, (se, st, sw, keep, dest)


def _moe_ffn_ep(x, p, cfg: ModelConfig, mesh, expert_perm=None):
    """Expert-parallel MoE with explicit all-to-all over the model axis.

    Per shard: local top-k routing -> local (E, C_loc, D) buffers ->
    all_to_all sends each expert group to its owner -> local-expert SwiGLU
    over (E_loc, n_ep*C_loc, D) -> reverse all_to_all -> weighted combine.
    """

    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    n_ep = mesh.shape["model"]
    E_loc = E // n_ep
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_dp = 1
    for a in batch_axes:
        n_dp *= mesh.shape[a]
    B_loc = B // n_dp if B % n_dp == 0 else B
    T_loc = B_loc * S
    adt = x.dtype

    perm = (expert_perm if expert_perm is not None
            else jnp.arange(E, dtype=jnp.int32))

    # §Perf lever: dispatch from sequence-sharded tokens.  Activations are
    # replicated over the model axis, so each model shard can own 1/n_ep
    # of the local tokens: the dispatch all_to_all payload shrinks n_ep×
    # at the cost of one output all-gather over "model".
    seq_shard = cfg.moe_seq_shard and (T_loc % n_ep == 0)
    T_disp = T_loc // n_ep if seq_shard else T_loc
    C = capacity(cfg, T_disp)

    def local_fn(x_loc, router, wg, wu, wd, perm_):
        # x_loc: (B_loc, S, D); router: (D, E) replicated;
        # wg/wu/wd: (E_loc, D, F) local experts.
        xt = x_loc.reshape(-1, D)
        if seq_shard:
            me = jax.lax.axis_index("model")
            xt = jax.lax.dynamic_slice_in_dim(xt, me * T_disp, T_disp, 0)
        logits = jnp.einsum("td,de->te", xt, router.astype(adt))
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        top_w, top_e = jax.lax.top_k(probs, K)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        top_e = jnp.take(perm_, top_e)

        # Statistics (summed over data; and over model when seq-sharded).
        mean_probs = probs.mean(axis=0)
        frac = jnp.zeros(E, jnp.float32).at[top_e.reshape(-1)].add(
            1.0 / (T_disp * K))
        aux = E * jnp.sum(frac * mean_probs)
        load_loc = frac * T_disp * K
        stat_axes = batch_axes + (("model",) if seq_shard else ())
        if stat_axes:
            aux = jax.lax.pmean(aux, stat_axes)
            load = jax.lax.psum(load_loc, stat_axes)
        else:
            load = load_loc

        buf, (se, st, sw, keep, dest) = _local_dispatch(
            xt, probs, top_w, top_e, E, K, C, adt)

        # (E, C, D) -> (n_ep, E_loc*C, D) -> all_to_all -> peers' tokens
        # for MY experts: (n_ep, E_loc*C, D).
        send = buf.reshape(n_ep, E_loc * C, D)
        recv = jax.lax.all_to_all(send, "model", split_axis=0,
                                  concat_axis=0, tiled=False)
        work = recv.reshape(n_ep, E_loc, C, D).transpose(1, 0, 2, 3) \
            .reshape(E_loc, n_ep * C, D)

        g = jnp.einsum("ecd,edf->ecf", work, wg.astype(adt))
        u = jnp.einsum("ecd,edf->ecf", work, wu.astype(adt))
        h = jax.nn.silu(g) * u
        out_w = jnp.einsum("ecf,efd->ecd", h, wd.astype(adt))

        # Reverse route.
        back = out_w.reshape(E_loc, n_ep, C, D).transpose(1, 0, 2, 3) \
            .reshape(n_ep, E_loc * C, D)
        ret = jax.lax.all_to_all(back, "model", split_axis=0,
                                 concat_axis=0, tiled=False)
        flat_out = ret.reshape(E * C, D)

        vals = jnp.take(flat_out, jnp.minimum(dest, E * C - 1), axis=0)
        vals = jnp.where(keep[:, None], vals, 0.0) * sw[:, None]
        out = jnp.zeros((T_disp, D), adt).at[st].add(vals)
        if seq_shard:
            out = jax.lax.all_gather(
                out, "model", axis=0, tiled=True)  # (T_loc, D)
        return out.reshape(x_loc.shape), aux, load

    bspec = batch_axes[0] if len(batch_axes) == 1 else batch_axes
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(bspec, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None), P(None)),
        out_specs=(P(bspec, None, None), P(), P()),
        check_vma=False)
    out, aux, load = fn(x, p["router"], p["w_gate"], p["w_up"],
                        p["w_down"], perm)

    if cfg.n_shared_experts > 0:
        out = out + swiglu(x, p["shared"])
    return (lc(out, "batch", "seq", "act_embed"), aux.astype(jnp.float32),
            load)
