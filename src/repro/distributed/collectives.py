"""Distributed-optimization collectives: compressed gradient all-reduce.

``compressed_psum_tree``: int8-on-the-wire data-parallel gradient
all-reduce with error feedback.  A ring fp32 all-reduce moves ~8 bytes per
element (4 B reduce-scatter + 4 B all-gather).  We replace it with:

1. add the carried error-feedback residual to the local gradient;
2. quantize to int8 with a *shared* per-tensor scale (``pmax`` of local
   max-abs — one scalar hop);
3. **reduce-scatter via int8 ``all_to_all``** (1 B/element on the wire),
   summing the received shards locally in int32 — no accumulator overflow
   since 512 × 127 « 2³¹;
4. requantize the summed chunk to int8 with a second shared scale and
   **all-gather int8** (1 B/element);
5. dequantize; store the phase-1 quantization error into the residual
   (error feedback compensates it over subsequent steps).

Net wire cost ≈ 2 B/element — a 4× reduction, visible to the dry-run's
collective-bytes parser as ``all-to-all`` + ``all-gather`` of ``s8``
operands instead of ``f32`` all-reduce.  Built with ``shard_map`` so the
collectives are explicit in the lowered HLO.

This is a beyond-paper distributed-optimization feature (recorded in
EXPERIMENTS.md §Perf); default training keeps XLA's fp32 all-reduce.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _compressed_allreduce(x, ef, axis_name: str, n_shards: int):
    """x, ef: identical shape on every shard.  Returns (mean, new_ef)."""
    shape = x.shape
    size = x.size
    x = x.astype(jnp.float32).reshape(-1) + ef.reshape(-1)

    pad = (-size) % n_shards
    xp = jnp.pad(x, (0, pad))
    chunk = xp.size // n_shards

    # Phase 1: shared-scale int8 quantization.
    scale1 = jax.lax.pmax(jnp.max(jnp.abs(xp)) / 127.0, axis_name) + 1e-12
    q1 = jnp.clip(jnp.round(xp / scale1), -127, 127).astype(jnp.int8)
    deq_local = q1.astype(jnp.float32) * scale1
    new_ef = (x - deq_local[:size]).reshape(shape)

    # Phase 2: int8 reduce-scatter (all_to_all + local int32 sum).
    qs = q1.reshape(n_shards, chunk)
    recv = jax.lax.all_to_all(qs, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
    ssum = recv.astype(jnp.int32).sum(axis=0)          # (chunk,) int32
    part = ssum.astype(jnp.float32) * scale1           # summed fp32 chunk

    # Phase 3: requantize + int8 all-gather.
    scale2 = jax.lax.pmax(jnp.max(jnp.abs(part)) / 127.0,
                          axis_name) + 1e-12
    q2 = jnp.clip(jnp.round(part / scale2), -127, 127).astype(jnp.int8)
    gathered = jax.lax.all_gather(q2, axis_name)       # (N, chunk) int8
    out = gathered.astype(jnp.float32).reshape(-1)[:size] * scale2
    return (out / n_shards).reshape(shape), new_ef


def compressed_psum_tree(grads, ef_tree, mesh: Mesh, axis: str = "data"
                         ) -> Tuple[Any, Any]:
    """Leaf-wise compressed all-reduce (mean) over mesh axis ``axis``.

    Gradients are expected replicated over the other mesh axes and holding
    per-shard partial sums along ``axis`` (the state right after a
    per-shard backward pass under shard_map-style DP).
    """
    n_shards = mesh.shape[axis]
    leaves, treedef = jax.tree.flatten(grads)
    ef_leaves = (jax.tree.leaves(ef_tree) if ef_tree != () else
                 [jnp.zeros(l.shape, jnp.float32) for l in leaves])

    def body(*args):
        n = len(args) // 2
        gs, efs = args[:n], args[n:]
        outs, nefs = [], []
        for g, e in zip(gs, efs):
            o, ne = _compressed_allreduce(g, e, axis, n_shards)
            outs.append(o)
            nefs.append(ne)
        return tuple(outs) + tuple(nefs)

    specs = tuple(P() for _ in range(2 * len(leaves)))
    fn = jax.shard_map(body, mesh=mesh, in_specs=specs, out_specs=specs,
                       check_vma=False)
    res = fn(*leaves, *ef_leaves)
    n = len(leaves)
    return (jax.tree.unflatten(treedef, res[:n]),
            jax.tree.unflatten(treedef, res[n:]))
