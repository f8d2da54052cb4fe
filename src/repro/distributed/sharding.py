"""Logical-axis sharding with divisibility fallback.

Model code annotates tensors with *logical* axis names ("batch", "heads",
"ff", "experts", …).  A ``MeshRules`` table maps logical names to physical
mesh axes; resolution checks divisibility and **falls back to replication**
on any axis that does not divide evenly (e.g. paligemma's 8 query heads or
its single KV head on a 16-way model axis).  Fallbacks are recorded so the
dry-run can report them per cell.

The default rule set implements the production layout of DESIGN.md §5:

* ``batch``    → ("pod", "data")   — data parallelism across pods and rows;
* ``embed``    → "data"            — FSDP: parameters' d_model dim sharded
                                      over the data axis (gathered per layer);
* ``heads`` / ``kv_heads`` / ``ff`` / ``experts`` / ``vocab`` → "model"
                                   — tensor/expert parallelism;
* ``seq``      → None              — sequence kept unsharded by default
                                      (sequence parallelism is opt-in via
                                      ``seq → "model"`` in §Perf experiments);
* activation ``act_embed`` → None  — activations replicated over model axis
                                      after collectives.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AxisVal = Union[None, str, Tuple[str, ...]]


DEFAULT_RULES: Dict[str, AxisVal] = {
    "batch": ("pod", "data"),
    "seq": None,
    "act_embed": None,
    "embed": "data",        # FSDP shard of parameter d_model dims
    "opt_embed": "data",    # ZeRO-1: optimizer-state d_model dims
    "heads": "model",
    "kv_heads": "model",
    "qkv_dim": None,
    "ff": "model",
    "experts": "model",
    "expert_cap": None,
    "vocab": "model",
    "cache_seq": "model",   # decode KV caches: split-T (flash-decoding)
    "layers": None,
    "conv": None,
    "ssm_inner": "model",
    "ssm_state": None,
    "ssm_heads": "model",
    "frontend": None,
    # CEP fleet: the leading K-partition axis of every data-plane tensor.
    # Partitions are independent streams, so this is the one logical axis
    # the CEP runtime shards; everything else stays replicated.
    "cep_partitions": "cep",
}


@dataclasses.dataclass
class MeshRules:
    mesh: Optional[Mesh]
    rules: Dict[str, AxisVal]
    fallbacks: List[str] = dataclasses.field(default_factory=list)

    def axis_size(self, phys: AxisVal) -> int:
        if phys is None or self.mesh is None:
            return 1
        if isinstance(phys, str):
            phys = (phys,)
        size = 1
        for a in phys:
            size *= self.mesh.shape.get(a, 1)
        return size

    def resolve(self, shape: Sequence[int],
                logical: Sequence[Optional[str]],
                tag: str = "") -> PartitionSpec:
        """Logical names -> PartitionSpec with divisibility fallback."""
        assert len(shape) == len(logical), (shape, logical, tag)
        out = []
        used: set = set()
        for dim, name in zip(shape, logical):
            if name is None:
                out.append(None)
                continue
            phys = self.rules.get(name)
            if phys is None:
                out.append(None)
                continue
            phys_t = (phys,) if isinstance(phys, str) else tuple(phys)
            # Drop mesh axes missing from the current mesh (e.g. "pod" on
            # the single-pod mesh) and axes already used by an earlier dim
            # of this tensor (a mesh axis may appear only once per spec —
            # e.g. MoE expert weights (E, D, F) map experts->model and must
            # then leave ff unsharded).
            dropped_dup = [a for a in phys_t
                           if self.mesh is not None
                           and a in self.mesh.shape and a in used]
            phys_t = tuple(a for a in phys_t
                           if (self.mesh is None or a in self.mesh.shape)
                           and a not in used)
            if dropped_dup:
                self.fallbacks.append(
                    f"{tag}: dim {dim} ({name}) axis {dropped_dup} already "
                    "used by an earlier dim -> replicated")
            size = self.axis_size(phys_t)
            if size <= 1:
                out.append(None)
            elif dim % size == 0:
                used.update(phys_t)
                out.append(phys_t[0] if len(phys_t) == 1 else phys_t)
            else:
                self.fallbacks.append(
                    f"{tag}: dim {dim} ({name}) not divisible by "
                    f"{phys_t} ({size}) -> replicated")
                out.append(None)
        return PartitionSpec(*out)

    def sharding(self, shape, logical, tag: str = "") -> NamedSharding:
        assert self.mesh is not None, "sharding requires an active mesh"
        return NamedSharding(self.mesh, self.resolve(shape, logical, tag))


_local = threading.local()


def current_rules() -> Optional[MeshRules]:
    return getattr(_local, "rules", None)


def set_rules(rules: Optional[MeshRules]) -> None:
    _local.rules = rules


@contextlib.contextmanager
def use_rules(mesh: Optional[Mesh],
              overrides: Optional[Dict[str, AxisVal]] = None):
    """Activate a mesh + logical-rule table for model tracing."""
    table = dict(DEFAULT_RULES)
    if overrides:
        table.update(overrides)
    prev = current_rules()
    set_rules(MeshRules(mesh=mesh, rules=table))
    try:
        yield current_rules()
    finally:
        set_rules(prev)


def logical_constraint(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical names; no-op without a mesh."""
    r = current_rules()
    if r is None or r.mesh is None:
        return x
    spec = r.resolve(x.shape, logical, tag="activation")
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(r.mesh, spec))


def logical_sharding(shape, logical, tag: str = "") -> Optional[NamedSharding]:
    r = current_rules()
    if r is None or r.mesh is None:
        return None
    return r.sharding(shape, logical, tag)


# ---------------------------------------------------------------------------
# CEP fleet mesh layer
# ---------------------------------------------------------------------------
#
# The CEP data plane is a pytree whose every leaf leads with the K-partition
# axis (stacked ring buffers, monitor rings, plan rows, lowered invariant
# tensors, per-partition counters).  Partitions are fully independent
# streams, so the fleet maps onto a 1-D device mesh with ONE rule — split K
# over the "cep" axis, replicate the rest — and needs zero collectives.
# The rule lives in DEFAULT_RULES ("cep_partitions") so dry-runs and
# fallback reporting treat the CEP fleet like any other sharded workload.

CEP_AXIS = "cep"


def cep_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    """A 1-D mesh over local devices with the ``cep`` partition axis."""
    import numpy as np

    devs = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"mesh wants {n_devices} devices, only {len(devs)} present")
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (CEP_AXIS,))


def resolve_cep_mesh(mesh, k: int) -> Optional[Mesh]:
    """Normalize the facade's ``mesh=`` config into a fleet mesh.

    Accepts ``None`` (no sharding), ``"auto"`` (all local devices), an
    ``int`` device count, or a prebuilt 1-D :class:`Mesh` carrying a
    ``cep`` axis.  The K-partition axis must divide evenly — an uneven
    split would silently unbalance per-partition semantics, so it raises
    (the logical-rule fallback-to-replication is for model weights, not
    for the stream data plane).
    """
    if mesh is None:
        return None
    if isinstance(mesh, Mesh):
        if CEP_AXIS not in mesh.shape:
            raise ValueError(
                f"fleet mesh must carry a {CEP_AXIS!r} axis; "
                f"got axes {tuple(mesh.shape)}")
        m = mesh
    elif mesh == "auto":
        m = cep_mesh()
    elif isinstance(mesh, int):
        m = cep_mesh(mesh)
    else:
        raise TypeError(f"mesh must be None, 'auto', an int device count "
                        f"or a jax Mesh; got {type(mesh).__name__}")
    d = m.shape[CEP_AXIS]
    if k % d != 0:
        raise ValueError(
            f"K={k} partitions do not divide over {d} devices; choose K "
            f"as a multiple of the mesh size")
    return m


def fleet_pspec(leading_k: bool = True) -> PartitionSpec:
    """The one CEP partition rule as a PartitionSpec tree prefix.

    ``leading_k=True`` shards a leaf's first axis over ``cep`` (state,
    plan rows, lowered tensors, per-partition outputs); ``False`` gives
    the scan layout — a leading superchunk axis, partitions second.
    """
    if leading_k:
        return PartitionSpec(CEP_AXIS)
    return PartitionSpec(None, CEP_AXIS)


def shard_fleet_fn(fn, mesh: Mesh):
    """``shard_map`` a per-chunk fleet step: every arg/out leads with K."""
    return jax.shard_map(fn, mesh=mesh, in_specs=fleet_pspec(),
                         out_specs=fleet_pspec(), check_vma=False)


def shard_fleet_scan(scan_fn, mesh: Mesh):
    """``shard_map`` the superchunk scan.

    Signature: ``scan_fn(buffers, monitor, cur_rows, old_rows, lowered,
    xs) -> (buffers, monitor, ys)``.  State/rows/lowered lead with K;
    ``xs``/``ys`` lead with (S, K) except the shared chunk clock and the
    ``enabled`` gate, which are replicated so every device gates the same
    chunks.  The body is collective-free (partitions are independent), so
    device-local ``lax.cond`` divergence — e.g. pass B running only on
    devices that own a migrating partition — is safe and free.
    """

    from ..core.scan import SuperchunkXs

    k_led = fleet_pspec()
    sk_led = fleet_pspec(leading_k=False)
    rep = PartitionSpec()
    xs_spec = SuperchunkXs(
        chunk=sk_led, t0=rep, t1=rep, enabled=rep,
        born_lo=sk_led, migrating=sk_led, old_sel=sk_led)
    return jax.shard_map(
        scan_fn, mesh=mesh,
        in_specs=(k_led, k_led, k_led, k_led, k_led, xs_spec),
        out_specs=(k_led, k_led, sk_led),
        check_vma=False)
