"""Compile-only checks for the TPU: the join kernels and the monitored fleet
step, compiled for a described (not attached) v5e chip.

Interpret mode proves the kernels' results; it cannot prove that the TPU
compiler (Mosaic) accepts them.  These tests lower each join kernel at the
widths the engine uses, alone and under the ``vmap`` the engine applies
(over K partitions in the fleet, over (K, Q) cells in the rulebook), and
one whole monitored fleet step with the Pallas backend, for a v5e.  Nothing
runs, so they need no chip.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file.  All such tests stay in this one file so
that one worker loads the library.
"""

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels.window_join import (window_join_count_pallas,
                                       window_join_packed_pallas,
                                       window_join_pallas,
                                       window_join_rowcount_pallas)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A persistent-cache entry written for a described chip cannot be read
    # back without one; keep these compiles out of any cache.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    """Compile ``fn`` for the described chip; return the compiled text."""
    specs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes)
    return jax.jit(fn).lower(*specs).compile().as_text()


# name -> (entry point, C, M, B, takes validity masks).  Widths are the
# engine's: the quickstart SEQ-4 order plan (packed, m_cap 2048 x b_cap
# 128), its tree plan (unpacked, match set x match set), the rulebook's
# Kleene/negation post-block (rowcount, 256 x 128).
KERNELS = {
    "packed": (window_join_packed_pallas, 10, 2048, 128, True),
    "unpacked": (window_join_pallas, 11, 2048, 2048, False),
    "rowcount": (window_join_rowcount_pallas, 9, 256, 128, False),
    "count": (window_join_count_pallas, 11, 2048, 2048, False),
}

# vmap levels, outermost first: the fleet maps K partitions, the rulebook
# K partitions of Q rule cells.  Both engines share the thresholds across
# the outermost level (trace constants in the fleet, per-rule data in the
# rulebook), so those enter unbatched there.
BATCHING = {
    "alone": (),
    "fleet": (16,),
    "rulebook": (4, 8),
}


def _kernel_operands(C, M, B, validity):
    ops_dtype = jnp.int8 if validity else jnp.int32
    shapes = [((C, M), jnp.float32), ((C, B), jnp.float32),
              ((C,), ops_dtype), ((C,), jnp.float32)]
    if validity:
        shapes += [((M,), jnp.bool_), ((B,), jnp.bool_)]
    return shapes


@pytest.mark.parametrize("batching", sorted(BATCHING))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_join_kernel_compiles_for_v5e(kernel, batching, one_chip):
    entry, C, M, B, validity = KERNELS[kernel]
    levels = BATCHING[batching]
    operands = _kernel_operands(C, M, B, validity)
    fn = entry
    for depth in reversed(range(len(levels))):
        in_axes = [0] * len(operands)
        if depth == 0:
            in_axes[3] = None  # thresholds: shared by the outer level
        fn = jax.vmap(fn, in_axes=tuple(in_axes))
    shapes = []
    for i, (shape, dtype) in enumerate(operands):
        lead = levels[1:] if i == 3 else levels
        shapes.append(jax.ShapeDtypeStruct(tuple(lead) + shape, dtype))
    text = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in text  # the Pallas kernel, not the jnp ref


@pytest.mark.parametrize("bm", autotune.BLOCK_M_CANDIDATES)
@pytest.mark.parametrize("bb", autotune.BLOCK_B_CANDIDATES)
def test_packed_kernel_compiles_for_every_autotune_tile(bm, bb, one_chip):
    """Any tile the autotuner may write into its table must compile."""
    C, M, B = 10, 2048, 512
    fn = functools.partial(window_join_packed_pallas, block_m=bm,
                           block_b=bb)
    shapes = [jax.ShapeDtypeStruct(s, d)
              for s, d in _kernel_operands(C, M, B, True)]
    assert "tpu_custom_call" in _compile(fn, shapes, one_chip)


@pytest.mark.parametrize("kind", ["order", "tree"])
def test_monitored_fleet_step_compiles_for_v5e(kind, one_chip):
    """The whole K = 16 monitored step of ``cep.open`` on the quickstart
    pattern, with the Pallas backend: finds whatever else in the step the
    TPU compiler refuses."""
    from repro.core.adaptation import make_planner
    from repro.core.decision import InvariantPolicy
    from repro.core.engine import (Chunk, EngineConfig,
                                   make_monitored_process)
    from repro.core.fleet import FleetEngine, prime_invariant_policies
    from repro.core.patterns import chain_predicates, seq_pattern

    k, cap = 16, 512
    pattern = seq_pattern([0, 1, 2, 3], window=4.0,
                          predicates=chain_predicates([0, 1, 2, 3],
                                                      theta=-0.3))
    fleet = FleetEngine(kind, pattern, k,
                        EngineConfig(b_cap=128, m_cap=2048,
                                     backend="pallas"))
    planner = make_planner("greedy" if kind == "order" else "zstream")
    plan0, low, _ = prime_invariant_policies(
        pattern, planner, [InvariantPolicy(k=1, d=0.0) for _ in range(k)],
        (None, None))
    S = jax.ShapeDtypeStruct
    chunk = Chunk(type_id=S((k, cap), jnp.int32),
                  ts=S((k, cap), jnp.float32),
                  attr=S((k, cap, pattern.n_attrs), jnp.float32),
                  valid=S((k, cap), jnp.bool_))
    kvec = S((k,), jnp.float32)
    args = (jax.eval_shape(fleet.init_state),
            jax.eval_shape(fleet.init_monitor),
            chunk,
            np.asarray(fleet.plans_to_array(plan0)),
            low.device(),
            kvec, kvec, kvec, kvec)
    step = jax.vmap(make_monitored_process(fleet.base.process_fn,
                                           fleet.base.spec))
    text = _compile(step, args, one_chip)
    assert "tpu_custom_call" in text
