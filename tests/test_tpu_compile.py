"""The main path's Pallas kernels compile for a described TPU v5e chip.

Nothing runs: each test lowers and compiles one kernel at the widths the
chip smoke run uses (SIFT-shaped, D = 128) against a ``v5e:2x2`` topology
that is described, not attached.  That is what refuses a block shape off
the 8×128 tiling, an op Mosaic cannot lower, or more VMEM than the kernel
may use, before any chip time is spent.  The topology is described inside
a module-scoped fixture (never at import), so under several test workers
only the one that runs this file loads the TPU compiler.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import beam, distance, topk

D = 128
R = 64  # IndexConfig().degree
WIDTH = 64
N_ENTRIES = 17  # GlobalIndex.entry_points(16)
FUSED_N = 86015  # chip_smoke.FUSED_N: largest shard all three stages hold
F32_MAX_N = 184319  # largest f32/bf16 shard pallas_vmem_bytes admits
FUSED_Q = 32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_pairwise_distance_f32_compiles(one_chip):
    f = functools.partial(distance.pairwise_distance_pallas, metric="l2")
    jax.jit(f).lower(_spec(one_chip, (512, D), jnp.float32),
                     _spec(one_chip, (8192, D), jnp.float32)).compile()


def test_pairwise_distance_u8_compiles(one_chip):
    f = functools.partial(distance.pairwise_distance_u8_pallas,
                          metric="l2", d_real=D)
    jax.jit(f).lower(_spec(one_chip, (512, D), jnp.uint8),
                     _spec(one_chip, (8192, D), jnp.uint8),
                     _spec(one_chip, (1, 1), jnp.float32),
                     _spec(one_chip, (1, 1), jnp.float32)).compile()


def test_knn_compiles_at_cagra_build_shape(one_chip):
    """The CAGRA kNN-graph build: k = L + 1 = 129 over a 75k-row shard,
    4096-row query blocks (``cagra.build_knn_graph``)."""
    f = functools.partial(topk.knn_pallas, k=129, metric="l2",
                          n_real=75000)
    jax.jit(f).lower(_spec(one_chip, (4096, D), jnp.float32),
                     _spec(one_chip, (75008, D), jnp.float32)).compile()


def _compile_fused_beam(sharding, n, stage):
    dt = {"f32": jnp.float32, "bf16": jnp.bfloat16, "uint8": jnp.uint8}[stage]
    rerank = stage == "uint8"
    assert beam.pallas_vmem_bytes(
        n, D, R, dt, dx=D if rerank else None) <= beam.VMEM_LIMIT_BYTES
    args = [
        _spec(sharding, (n, D), dt),
        _spec(sharding, (n, R), jnp.int32),
        _spec(sharding, (N_ENTRIES,), jnp.int32),
        _spec(sharding, (FUSED_Q, D), dt),
        _spec(sharding, (), jnp.float32),
        _spec(sharding, (), jnp.float32),
        _spec(sharding, (n, D), jnp.float32) if rerank else None,
        _spec(sharding, (FUSED_Q, D), jnp.float32) if rerank else None,
    ]
    compiled = beam._fused_beam_pallas.lower(
        *args, k=40 if rerank else 10, width=WIDTH, n_iters=96,
        expand=beam.DEFAULT_EXPAND, metric="l2",
        rerank_k=10 if rerank else None, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("stage", ["f32", "bf16", "uint8"])
def test_fused_beam_compiles_at_smoke_size(one_chip, stage):
    """The Pallas ``fused_beam`` at the largest shard its VMEM model
    admits for every stage (uint8 with the fused re-rank binds)."""
    _compile_fused_beam(one_chip, FUSED_N, stage)


def test_fused_beam_f32_compiles_at_vmem_bound(one_chip):
    """The f32 stage at the largest shard the ``pallas`` backend admits:
    the VMEM model's bound is one the compiler accepts."""
    assert beam.pallas_vmem_bytes(F32_MAX_N + 1, D, R, jnp.float32) \
        > beam.VMEM_LIMIT_BYTES
    _compile_fused_beam(one_chip, F32_MAX_N, "f32")
