"""Compile the gossip-mix Pallas kernels for a described TPU v5e (2x2)
without a chip: shapes only, nothing runs. The TPU compiler refuses here
what interpret mode cannot see (scalar-prefetch operands that overflow SMEM,
tiles that overflow VMEM, slices not aligned to the tiling), so these tests
guard the chip path at CPU cost.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and the worker given this file
is the one that loads it. All chip-compile tests live in this one file for
the same reason.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gossip_mix import gossip_mix_gather, gossip_mix_matmul

# MNIST CNN parameter count: the row width one gossip mix moves per vehicle
PARAMS = 21_840


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k_out,k_in,p", [
    (100, 100, 16_000),      # the paper fleet's full gossip mix
    (100, 25, PARAMS),       # one shard's column block, 4-way sharded mix
    (1024, 1024, PARAMS),    # a thousand-vehicle fleet, dense contacts
])
def test_gossip_mix_matmul_compiles_for_v5e(one_chip, k_out, k_in, p):
    compiled = jax.jit(gossip_mix_matmul).lower(
        _spec((k_out, k_in), jnp.float32, one_chip),
        _spec((k_in, p), jnp.float32, one_chip)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("k_out,k_in,d", [
    (100, 100, 12),          # the paper fleet, sparse contacts
    (100, 25, 12),           # sharded: global rows gather one shard's sources
    (1024, 1024, 12),        # the fleet size the sparse format exists for
])
def test_gossip_mix_gather_compiles_for_v5e(one_chip, k_out, k_in, d):
    compiled = jax.jit(gossip_mix_gather).lower(
        _spec((k_out, d), jnp.int32, one_chip),
        _spec((k_out, d), jnp.float32, one_chip),
        _spec((k_in, PARAMS), jnp.float32, one_chip)).compile()
    _assert_kernel(compiled)
