"""Compile-only checks of the Pallas kernels for a TPU v5e.

Nothing here runs on a chip: each test compiles a kernel at a realistic
shape for a described (not attached) ``v5e:2x2`` topology, which is what
the TPU compiler would refuse (unsupported casts, tiles, too much VMEM)
that interpret mode never sees.  The topology is described inside a
module-scoped fixture, so only the process that runs these tests loads the
TPU library; where it cannot be described, the tests skip.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.ingest_norm.ops import ingest_norm
from repro.kernels.rmsnorm.ops import rmsnorm

# the ingest kernel's temporaries at (256, 224, 224, 3): the HWC-blocked
# layout with C on the lane axis needed 1.82 GB
INGEST_TEMP_BOUND = 0.4e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_ingest_norm_compiles_lane_dense(one_chip):
    compiled = ingest_norm.lower(
        _spec((256, 224, 224, 3), jnp.uint8, one_chip),
        _spec((3,), jnp.float32, one_chip),
        _spec((3,), jnp.float32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= INGEST_TEMP_BOUND, mem.temp_size_in_bytes
    assert mem.output_size_in_bytes == 256 * 3 * 224 * 224 * 4


def test_rmsnorm_compiles(one_chip):
    # granite-8b width, one 8 x 4096-token microbatch
    compiled = rmsnorm.lower(
        _spec((8, 4096, 4096), jnp.bfloat16, one_chip),
        _spec((4096,), jnp.float32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    # granite-8b attention: 32 query heads over 8 kv heads, head_dim 128
    compiled = flash_attention.lower(
        _spec((1, 32, 4096, 128), jnp.bfloat16, one_chip),
        _spec((1, 8, 4096, 128), jnp.bfloat16, one_chip),
        _spec((1, 8, 4096, 128), jnp.bfloat16, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
