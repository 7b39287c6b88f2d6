"""The paged-attention kernel compiles for a TPU v5e, with no chip attached.

The TPU compiler is installed with JAX and compiles for a described
topology. These compiles catch what interpret mode cannot: block shapes the
Mosaic tiling refuses, VMEM overuse, unsupported dot precisions. The
topology is described inside a fixture (never at import): only one process
at a time may load the TPU library, and test workers import every file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.kernel import paged_attention_kernel

BATCH = 8
PAGE_TOKENS = 16
POOL_PAGES = 512
MAX_PAGES = 32

# (n_heads, kv_heads, head_dim, dtype): the KV geometries of two configs
GEOMETRIES = {
    "minitron8b-f32": (32, 8, 128, jnp.float32),
    "minitron8b-bf16": (32, 8, 128, jnp.bfloat16),
    "glm4_9b-f32": (32, 2, 128, jnp.float32),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_paged_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                          geometry):
    heads, kv_heads, head_dim, dtype = GEOMETRIES[geometry]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    args = (sds((BATCH, heads, head_dim), dtype),
            sds((POOL_PAGES, PAGE_TOKENS, 2, kv_heads, head_dim), dtype),
            sds((BATCH, MAX_PAGES), jnp.int32),
            sds((BATCH,), jnp.int32))
    compiled = jax.jit(paged_attention_kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
