"""The paged-attention kernel compiles for a TPU v5e, with no chip attached.

The TPU compiler is installed with JAX and compiles for a described
topology. These compiles catch what interpret mode cannot: block shapes the
Mosaic tiling refuses, VMEM overuse, unsupported dot precisions. The
topology is described inside a fixture (never at import): only one process
at a time may load the TPU library, and test workers import every file.
"""
import re

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


def _shapes(one_chip, geometry):
    """(q, pool, block tables, lengths) of one geometry on the chip."""
    heads, kv_heads, head_dim, dtype = GEOMETRIES[geometry]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    return (sds((BATCH, heads, head_dim), dtype),
            sds((POOL_PAGES, PAGE_TOKENS, 2, kv_heads, head_dim), dtype),
            sds((BATCH, MAX_PAGES), jnp.int32),
            sds((BATCH,), jnp.int32))


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_paged_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                          geometry):
    args = _shapes(one_chip, geometry)
    compiled = jax.jit(paged_attention_kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture
def short_locations():
    """Source locations of one frame, as the benchmark runs (``bench/
    harness.py``, ``configure_compilation_cache``). Inside a jit, a
    ``pallas_call``'s op takes the name of its location when that holds
    the call stack, and the name of its custom-call target when not."""
    prev = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    try:
        yield
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", prev)


def test_kernel_op_keeps_the_name_the_benchmark_finds(one_chip,
                                                      no_persistent_cache,
                                                      short_locations):
    """The benchmark finds the kernel's device time by the op's name in the
    trace, ``^%tpu_custom_call`` (``KERNELS`` in
    ``bench/drivers/kv_serve.py``); a ``name=`` on the ``pallas_call``
    renames the op and would leave ``paged_attn_roofline`` nothing to read.
    What runs is the jitted program that ``ops.paged_attention`` calls: it
    is compiled here."""
    from repro.kernels.paged_attention import ops
    q, kv, tables, lengths = _shapes(one_chip, "minitron8b-bf16")
    hlo = ops._kernel.lower(q, kv, tables, lengths,
                            interpret=False).compile().as_text()
    names = [line.strip().removeprefix("ROOT ").split(" = ", 1)[0]
             for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert names
    for name in names:
        assert re.match(r"^%tpu_custom_call", name), (
            f"the kernel's op is {name!r}: a benchmark PR must widen KERNELS "
            f"in bench/drivers/kv_serve.py before the pallas_call gets a "
            f"name=")


def test_latent_kernel_compiles_for_v5e_at_deepseek_v3_widths(
        one_chip, no_persistent_cache, short_locations):
    """The latent (MLA) kernel that ``ops.paged_latent_attention`` calls, at
    DeepSeek-V3's widths: 128 query heads over 576-channel latent pages of
    64 tokens in bfloat16, 512-channel values, a 1,024-page pool and a
    batch of two 39k-token sessions. Its op keeps the name that ``KERNELS``
    of ``bench/drivers/kv_serve_mla.py`` finds."""
    from repro.kernels.paged_attention import ops

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    hlo = ops._latent_kernel.lower(
        sds((2, 128, 576), jnp.bfloat16), sds((1024, 64, 576), jnp.bfloat16),
        sds((2, 610), jnp.int32), sds((2,), jnp.int32), value_dim=512,
        scale=0.1352337788608801, interpret=False).compile().as_text()
    names = [line.strip().removeprefix("ROOT ").split(" = ", 1)[0]
             for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert names
    assert all(re.match(r"^%tpu_custom_call", name) for name in names), names
