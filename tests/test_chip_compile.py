"""Compile every Pallas kernel for a described TPU v5e chip (no chip needed).

The TPU compiler is installed with jaxlib's TPU support, so a kernel can be
lowered and compiled for a v5e that is described rather than attached.  That
catches what interpret mode cannot: block shapes Mosaic refuses, and more
VMEM than a kernel may use.  Shapes are the served models' real widths:
qwen2-1.5b verify attention (Hq 12, Hkv 2, D 128, K+1 = 5, Skv 4096) and the
mamba2-370m SSD scan (H 32, P 64, N 128, chunk 256).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all import
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

B, SQ, HQ, HKV, D, SKV, N_SLOTS = 4, 5, 12, 2, 128, 4096, 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _attn_args(one_chip, kv_dtype, paged):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = N_SLOTS + 1 if paged else B
    args = [s((B, SQ, HQ, D), jnp.bfloat16), s((rows, SKV, HKV, D), kv_dtype),
            s((rows, SKV, HKV, D), kv_dtype)]
    if paged:
        args.append(s((B,), jnp.int32))  # slots
    args.append(s((B,), jnp.int32))  # kv_valid
    if kv_dtype == jnp.int8:
        args += [s((rows, HKV), jnp.float32), s((rows, HKV), jnp.float32)]
    return args


KERNELS = {
    "packed": (ops.verify_attention, jnp.bfloat16, False),
    "paged_bf16": (ops.verify_attention_paged, jnp.bfloat16, True),
    "paged_int8": (ops.verify_attention_paged, jnp.int8, True),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_verify_attention_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, kv_dtype, paged = KERNELS[name]
    compiled = jax.jit(fn).lower(*_attn_args(one_chip, kv_dtype, paged)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles_for_v5e(one_chip, no_persistent_cache):
    b, s, h, p, n, chunk = 1, 256, 32, 64, 128, 256

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((b, s, h, p), jnp.bfloat16), sds((b, s, h), jnp.float32),
            sds((h,), jnp.float32), sds((b, s, n), jnp.bfloat16),
            sds((b, s, n), jnp.bfloat16), sds((b, h, p, n), jnp.float32))
    compiled = jax.jit(lambda *a: ops.ssd_scan(*a, chunk=chunk)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
