"""Compile for a described TPU v5e chip (no chip needed): every Pallas
kernel, and the served programs that write the KV pool.

The TPU compiler is installed with jaxlib's TPU support, so a kernel can be
lowered and compiled for a v5e that is described rather than attached.  That
catches what interpret mode cannot: block shapes Mosaic refuses, and more
VMEM than a kernel may use.  Shapes are the served models' real widths:
qwen2-1.5b verify attention (Hq 12, Hkv 2, D 128, K+1 = 5, Skv 4096) and the
mamba2-370m SSD scan (H 32, P 64, N 128, chunk 256).  The pool programs
(verify, extend, admission write) are compiled from the registered configs
with the benchmark cells' pool geometry, and their HLO says whether they
update the pool in place.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all import
this file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.core import verification
from repro.core.engine import VerifySteps, pool_copies
from repro.kernels import ops
from repro.models.kvcache import write_row
from repro.models.model_zoo import build_model

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


# The served programs that write the KV pool, compiled at published widths
# with the cells' pool geometry (768 positions, chunk 32, k_max 4):
# {config: (changes, pool rows, verify bucket)}.  phi3 keeps its 32 layers:
# the pool copy it rematerialises comes from HBM pressure at full depth.
POOL_CELLS = {
    "qwen2-1.5b": ({"num_layers": 2}, 129, 16),
    "phi3-mini-3.8b": ({}, 9, 1),
    "moonlight-16b-a3b": ({"num_layers": 2, "held_experts": (0, 16)}, 97, 16),
}


def _pool_program(name, program, one_chip):
    """(compiled program, pool spec) for the verify, extend or admission
    write program of config ``name`` as the engine serves it."""
    changes, rows, bucket = POOL_CELLS[name]
    model = build_model(dataclasses.replace(get_config(name), **changes))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    pool = on_chip(model.make_cache(rows, 768, spec_only=True, attn_chunk=32))
    if program == "write":
        row = on_chip(model.make_cache(1, 768, spec_only=True, attn_chunk=32))
        return write_row.lower(pool, i32(), row).compile(), pool
    params = on_chip(model.init_params_spec())
    steps = VerifySteps(model, scratch_slot=rows - 1, attn_chunk=32)
    if program == "extend":
        return steps.extend.lower(params, pool, i32(1), i32(1, 5), i32(1)).compile(), pool
    batch = verification.make_verify_batch(
        jnp.zeros((bucket,), jnp.int32), jnp.zeros((bucket, 4), jnp.int32),
        jnp.zeros((bucket,), jnp.int32), seed=np.uint32(0))
    return steps.verify.lower(params, pool, i32(bucket), on_chip(batch)).compile(), pool


@pytest.mark.parametrize("program", ["verify", "extend", "write"])
@pytest.mark.parametrize("name", sorted(POOL_CELLS))
def test_pool_programs_update_the_pool_in_place(name, program, one_chip, no_persistent_cache):
    """Every program that writes the pool takes it donated (each leaf's
    output aliases its input) and, where the leaf's minor axis is 128 wide,
    copies no pool leaf: qwen2's head-major K/V and Moonlight's ``ckv``.
    phi3's 96-wide heads still relayout (4 copies), but none is a remat."""
    compiled, pool = _pool_program(name, program, one_chip)
    hlo = compiled.as_text()
    header = hlo.split("\n", 1)[0]
    assert "input_output_alias=" in header
    aliases = header.split("input_output_alias=", 1)[1].split("entry_computation_layout", 1)[0]
    assert aliases.count("-alias)") == len(jax.tree.leaves(pool))
    copies = pool_copies(hlo, pool)
    if name == "qwen2-1.5b":
        assert copies == []
    elif name == "moonlight-16b-a3b":
        assert pool_copies(hlo, {"ckv": pool["ckv"]}) == []
    else:
        assert not [c for c in copies if "remat" in c]
