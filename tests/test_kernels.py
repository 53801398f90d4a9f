"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(assignment requirement: per-kernel allclose against ref.py).  Every call
passes ``interpret=True``: the kernels default to compiling for the TPU, and
tests/test_chip_compile.py covers that path for a described chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

ATTN_SHAPES = [
    # (B, Sq, Hq, Hkv, Skv, D, block_k)
    (2, 5, 8, 2, 128, 64, 32),
    (1, 3, 4, 4, 64, 32, 64),      # MHA, block_k == Skv
    (3, 5, 12, 1, 256, 16, 64),    # MQA
    (2, 1, 8, 8, 128, 64, 32),     # plain decode (Sq=1)
    (1, 8, 16, 2, 512, 128, 128),  # deep GQA group
    (2, 5, 8, 2, 80, 64, 64),      # Skv % block_k != 0 (partial tail chunk)
    (1, 4, 8, 4, 100, 32, 32),     # partial tail chunk, GQA
]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_verify_attention_matches_oracle(shape, dtype):
    B, Sq, Hq, Hkv, Skv, D, blk = shape
    ks = jax.random.split(jax.random.key(sum(shape)), 4)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, D), dtype)
    kv_valid = jax.random.randint(ks[3], (B,), Sq, Skv + 1)
    out = ops.verify_attention(q, k, v, kv_valid, block_k=blk, interpret=True)
    want = ref.verify_attention_ref(q, k, v, kv_valid)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_verify_attention_matches_model_flash():
    """The kernel and the model's XLA flash path agree on cache semantics."""
    from repro.models.layers import flash_attention
    B, Sq, Hq, Hkv, Skv, D = 2, 5, 8, 2, 128, 32
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D))
    k = jax.random.normal(ks[1], (B, Skv, Hkv, D))
    v = jax.random.normal(ks[2], (B, Skv, Hkv, D))
    kv_valid = jnp.array([40, 90], jnp.int32)
    q_pos = kv_valid[:, None] - Sq + jnp.arange(Sq)[None]
    a = flash_attention(q, k.swapaxes(1, 2), v.swapaxes(1, 2), q_pos=q_pos,
                        kv_valid=kv_valid, chunk=32)  # the model's K/V are head-major
    b = ops.verify_attention(q, k, v, kv_valid, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)


PAGED_SHAPES = [
    # (n_slots, B, Sq, Hq, Hkv, Skv, D, block_k)
    (6, 3, 5, 8, 2, 128, 64, 32),    # GQA, bucket < pool
    (4, 2, 4, 4, 4, 96, 32, 64),     # MHA, Skv % block_k != 0
    (5, 4, 5, 12, 1, 160, 16, 64),   # MQA, partial tail chunk
    (3, 3, 2, 16, 2, 64, 32, 64),    # deep GQA group, block_k == Skv
]


@pytest.mark.parametrize("shape", PAGED_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_verify_attention_paged_equivalence_sweep(shape, dtype):
    """Slot-indexed pool kernel == gather + packed kernel == XLA reference,
    across uneven per-slot lengths, duplicate scratch-slot padding rows, and
    GQA/MQA head counts (interpret mode)."""
    n_slots, B, Sq, Hq, Hkv, Skv, D, blk = shape
    ks = jax.random.split(jax.random.key(sum(shape)), 5)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), dtype)
    k_pool = jax.random.normal(ks[1], (n_slots + 1, Skv, Hkv, D), dtype)
    v_pool = jax.random.normal(ks[2], (n_slots + 1, Skv, Hkv, D), dtype)
    # real rows out of order + the last TWO entries padded with the
    # duplicated scratch slot (the engine's partial-fill convention)
    real = jax.random.permutation(ks[3], n_slots)[: max(B - 2, 1)]
    slots = jnp.concatenate(
        [real, jnp.full((B - real.shape[0],), n_slots)]
    ).astype(jnp.int32)
    kv_valid = jax.random.randint(ks[4], (B,), Sq, Skv + 1)

    out_paged = ops.verify_attention_paged(
        q, k_pool, v_pool, slots, kv_valid, block_k=blk, interpret=True
    )
    out_gather = ops.verify_attention(
        q, k_pool[slots], v_pool[slots], kv_valid, block_k=blk, interpret=True
    )
    want = ref.verify_attention_paged_ref(q, k_pool, v_pool, slots, kv_valid)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out_paged, np.float32),
                               np.asarray(out_gather, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(out_paged, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", PAGED_SHAPES)
def test_verify_attention_paged_int8_equivalence_sweep(shape):
    """Dequant-in-kernel int8 pool == XLA oracle (dequantized gather) ==
    dequantize-then-bf16-kernel, across uneven per-slot lengths, duplicate
    scratch-slot padding, and per-(slot, head) scales (interpret mode)."""
    n_slots, B, Sq, Hq, Hkv, Skv, D, blk = shape
    ks = jax.random.split(jax.random.key(sum(shape) + 17), 7)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), jnp.bfloat16)
    kf = jax.random.normal(ks[1], (n_slots + 1, Skv, Hkv, D))
    vf = jax.random.normal(ks[2], (n_slots + 1, Skv, Hkv, D))
    # per-(slot, head) symmetric scales, deliberately non-uniform
    k_scale = jnp.abs(kf).max(axis=(1, 3)) / 127.0 + 1e-6
    v_scale = jnp.abs(vf).max(axis=(1, 3)) / 127.0 + 1e-6
    k_pool = jnp.clip(jnp.round(kf / k_scale[:, None, :, None]), -127, 127).astype(jnp.int8)
    v_pool = jnp.clip(jnp.round(vf / v_scale[:, None, :, None]), -127, 127).astype(jnp.int8)
    real = jax.random.permutation(ks[3], n_slots)[: max(B - 2, 1)]
    slots = jnp.concatenate(
        [real, jnp.full((B - real.shape[0],), n_slots)]
    ).astype(jnp.int32)
    kv_valid = jax.random.randint(ks[4], (B,), Sq, Skv + 1)

    out = ops.verify_attention_paged(
        q, k_pool, v_pool, slots, kv_valid, k_scale, v_scale, block_k=blk,
        interpret=True,
    )
    want = ref.verify_attention_paged_ref(
        q, k_pool, v_pool, slots, kv_valid, k_scale=k_scale, v_scale=v_scale
    )
    # dequantize the gathered rows up front, run the bf16 packed kernel:
    # the in-kernel dequant must change nothing but the HBM stream width
    kd = (k_pool[slots].astype(jnp.float32)
          * k_scale[slots][:, None, :, None]).astype(jnp.bfloat16)
    vd = (v_pool[slots].astype(jnp.float32)
          * v_scale[slots][:, None, :, None]).astype(jnp.bfloat16)
    out_dq = ops.verify_attention(q, kd, vd, kv_valid, block_k=blk, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(out_dq, np.float32), rtol=2e-2, atol=2e-2)


def test_verify_attention_paged_int8_requires_scales():
    n_slots, B, Sq, Hq, Hkv, Skv, D = 3, 2, 2, 4, 2, 64, 32
    q = jnp.zeros((B, Sq, Hq, D), jnp.bfloat16)
    pool = jnp.zeros((n_slots + 1, Skv, Hkv, D), jnp.int8)
    slots = jnp.zeros((B,), jnp.int32)
    kv_valid = jnp.full((B,), Sq, jnp.int32)
    with pytest.raises(ValueError, match="k_scale"):
        ops.verify_attention_paged(q, pool, pool, slots, kv_valid, interpret=True)


def test_verify_attention_partial_tail_chunk_finite():
    """A cache length that is not a block multiple must degrade to masking,
    not crash or leak NaN from the out-of-bounds tail lanes."""
    B, Sq, Hq, Hkv, Skv, D = 2, 5, 8, 2, 80, 32
    ks = jax.random.split(jax.random.key(11), 4)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D))
    k = jax.random.normal(ks[1], (B, Skv, Hkv, D))
    v = jax.random.normal(ks[2], (B, Skv, Hkv, D))
    kv_valid = jnp.asarray([Skv, Sq], jnp.int32)  # full row + minimal row
    out = ops.verify_attention(q, k, v, kv_valid, block_k=64, interpret=True)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    want = ref.verify_attention_ref(q, k, v, kv_valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-3, atol=2e-3)


SSD_SHAPES = [
    # (B, S, H, P, N, chunk)
    (2, 64, 4, 16, 32, 16),
    (1, 128, 2, 8, 16, 32),
    (2, 32, 1, 32, 8, 32),   # single head, chunk == S
    (1, 96, 3, 16, 64, 24),  # odd-ish chunking
    (1, 32, 16, 16, 16, 16),  # two 8-head program groups
]


@pytest.mark.parametrize("shape", SSD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_matches_oracle(shape, dtype):
    B, S, H, P, N, chunk = shape
    ks = jax.random.split(jax.random.key(sum(shape)), 6)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N), dtype)
    Cm = jax.random.normal(ks[4], (B, S, N), dtype)
    h0 = jax.random.normal(ks[5], (B, H, P, N))
    y, hf = ops.ssd_scan(x, dt, A, Bm, Cm, h0, chunk=chunk, interpret=True)
    yw, hw = ref.ssd_scan_ref(x, dt, A, Bm, Cm, h0)
    tol = 4e-2 if dtype == jnp.bfloat16 else 3e-3
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yw, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hw), rtol=tol, atol=tol)


def test_ssd_kernel_matches_model_chunked_path():
    """Kernel == the model's pure-jnp chunked SSD (mamba2.ssd_chunked)."""
    from repro.models.mamba2 import ssd_chunked
    B, S, H, P, N = 2, 64, 4, 16, 32
    ks = jax.random.split(jax.random.key(9), 6)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    h0 = jax.random.normal(ks[5], (B, H, P, N))
    y1, h1 = ops.ssd_scan(x, dt, A, Bm, Cm, h0, chunk=16, interpret=True)
    y2, h2 = ssd_chunked(x, dt, A, Bm, Cm, 16, h0=h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=3e-3, atol=3e-3)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=3e-3, atol=3e-3)
