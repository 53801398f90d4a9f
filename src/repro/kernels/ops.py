"""jit'd public wrappers around the Pallas kernels (layout packing + vjp-free
serving entry points).  Each op has a pure-jnp oracle in ref.py; tests sweep
shapes/dtypes in interpret mode."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan import ssd_scan_chunked
from repro.kernels.verify_attn import verify_attention_packed
from repro.kernels.verify_attn import verify_attention_paged as _paged_kernel


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def verify_attention(
    q: jax.Array,        # (B, Sq, Hq, D)
    k: jax.Array,        # (B, Skv, Hkv, D)
    v: jax.Array,
    kv_valid: jax.Array,  # (B,)
    *,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """SLED verification attention (see verify_attn.py for the TPU design)."""
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    # pack (Sq, G) into MXU rows, grouped per kv head: row r = i*G + g
    qp = q.reshape(B, Sq, Hkv, G, D).transpose(0, 2, 1, 3, 4).reshape(B, Hkv, Sq * G, D)
    o = verify_attention_packed(qp, k, v, kv_valid.astype(jnp.int32), sq=Sq,
                                block_k=block_k, interpret=interpret)
    return o.reshape(B, Hkv, Sq, G, D).transpose(0, 2, 1, 3, 4).reshape(B, Sq, Hq, D)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def verify_attention_paged(
    q: jax.Array,         # (B, Sq, Hq, D)
    k_pool: jax.Array,    # (n_slots+1, Skv, Hkv, D) — PagedKVCache pool rows
    v_pool: jax.Array,
    slots: jax.Array,     # (B,) int32 pool row per batch entry
    kv_valid: jax.Array,  # (B,)
    k_scale: Optional[jax.Array] = None,  # (n_slots+1, Hkv) f32 — required
    v_scale: Optional[jax.Array] = None,  # when the pool is int8
    *,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Slot-indexed verification attention straight out of the cache pool —
    the scalar-prefetched index maps pick pool row ``slots[b]`` per chunk,
    so no gathered dense K/V ever exists (see verify_attn.py).  An int8 pool
    additionally takes its per-(slot, head) dequant scales; tiles are
    dequantized in-kernel, never as a bf16 pool copy."""
    B, Sq, Hq, D = q.shape
    Hkv = k_pool.shape[2]
    G = Hq // Hkv
    qp = q.reshape(B, Sq, Hkv, G, D).transpose(0, 2, 1, 3, 4).reshape(B, Hkv, Sq * G, D)
    o = _paged_kernel(qp, k_pool, v_pool, slots.astype(jnp.int32),
                      kv_valid.astype(jnp.int32), sq=Sq, block_k=block_k,
                      interpret=interpret, k_scale=k_scale, v_scale=v_scale)
    return o.reshape(B, Hkv, Sq, G, D).transpose(0, 2, 1, 3, 4).reshape(B, Sq, Hq, D)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,    # (B, S, H, P)
    dt: jax.Array,   # (B, S, H) post-softplus fp32
    A: jax.Array,    # (H,) negative fp32
    Bm: jax.Array,   # (B, S, N)
    Cm: jax.Array,   # (B, S, N)
    h0: Optional[jax.Array] = None,
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Mamba2 SSD over a full sequence (chunked kernel). Returns (y, h_final)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((B, H, P, N), jnp.float32)
    return ssd_scan_chunked(x, dt, A, Bm, Cm, h0, chunk=chunk, interpret=interpret)
