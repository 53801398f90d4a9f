"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

Every contraction runs at ``Precision.HIGHEST``: on a TPU the default is one
bf16 pass, which would round the oracles' f32 operands (softmax weights, the
SSM state) and leave the ground truth less exact than the kernels it checks.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST

def verify_attention_ref(
    q: jax.Array,        # (B, Sq, Hq, D) — the K+1 verify tokens' queries
    k: jax.Array,        # (B, Skv, Hkv, D) cache (buffer idx == position)
    v: jax.Array,        # (B, Skv, Hkv, D)
    kv_valid: jax.Array,  # (B,) valid entries incl. the Sq new rows
    scale: Optional[float] = None,
) -> jax.Array:
    """Causal-offset attention: query i sits at position kv_valid - Sq + i."""
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, Sq, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bihgd,bjhd->bhgij", qg, k.astype(jnp.float32), precision=_HI) * scale
    j = jnp.arange(Skv)
    q_pos = kv_valid[:, None] - Sq + jnp.arange(Sq)[None]  # (B, Sq)
    mask = j[None, None, :] <= q_pos[:, :, None]  # (B, Sq, Skv)
    s = jnp.where(mask[:, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgij,bjhd->bihgd", p, v.astype(jnp.float32), precision=_HI)
    return o.reshape(B, Sq, Hq, D).astype(q.dtype)


def verify_attention_paged_ref(
    q: jax.Array,         # (B, Sq, Hq, D)
    k_pool: jax.Array,    # (n_slots+1, Skv, Hkv, D) cache-row pool
    v_pool: jax.Array,
    slots: jax.Array,     # (B,) int32 pool row per batch entry
    kv_valid: jax.Array,  # (B,)
    scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,  # (n_slots+1, Hkv) f32 dequant
    v_scale: Optional[jax.Array] = None,  # scales for an int8 pool
) -> jax.Array:
    """Pool-indexed oracle: materialise the gather, then dense attention.

    The Pallas paged kernel must match this bit-for-tolerance — the gather
    here is the very traffic the kernel's scalar-prefetched index maps
    eliminate, but as an oracle it is the cleanest statement of semantics.
    For an int8 pool the oracle does exactly what the kernel refuses to do:
    materialise the dequantized bf16 gather (layers.kv_dequant arithmetic,
    int8 -> f32 * scale -> bf16), then run dense attention over it.
    """
    k = jnp.take(k_pool, slots, axis=0)
    v = jnp.take(v_pool, slots, axis=0)
    if k.dtype == jnp.int8:
        if k_scale is None or v_scale is None:
            raise ValueError("int8 pool oracle requires k_scale/v_scale")
        ks = jnp.take(k_scale, slots, axis=0)[:, None, :, None]  # (B,1,Hkv,1)
        vs = jnp.take(v_scale, slots, axis=0)[:, None, :, None]
        k = (k.astype(jnp.float32) * ks).astype(jnp.bfloat16)
        v = (v.astype(jnp.float32) * vs).astype(jnp.bfloat16)
    return verify_attention_ref(q, k, v, kv_valid, scale=scale)


def ssd_scan_ref(
    x: jax.Array,    # (B, S, H, P)
    dt: jax.Array,   # (B, S, H) fp32, post-softplus
    A: jax.Array,    # (H,) fp32, negative
    Bm: jax.Array,   # (B, S, N)
    Cm: jax.Array,   # (B, S, N)
    h0: Optional[jax.Array] = None,  # (B, H, P, N)
) -> Tuple[jax.Array, jax.Array]:
    """Sequential SSD recurrence — the slow exact oracle."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    h = jnp.zeros((B, H, P, N), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, t):
        xt = x[:, t].astype(jnp.float32)
        dtt = dt[:, t]
        Bt = Bm[:, t].astype(jnp.float32)
        Ct = Cm[:, t].astype(jnp.float32)
        decay = jnp.exp(dtt * A[None])  # (B, H)
        h = decay[..., None, None] * h + jnp.einsum("bh,bn,bhp->bhpn", dtt, Bt, xt, precision=_HI)
        y = jnp.einsum("bn,bhpn->bhp", Ct, h, precision=_HI)
        return h, y

    h, ys = jax.lax.scan(step, h, jnp.arange(S))
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), h
