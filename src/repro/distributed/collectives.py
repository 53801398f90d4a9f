"""Sequence-parallel (context-parallel) attention over a model-axis-sharded
KV cache — jax-native flash-decoding.

Why: GQA archs with few KV heads (granite-34b MQA kv=1, qwen3-moe kv=4,
llava kv=8, qwen1.5 whose 40 heads don't divide the 16-way model axis)
cannot head-shard their KV caches, and a 32k x 128-row cache replicated
over the model axis is tens of GB per device.  Sharding the cache's
SEQUENCE dim over the model axis fits it, at the price of a softmax
combine across shards:

  per shard:  (acc_r, m_r, l_r) = flash(q, K_r, V_r)    # local chunks only
  combine:    m* = pmax_r m_r;  c_r = exp(m_r - m*)
              out = psum_r(acc_r * c_r) / psum_r(l_r * c_r)

This is the TPU/shard_map version of flash-decoding's split-KV reduction
(maps the paper's "batched verification" onto a 2D (request, sequence)
decomposition).  The append of the K+1 fresh rows happens inside the same
shard_map: each shard scatters (mode="drop") the rows that land in its
sequence range — new rows may straddle a shard boundary.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import MeshContext, flash_attention


def sp_append_attend(
    q: jax.Array,       # (B, Sq, Hq, D) — replicated over model axis
    k_cache: jax.Array,  # (B, Hkv, S, D) — S sharded over model axis
    v_cache: jax.Array,
    k_new: jax.Array,   # (B, Hkv, Sq, D) fresh rows (replicated)
    v_new: jax.Array,
    cache_len: jax.Array,   # (B,) committed lengths
    start: jax.Array,       # scalar: the batch's insert position (cache_len[0])
    ctx: MeshContext,
    *,
    causal: bool = True,
    chunk: int = 1024,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (attn_out (B,Sq,Hq,D), k_cache', v_cache')."""
    ax = ctx.model_axis
    tp = ctx.tp
    B, Sq, Hq, D = q.shape
    S = k_cache.shape[2]
    S_loc = S // tp
    bspec = ctx.batch_axes if ctx.batch_axes else None
    chunk = min(chunk, S_loc)

    def f(q, kc, vc, kn, vn, clen, st):
        r = jax.lax.axis_index(ax)
        base = r * S_loc
        # scatter the fresh rows that land in this shard (straddle-safe);
        # negative locals would WRAP under jnp indexing, so route them to an
        # explicit OOB sentinel that mode="drop" discards
        pos = st + jnp.arange(Sq, dtype=jnp.int32) - base  # local positions
        pos = jnp.where((pos >= 0) & (pos < S_loc), pos, S_loc)
        from repro.models.layers import kv_quant
        kc = kc.at[:, :, pos].set(kv_quant(kn, kc.dtype), mode="drop")
        vc = vc.at[:, :, pos].set(kv_quant(vn, vc.dtype), mode="drop")
        # local flash with global position masking
        q_pos = clen[:, None] + jnp.arange(Sq, dtype=jnp.int32)[None]
        kv_valid = clen + Sq
        acc, m, l = flash_attention(
            q, kc, vc, q_pos=q_pos, kv_valid=kv_valid, causal=causal,
            chunk=chunk, pos_offset=base, return_stats=True,
        )
        # flash-decoding combine across sequence shards
        m_g = jax.lax.pmax(m, ax)
        c = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * c, ax)
        acc_g = jax.lax.psum(acc * c[..., None], ax)
        out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]  # (B, Sq, Hkv, G, D)
        return out.reshape(q.shape[0], Sq, Hq, D).astype(q.dtype), kc, vc

    out, kc, vc = jax.shard_map(
        f,
        mesh=ctx.mesh,
        in_specs=(
            P(bspec, None, None, None),      # q
            P(bspec, None, ax, None),        # k_cache (S sharded)
            P(bspec, None, ax, None),        # v_cache
            P(bspec, None, None, None),      # k_new
            P(bspec, None, None, None),      # v_new
            P(bspec),                        # cache_len
            P(),                             # start
        ),
        out_specs=(
            P(bspec, None, None, None),
            P(bspec, None, ax, None),
            P(bspec, None, ax, None),
        ),
        check_vma=False,
    )(q, k_cache, v_cache, k_new, v_new, cache_len, start)
    return out, kc, vc
