"""Core pure-JAX layers: norms, RoPE, flash-style attention, latent
attention (MLA), MLP, MoE.

Everything is a pure function over parameter pytrees (no flax).  Attention is
implemented as an online-softmax scan over KV chunks ("xla_flash") so that
32k-524k contexts never materialise (S_q, S_kv) score tensors; the Pallas
kernel in repro.kernels.verify_attn is the TPU-target version of the same
computation and is validated against the same oracle.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


Params = Dict[str, Any]

NEG_INF = -1e30  # large-negative instead of -inf: keeps fully-masked rows NaN-free

# int8 KV cache (beyond-paper: halves the cache stream and the pool's bytes
# per slot).  Symmetric scale per (row, kv-head), FIXED at
# prompt prefill: the first append into an empty row (cache_len == 0)
# computes scale = max(|K|)/127 over the prompt and stores it in the
# cache's ``k_scale``/``v_scale`` leaves; every later append reuses the
# stored value.  Fixing the scale at prefill is what keeps quantization
# deterministic under replay — device-replay recovery re-prefills the same
# prompt (same scale) and then force-extends, so a recovered stream's int8
# rows are bit-identical to its fault-free twin's no matter how the appends
# were grouped.  ``KV_SCALE`` remains the static fallback for callers that
# pass no scale.  Opt-in: make_cache(kv_dtype=jnp.int8), the engine's
# kv_dtype='int8'.
KV_SCALE = 0.05
KV_SCALE_EPS = 1e-6  # floor for amax/127 so all-zero rows stay invertible


def _bc(scale: jax.Array) -> jax.Array:
    """(B, Hkv) scale broadcast against a (B, Hkv, S, D) K/V tile."""
    return scale[:, :, None, None]


def kv_quant(x: jax.Array, dtype, scale: Optional[jax.Array] = None) -> jax.Array:
    if dtype != jnp.int8:
        return x.astype(dtype)
    s = KV_SCALE if scale is None else _bc(scale)
    return jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127).astype(jnp.int8)


def kv_dequant(x: jax.Array, scale: Optional[jax.Array] = None) -> jax.Array:
    if x.dtype != jnp.int8:
        return x
    s = KV_SCALE if scale is None else _bc(scale)
    return (x.astype(jnp.float32) * s).astype(jnp.bfloat16)


def kv_fresh_scale(x: jax.Array) -> jax.Array:
    """Per-(row, kv-head) symmetric scale for a fresh (B, Hkv, S, D) tile."""
    amax = jnp.abs(x.astype(jnp.float32)).max(axis=(2, 3))
    return jnp.maximum(amax / 127.0, KV_SCALE_EPS)


@dataclasses.dataclass(frozen=True)
class MeshContext:
    """How a layer should shard itself when running under a mesh.

    ``mesh=None`` means single-device math (smoke tests, examples).
    ``batch_axes`` are the mesh axes carrying the batch dimension
    (('pod','data') multi-pod, ('data',) single pod), ``model_axis`` carries
    tensor/expert parallelism.  ``seq_shard_kv`` switches attention caches
    to sequence sharding over the model axis (flash-decoding combine; see
    distributed/collectives.py) — the fit strategy for small-kv GQA archs.
    """

    mesh: Any = None
    batch_axes: Tuple[str, ...] = ()
    model_axis: Optional[str] = None
    fsdp: bool = False
    seq_shard_kv: bool = False

    @property
    def tp(self) -> int:
        if self.mesh is None or self.model_axis is None:
            return 1
        return self.mesh.shape[self.model_axis]

    @property
    def n_batch_shards(self) -> int:
        n = 1
        for a in self.batch_axes:
            n *= self.mesh.shape[a] if self.mesh is not None else 1
        return n

    def bspec(self, batch_size: int):
        """Batch-dim axes, or None when the batch can't shard evenly."""
        if self.batch_axes and batch_size % self.n_batch_shards == 0:
            return self.batch_axes
        return None


NO_MESH = MeshContext()


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)).astype(dtype)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def apply_norm(x: jax.Array, p: Params, cfg) -> jax.Array:
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, p["scale"], cfg.norm_eps)
    return layernorm(x, p["scale"], p["bias"])


def init_norm(d: int, kind: str) -> Params:
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


# ---------------------------------------------------------------------------
# Embedding lookup (vocab-TP aware)
# ---------------------------------------------------------------------------


def embed_lookup(embed: jax.Array, tokens: jax.Array, ctx: "MeshContext") -> jax.Array:
    """Token embedding gather that works WITH a vocab-sharded table.

    A plain ``embed[tokens]`` on a model-axis-sharded table makes GSPMD
    replicate the whole table per step ("involuntary full rematerialization");
    instead each model shard gathers its own vocab range and a psum combines
    — the standard TP embedding trick, here via shard_map.
    """
    V, d = embed.shape
    tp = ctx.tp
    if ctx.mesh is None or tp == 1 or V % tp != 0:
        return embed[tokens]
    ax = ctx.model_axis
    bspec = ctx.bspec(tokens.shape[0])
    v_loc = V // tp

    def f(emb, toks):
        r = jax.lax.axis_index(ax)
        rel = toks - r * v_loc
        ok = (rel >= 0) & (rel < v_loc)
        rows = emb[jnp.clip(rel, 0, v_loc - 1)]
        rows = jnp.where(ok[..., None], rows, jnp.zeros((), rows.dtype))
        return jax.lax.psum(rows, ax)

    return jax.shard_map(
        f,
        mesh=ctx.mesh,
        in_specs=(P(ax, None), P(bspec, None)),
        out_specs=P(bspec, None, None),
        check_vma=False,
    )(embed, tokens)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) rotated at ``positions`` (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Flash-style attention (online softmax over KV chunks, pure XLA)
# ---------------------------------------------------------------------------


def flash_attention(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Hkv, Skv, D) head-major — or (L, B, Hkv, Skv, D) with ``layer``
    v: jax.Array,
    *,
    q_pos: Optional[jax.Array] = None,  # (B, Sq) absolute positions; None -> arange
    kv_valid: Optional[jax.Array] = None,  # (B,) number of valid kv entries; None -> Skv
    causal: bool = True,
    chunk: int = 1024,
    scale: Optional[float] = None,
    layer: Optional[jax.Array] = None,  # stream chunks straight from a
    # stacked (L, B, H, S, D) cache buffer — avoids materialising a per-layer
    # slice copy of the cache inside the layer loop
    slots: Optional[jax.Array] = None,  # (B,) pool-row indices: k/v are a
    # PagedKVCache pool ((n_pool, H, S, D), or (L, n_pool, H, S, D) with
    # ``layer``) and batch entry b attends against pool row slots[b].  Each
    # kv chunk is sliced from the pool FIRST and row-indexed second, so only
    # chunk-sized slot-indexed tiles ever materialise — the XLA mirror of
    # kernels/verify_attn.verify_attention_paged's scalar-prefetch indexing
    # (no step-level gather of the multi-GB cache).
    pos_offset: Optional[jax.Array] = None,  # global position of k[:, 0]
    # (sequence-parallel shards pass their shard offset)
    return_stats: bool = False,  # return (acc, m, l) un-normalised for
    # cross-shard softmax combination (flash-decoding style)
    remat: bool = False,  # checkpoint the chunk body (training: do not save
    # per-chunk score tensors for backward)
    k_scale: Optional[jax.Array] = None,  # (B, Hkv) per-row dequant scales
    v_scale: Optional[jax.Array] = None,  # for int8 k/v (already row-selected
    # by the caller: constant over the sequence, so every chunk shares them)
):
    """Chunked online-softmax attention.

    KV entry ``j`` is visible to query at absolute position ``p`` iff
    ``j < kv_valid`` and (not causal or ``j <= p``).  Cache semantics: buffer
    index == absolute position, so speculative rollback is just a smaller
    ``kv_valid`` next round.
    """
    B, Sq, Hq, D = q.shape
    stacked = layer is not None
    Bk, Hkv, Skv = k.shape[-4:-1]  # Bk: pool rows when slots given
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    if q_pos is None:
        q_pos = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32)[None], (B, Sq))
    if kv_valid is None:
        kv_valid = jnp.full((B,), Skv, jnp.int32)

    chunk = min(chunk, Skv)
    n_chunks = math.ceil(Skv / chunk)
    pad = n_chunks * chunk - Skv
    if pad:
        # rare path: callers size KV buffers to a chunk multiple (make_cache
        # rounds up), so only short fresh K/V (e.g. whisper's 1500-frame
        # encoder) ever pays this copy.
        padw = ((0, 0),) * (k.ndim - 2) + ((0, pad), (0, 0))
        k = jnp.pad(k, padw)
        v = jnp.pad(v, padw)

    if stacked:
        def chunk_at(a, idx):
            sl = jax.lax.dynamic_slice(
                a, (layer, 0, 0, idx * chunk, 0), (1, Bk, Hkv, chunk, D)
            )[0]
            # pool layout: slice the chunk first, row-index second — only a
            # (B, H, chunk, D) slot-indexed tile materialises, never a dense
            # gathered copy of the cache rows
            return jnp.take(sl, slots, axis=0) if slots is not None else sl
    else:
        def chunk_at(a, idx):
            sl = jax.lax.dynamic_slice_in_dim(a, idx * chunk, chunk, axis=2)
            return jnp.take(sl, slots, axis=0) if slots is not None else sl

    qg = q.reshape(B, Sq, Hkv, G, D)

    m0 = jnp.full((B, Sq, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    acc0 = jnp.zeros((B, Sq, Hkv, G, D), jnp.float32)

    def body(carry, idx):
        # stream chunks with dynamic_slice (no transposed copy of the cache:
        # a reshape+moveaxis here doubles the HBM traffic)
        m, l, acc = carry
        kb = kv_dequant(chunk_at(k, idx), k_scale)
        vb = kv_dequant(chunk_at(v, idx), v_scale)
        # scores: (B, Sq, Hkv, G, chunk)
        s = jnp.einsum(
            "bshgd,bhcd->bshgc", qg, kb, preferred_element_type=jnp.float32
        ) * scale
        j = idx * chunk + jnp.arange(chunk, dtype=jnp.int32)  # (chunk,)
        if pos_offset is not None:
            j = j + pos_offset
        mask = j[None, None, :] < kv_valid[:, None, None]  # (B, 1, chunk)
        if causal:
            mask = mask & (j[None, None, :] <= q_pos[:, :, None])
        s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        # V first: the dot then contracts the chunk axis of its right
        # operand, a form the CPU backend runs in bf16 (the TPU takes either)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhcd,bshgc->bshgd", vb, p.astype(vb.dtype),
            preferred_element_type=jnp.float32,
        )
        return (m_new, l, acc), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), jnp.arange(n_chunks, dtype=jnp.int32)
    )
    if return_stats:
        return acc, m, l
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Sq, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block
# ---------------------------------------------------------------------------


def init_attention(key, cfg) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = 0.02
    out_std = std / math.sqrt(2 * max(cfg.num_layers, 1))
    p = {
        "wq": (jax.random.normal(k1, (d, hq * hd)) * std).astype(jnp.bfloat16),
        "wk": (jax.random.normal(k2, (d, hkv * hd)) * std).astype(jnp.bfloat16),
        "wv": (jax.random.normal(k3, (d, hkv * hd)) * std).astype(jnp.bfloat16),
        "wo": (jax.random.normal(k4, (hq * hd, d)) * out_std).astype(jnp.bfloat16),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), jnp.bfloat16)
        p["bk"] = jnp.zeros((hkv * hd,), jnp.bfloat16)
        p["bv"] = jnp.zeros((hkv * hd,), jnp.bfloat16)
    return p


def attention_block(
    x: jax.Array,  # (B, S, d)
    p: Params,
    cfg,
    *,
    positions: jax.Array,  # (B, S)
    kv_cache: Optional[Tuple[jax.Array, jax.Array]] = None,  # (B, Hkv, Smax, D) x2
    cache_len: Optional[jax.Array] = None,  # (B,)
    cache_layer: Optional[jax.Array] = None,  # kv_cache is the full (L, ...) stack
    causal: bool = True,
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    cross_len: Optional[jax.Array] = None,
    cross_layer: Optional[jax.Array] = None,
    chunk: int = 1024,
    ctx: "MeshContext" = NO_MESH,
    flash_remat: bool = False,
    slots: Optional[jax.Array] = None,  # kv_cache is a slot pool; batch row
    # b owns pool row slots[b] (PagedKVCache continuous batching)
    kv_scales: Optional[Tuple[jax.Array, jax.Array]] = None,  # int8 cache
    # dequant scales, same addressing as the cache: (B, Hkv) plain,
    # (L, B, Hkv) with ``cache_layer``, (L, n_pool, Hkv) with ``slots`` too
) -> Tuple[jax.Array, Optional[Tuple[jax.Array, ...]]]:
    """QKV -> (optional cache append) -> flash attention -> output proj.

    With a kv_cache, new K/V rows are scattered into the buffer at
    ``cache_len + arange(S)`` per row, and attention runs over the whole
    buffer with ``kv_valid = cache_len + S``; returns the updated cache.
    Cache and cross K/V are head-major, (B, Hkv, S, D), so a row's
    positions of one head are contiguous: an append is a (Hkv, S, D) window
    and the step runs its layer loop over the pool as stored.
    With ``cache_layer``, the cache is the stacked (L, B, H, S, D) buffer:
    only the S new rows are written (tiny scatter) and attention streams
    chunks directly from the stack — the layer loop never copies the cache.
    With ``slots``, the cache batch axis is a PagedKVCache row pool: the
    fresh K/V rows are scattered straight into pool rows ``slots`` and
    attention streams slot-indexed chunks from the pool (flash_attention
    ``slots=``) — the pool is only ever touched at O(B*S) fresh rows.
    Cross-attention ignores caches for K/V and uses ``cross_kv``.
    """
    B, S, d = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    q = x @ p["wq"]
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(B, S, hq, hd)

    if cross_kv is not None:
        k, v = cross_kv
        out = flash_attention(
            q, k, v, q_pos=positions, kv_valid=cross_len, causal=False,
            chunk=chunk, layer=cross_layer, slots=slots,
        )
        return (out.reshape(B, S, hq * hd) @ p["wo"], None)

    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bk" in p:
        k = k + p["bk"]
        v = v + p["bv"]
    k = k.reshape(B, S, hkv, hd)
    v = v.reshape(B, S, hkv, hd)

    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    k, v = k.swapaxes(1, 2), v.swapaxes(1, 2)  # (B, Hkv, S, D), the cache's layout

    new_cache = None
    if slots is not None and ctx.seq_shard_kv:
        raise NotImplementedError("slot-pool caches are not sequence-sharded")
    if kv_cache is not None and ctx.seq_shard_kv:
        # sequence-parallel cache: append + flash-decoding combine in one
        # shard_map (distributed/collectives.py)
        from repro.distributed.collectives import sp_append_attend

        out, ck, cv = sp_append_attend(
            q, kv_cache[0], kv_cache[1], k, v, cache_len, cache_len[0], ctx,
            causal=causal, chunk=chunk,
        )
        return out.reshape(B, S, hq * hd) @ p["wo"], (ck, cv)
    if kv_cache is not None:
        ck, cv = kv_cache
        ksc = vsc = row_ks = row_vs = None
        if kv_scales is not None and ck.dtype == jnp.int8:
            ksc, vsc = kv_scales

            def _rows(sc):
                # select this layer's / these slots' (B, Hkv) scale rows,
                # mirroring the cache addressing
                if cache_layer is not None:
                    sc = jax.lax.dynamic_index_in_dim(sc, cache_layer, 0, keepdims=False)
                if slots is not None:
                    sc = jnp.take(sc, slots, axis=0)
                return sc

            # determinism contract: scale is FIXED at prefill.  The first
            # append into an empty row (cache_len == 0) derives it from the
            # fresh K/V amax; every later append reuses the stored value, so
            # replayed appends quantize bit-identically however they are
            # grouped (device-replay recovery re-prefills the same prompt).
            row_ks = jnp.where(cache_len[:, None] == 0, kv_fresh_scale(k), _rows(ksc))
            row_vs = jnp.where(cache_len[:, None] == 0, kv_fresh_scale(v), _rows(vsc))
        kq, vq = kv_quant(k, ck.dtype, row_ks), kv_quant(v, cv.dtype, row_vs)
        if slots is not None:
            # slot pool: batch row b appends its S fresh rows into pool row
            # slots[b].  A static unroll of per-row dynamic_update_slice
            # (one (1, H, S, D) window each) is the ONLY pool
            # write of the step — a scatter here would be rewritten by XLA's
            # scatter expander into a B*S-trip select loop over the whole
            # pool buffer.  Duplicate scratch-slot rows overwrite in order
            # (deterministic last-writer; scratch is never read as
            # committed).  NB dynamic_update_slice clamps, so callers size
            # max_len >= committed + S (same contract the engine already
            # keeps for the dense path's drop-mode scatter).
            for b in range(B):
                row = slots[b].astype(jnp.int32)
                pos = cache_len[b].astype(jnp.int32)
                if cache_layer is not None:
                    start = (cache_layer, row, jnp.int32(0), pos, jnp.int32(0))
                    ck = jax.lax.dynamic_update_slice(ck, kq[b][None, None], start)
                    cv = jax.lax.dynamic_update_slice(cv, vq[b][None, None], start)
                else:
                    start = (row, jnp.int32(0), pos, jnp.int32(0))
                    ck = jax.lax.dynamic_update_slice(ck, kq[b][None], start)
                    cv = jax.lax.dynamic_update_slice(cv, vq[b][None], start)
        else:
            # one (Hkv, D) window per (row, position): the index arrays sit
            # around the head axis, so the update is indexed (B, S, Hkv, D)
            b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]  # (B,1)
            s_idx = cache_len[:, None] + jnp.arange(S, dtype=jnp.int32)[None]  # (B,S)
            kq, vq = kq.swapaxes(1, 2), vq.swapaxes(1, 2)
            if cache_layer is not None:
                ck = ck.at[cache_layer, b_idx, :, s_idx].set(kq, mode="drop")
                cv = cv.at[cache_layer, b_idx, :, s_idx].set(vq, mode="drop")
            else:
                ck = ck.at[b_idx, :, s_idx].set(kq, mode="drop")
                cv = cv.at[b_idx, :, s_idx].set(vq, mode="drop")
        if row_ks is not None:
            # persist the selected scales along the same addressing as the
            # K/V append (a no-op rewrite for rows whose scale was already
            # fixed: row_ks == the stored value there)
            if slots is not None:
                for b in range(B):
                    row = slots[b].astype(jnp.int32)
                    if cache_layer is not None:
                        st = (cache_layer, row, jnp.int32(0))
                        ksc = jax.lax.dynamic_update_slice(ksc, row_ks[b][None, None], st)
                        vsc = jax.lax.dynamic_update_slice(vsc, row_vs[b][None, None], st)
                    else:
                        st = (row, jnp.int32(0))
                        ksc = jax.lax.dynamic_update_slice(ksc, row_ks[b][None], st)
                        vsc = jax.lax.dynamic_update_slice(vsc, row_vs[b][None], st)
            elif cache_layer is not None:
                st = (cache_layer, jnp.int32(0), jnp.int32(0))
                ksc = jax.lax.dynamic_update_slice(ksc, row_ks[None], st)
                vsc = jax.lax.dynamic_update_slice(vsc, row_vs[None], st)
            else:
                ksc, vsc = row_ks, row_vs
        new_cache = (ck, cv) if ksc is None else (ck, cv, ksc, vsc)
        kv_valid = cache_len + S
        out = flash_attention(
            q, ck, cv, q_pos=positions, kv_valid=kv_valid, causal=causal,
            chunk=chunk, layer=cache_layer, slots=slots,
            k_scale=row_ks, v_scale=row_vs,
        )
    else:
        out = flash_attention(q, k, v, q_pos=positions, causal=causal, chunk=chunk,
                              remat=flash_remat)

    return out.reshape(B, S, hq * hd) @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# Latent attention (MLA, DeepSeek-V2/V3), served in the absorbed form
# ---------------------------------------------------------------------------


def init_mla(key, cfg) -> Params:
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rope_d, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = 0.02
    out_std = std / math.sqrt(2 * max(cfg.num_layers, 1))
    n = lambda k, shp, sd=std: (jax.random.normal(k, shp) * sd).astype(jnp.bfloat16)
    return {
        "wq": n(k1, (d, h * (nope + rope_d))),
        "wkv_a": n(k2, (d, r + rope_d)),
        "kv_norm": {"scale": jnp.ones((r,), jnp.float32)},
        "wkv_b": n(k3, (r, h * (nope + vd))),
        "wo": n(k4, (h * vd, d), out_std),
    }


def latent_attention(
    q_lat: jax.Array,  # (B, Sq, H, R): absorbed queries
    q_pe: jax.Array,  # (B, Sq, H, rope): roped query parts
    ckv: jax.Array,  # (L, n_rows, S, R): normed latent cache stack
    kpe: jax.Array,  # (L, n_rows, S, rope): roped key cache stack
    *,
    layer: jax.Array,
    rows: Optional[jax.Array],  # (B,) pool rows; None -> row b for batch b
    kv_valid: jax.Array,  # (B,)
    q_pos: jax.Array,  # (B, Sq)
    scale: float,
    chunk: int,
) -> jax.Array:
    """Chunked online-softmax attention of every head against one shared
    latent row per position (multi-query): score ``q_lat . c~ + q_pe .
    k_pe``, value ``c~``.  Returns (B, Sq, H, R)."""
    B, Sq, H, R = q_lat.shape
    n_rows, Skv, rope_d = ckv.shape[1], ckv.shape[2], kpe.shape[-1]
    chunk = min(chunk, Skv)
    if Skv % chunk:
        raise ValueError(f"latent cache length {Skv} is not a multiple of chunk {chunk}")

    def load(buf, idx, width):
        blk = jax.lax.dynamic_slice(buf, (layer, 0, idx * chunk, 0), (1, n_rows, chunk, width))[0]
        return blk if rows is None else jnp.take(blk, rows, axis=0)  # (B, chunk, width)

    def body(carry, idx):
        m, l, acc = carry
        cb, pb = load(ckv, idx, R), load(kpe, idx, rope_d)
        s = (jnp.einsum("bshr,bcr->bshc", q_lat, cb, preferred_element_type=jnp.float32)
             + jnp.einsum("bshr,bcr->bshc", q_pe, pb, preferred_element_type=jnp.float32)) * scale
        j = idx * chunk + jnp.arange(chunk, dtype=jnp.int32)
        mask = (j[None, None, :] < kv_valid[:, None, None]) & (j[None, None, :] <= q_pos[:, :, None])
        s = jnp.where(mask[:, :, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bshc,bcr->bshr", p.astype(cb.dtype), cb, preferred_element_type=jnp.float32
        )
        return (m_new, l, acc), None

    init = (jnp.full((B, Sq, H), NEG_INF, jnp.float32), jnp.zeros((B, Sq, H), jnp.float32),
            jnp.zeros((B, Sq, H, R), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init, jnp.arange(Skv // chunk, dtype=jnp.int32))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q_lat.dtype)


def mla_block(
    x: jax.Array,  # (B, S, d)
    p: Params,
    cfg,
    *,
    positions: jax.Array,  # (B, S)
    pool: Tuple[jax.Array, jax.Array],  # (ckv (L, n_rows, Smax, R), kpe (L, n_rows, Smax, rope))
    cache_len: jax.Array,  # (B,)
    layer: jax.Array,
    rows: Optional[jax.Array] = None,  # (B,) pool rows (slot pool); None -> row b
    chunk: int = 1024,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Latent attention with its cache append; returns (out, pool').

    Per token: ``[q_nope | q_pe] = x W_q`` per head, ``[c | k_pe] = x
    W_kva``, ``c~ = RMSNorm(c)``, RoPE on q_pe and on k_pe (one head shared
    by all heads).  The cache keeps ``c~`` and ``k_pe`` per position, in two
    leaves: one 576-wide row would pad to 640 lanes on the TPU and take a
    layout copy of the whole pool in and out of the step.  Scores use the
    absorbed query ``q^_h = q_nope,h W_kvb^{K,h}T`` against c~ plus ``q_pe
    . k_pe`` over sqrt(nope + rope); the head output is ``(sum p c~)
    W_kvb^{V,h}`` — the plain form's k_nope and v never materialise.  Fresh
    rows go into pool rows ``rows`` at ``cache_len`` with one
    dynamic_update_slice per batch row and leaf (the slot pool's only
    write; see attention_block).
    """
    B, S, _ = x.shape
    h, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope_d, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = (x @ p["wq"]).reshape(B, S, h, nope + rope_d)
    q_pe = rope(q[..., nope:], positions, cfg.rope_theta)
    kva = x @ p["wkv_a"]
    c = rmsnorm(kva[..., :r], p["kv_norm"]["scale"], cfg.norm_eps)
    k_pe = rope(kva[..., None, r:], positions, cfg.rope_theta)[:, :, 0]
    ckv, kpe = pool
    c, k_pe = c.astype(ckv.dtype), k_pe.astype(kpe.dtype)
    for b in range(B):
        row = jnp.int32(b) if rows is None else rows[b].astype(jnp.int32)
        start = (layer, row, cache_len[b].astype(jnp.int32), jnp.int32(0))
        ckv = jax.lax.dynamic_update_slice(ckv, c[b][None, None], start)
        kpe = jax.lax.dynamic_update_slice(kpe, k_pe[b][None, None], start)
    wkv_b = p["wkv_b"].reshape(r, h, nope + vd)
    q_lat = jnp.einsum("bshn,rhn->bshr", q[..., :nope], wkv_b[..., :nope])
    o = latent_attention(
        q_lat, q_pe.astype(q_lat.dtype), ckv, kpe, layer=layer, rows=rows,
        kv_valid=cache_len + S, q_pos=positions,
        scale=1.0 / math.sqrt(nope + rope_d), chunk=chunk,
    )
    o = jnp.einsum("bshr,rhv->bshv", o, wkv_b[..., nope:])
    return o.reshape(B, S, h * vd) @ p["wo"], (ckv, kpe)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(key, cfg, d_ff: Optional[int] = None) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    std = 0.02
    out_std = std / math.sqrt(2 * max(cfg.num_layers, 1))
    if cfg.act == "swiglu":
        return {
            "wg": (jax.random.normal(k1, (d, f)) * std).astype(jnp.bfloat16),
            "wu": (jax.random.normal(k2, (d, f)) * std).astype(jnp.bfloat16),
            "wd": (jax.random.normal(k3, (f, d)) * out_std).astype(jnp.bfloat16),
        }
    return {
        "wi": (jax.random.normal(k1, (d, f)) * std).astype(jnp.bfloat16),
        "wd": (jax.random.normal(k3, (f, d)) * out_std).astype(jnp.bfloat16),
    }


def mlp_block(x: jax.Array, p: Params, cfg) -> jax.Array:
    if "wg" in p:
        return (jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]
    return jax.nn.gelu(x @ p["wi"], approximate=True) @ p["wd"]


# ---------------------------------------------------------------------------
# MoE (top-k, dropless grouped matmul over the experts held here; EP via
# shard_map when a mesh is given)
# ---------------------------------------------------------------------------


class MoEStats(NamedTuple):
    """What MoE layers report beside their output (summed over layers by a
    stack)."""
    aux: jax.Array  # () Switch-style load-balance loss over all experts
    rows: Optional[jax.Array]  # (held,) int32 (token, choice) pairs each
    # held expert computed; None where no layer routed


def init_moe(key, cfg) -> Params:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    first, count = cfg.held
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = 0.02
    out_std = std / math.sqrt(2 * max(cfg.num_layers, 1))
    p = {
        "router": (jax.random.normal(k1, (d, e)) * std).astype(jnp.float32),
        "wg": (jax.random.normal(k2, (e, d, f)) * std)[first:first + count].astype(jnp.bfloat16),
        "wu": (jax.random.normal(k3, (e, d, f)) * std)[first:first + count].astype(jnp.bfloat16),
        "wd": (jax.random.normal(k4, (e, f, d)) * out_std)[first:first + count].astype(jnp.bfloat16),
    }
    if cfg.router_bias:
        p["router_bias"] = jnp.zeros((e,), jnp.float32)
    return p


def route(x_flat: jax.Array, p: Params, cfg) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Router over ALL ``cfg.num_experts`` experts, in float32.

    Returns (scores (T, E), top-k expert ids (T, K), their weights (T, K)).
    softmax scoring: weights are the top-k probabilities renormalised.
    sigmoid scoring (DeepSeek-V3): the top k are chosen by score + the
    selection bias, weighted by score, normalised and scaled by
    ``cfg.routed_scale``.
    """
    K = cfg.experts_per_token
    logits = x_flat.astype(jnp.float32) @ p["router"]  # (T, E)
    if cfg.router_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + p["router_bias"] if "router_bias" in p else scores
        _, top_idx = jax.lax.top_k(sel, K)
        top_vals = jnp.take_along_axis(scores, top_idx, axis=-1)
        top_vals = top_vals / (top_vals.sum(-1, keepdims=True) + 1e-20)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        top_vals, top_idx = jax.lax.top_k(scores, K)
        top_vals = top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True), 1e-9)
    return scores, top_idx, top_vals * cfg.routed_scale


def moe_routed(x_flat: jax.Array, p: Params, cfg, first, count: int,
               valid: Optional[jax.Array] = None) -> Tuple[jax.Array, MoEStats]:
    """The routed experts' part of an MoE layer, for the experts
    ``[first, first + count)`` that ``p`` holds (``first`` may be traced).

    x_flat: (T, d) tokens; ``valid`` (T,) bool marks real tokens (bucket
    padding is routed nowhere).  The router scores all experts; each
    (token, choice) that lands on a held expert is computed, none is
    dropped: assignments are sorted by expert and run as one grouped
    matmul per projection (``lax.ragged_dot``), so a token's result never
    depends on which other tokens share its batch.  Returns (out (T, d)
    partial sum over the held experts, MoEStats: the aux loss over all
    experts, rows (count,) computed per held expert).
    """
    T, d = x_flat.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    scores, top_idx, top_w = route(x_flat, p, cfg)
    me = jnp.mean(scores, axis=0)  # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[top_idx.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce)

    held = (top_idx >= first) & (top_idx < first + count)  # (T, K)
    if valid is not None:
        held = held & valid[:, None]
    group = jnp.where(held, top_idx - first, count).reshape(-1)  # absent -> group `count`
    order = jnp.argsort(group, stable=True)
    rows = jnp.zeros((count + 1,), jnp.int32).at[group].add(1)[:count]
    xs = x_flat[order // K]  # (T*K, d), grouped by expert
    h = jax.lax.ragged_dot(xs, p["wg"], rows, preferred_element_type=jnp.float32)
    u = jax.lax.ragged_dot(xs, p["wu"], rows, preferred_element_type=jnp.float32)
    a = (jax.nn.silu(h) * u).astype(x_flat.dtype)
    y = jax.lax.ragged_dot(a, p["wd"], rows, preferred_element_type=jnp.float32)
    # assignments past the held groups belong to no group: zero them
    y = jnp.where((jnp.arange(T * K) < rows.sum())[:, None], y, 0.0)
    y = jnp.zeros_like(y).at[order].set(y).reshape(T, K, d)  # back to (token, choice)
    w = jnp.where(held, top_w, 0.0)
    out = jnp.einsum("tkd,tk->td", y, w)
    return out.astype(x_flat.dtype), MoEStats(aux, rows)


def moe_block(x: jax.Array, p: Params, cfg, ctx: MeshContext,
              valid: Optional[jax.Array] = None) -> Tuple[jax.Array, MoEStats]:
    """x: (B, S, d) -> (B, S, d), MoEStats; ``valid`` (B, S) marks the real
    tokens (padding is routed nowhere; None: all).

    Without a mesh: the experts ``cfg.held`` names.  Under a mesh: tokens
    stay sharded over the batch axes; experts are sharded over the model
    axis when E % tp == 0 (EP: each rank is :func:`moe_routed` over its
    own slice), otherwise expert-internal d_ff is sharded (f-TP).  Either
    way the partial outputs are psum'd over the model axis — same
    collective volume as a dense TP MLP — and the rows cover all E experts.
    """
    B, S, d = x.shape
    E = cfg.num_experts
    flat = lambda v, t: None if v is None else v.reshape(t)

    if ctx.mesh is None:
        first, count = cfg.held
        out, st = moe_routed(x.reshape(B * S, d), p, cfg, first, count, flat(valid, B * S))
        return out.reshape(B, S, d), st

    tp = ctx.tp
    ep = E % tp == 0
    ax = ctx.model_axis
    n_batch_shards = 1
    for a in ctx.batch_axes:
        n_batch_shards *= ctx.mesh.shape[a]

    if ep:
        w_specs = {
            "router": P(None, None),
            "wg": P(ax, None, None),
            "wu": P(ax, None, None),
            "wd": P(ax, None, None),
        }
    else:
        w_specs = {
            "router": P(None, None),
            "wg": P(None, None, ax),
            "wu": P(None, None, ax),
            "wd": P(None, ax, None),
        }
    if "router_bias" in p:
        w_specs["router_bias"] = P(None)

    def shard_fn(xb, pw, vb=None):
        tb, _, _ = xb.shape
        xf, vf = xb.reshape(tb * S, d), flat(vb, tb * S)
        if ep:
            e_local = E // tp
            out, (aux, rows) = moe_routed(xf, pw, cfg, jax.lax.axis_index(ax) * e_local,
                                          e_local, vf)
            rows = jax.lax.all_gather(rows, ax, tiled=True)  # (E,)
        else:
            # f-TP: all experts, partial d_ff -> swiglu is elementwise in f,
            # wd contracts the local f slice; psum completes both f and E sums.
            out, (aux, rows) = moe_routed(xf, pw, cfg, 0, E, vf)
        # aux is computed from the full router on every model rank; de-dup.
        aux = aux / tp
        out = jax.lax.psum(out, ax)
        aux = jax.lax.psum(aux, ax)
        if ctx.batch_axes:
            rows = jax.lax.psum(rows, ctx.batch_axes)
        return out.reshape(tb, S, d), aux, rows

    batch = ctx.batch_axes if ctx.batch_axes else None
    args, specs = (x, {k: p[k] for k in w_specs}), (P(batch, None, None), w_specs)
    if valid is not None:
        args, specs = args + (valid,), specs + (P(batch, None),)
    out, aux, rows = jax.shard_map(
        shard_fn,
        mesh=ctx.mesh,
        in_specs=specs,
        out_specs=(P(batch, None, None), P(), P()),
        check_vma=False,
    )(*args)
    return out, MoEStats(aux / n_batch_shards, rows)
