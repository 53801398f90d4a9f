"""Decoder-only transformer (dense / MoE / VLM) + whisper-style enc-dec.

Pure functions over parameter pytrees.  Layers are stacked along a leading
``L`` axis and executed with ``lax.scan`` so HLO size (and hence
compile time) is independent of depth.  The same ``decode_forward`` serves
prefill (S = prompt length, cache_len = 0) and SLED verification
(S = K draft tokens + 1, cache_len = committed length).

Latent-attention models (``cfg.kv_lora_rank``, DeepSeek-V3 blocks) keep
``cfg.first_k_dense`` leading dense layers in a stack of their own
(``dense_layers``) ahead of the MoE stack, shared experts beside the routed
ones (``shared``), and two cache leaves per position and layer: ``ckv``
(L, B, S, kv_lora_rank), the normed latent, and ``kpe`` (L, B, S,
qk_rope_head_dim), the roped key part shared by all heads.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.kvcache import init_kv_cache, kv_cache_spec
from repro.models.layers import MeshContext, NO_MESH

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_layer(cfg, key, moe: Optional[bool] = None) -> Params:
    if moe is None:
        moe = cfg.family == "moe"
    ks = jax.random.split(key, 4)
    p = {
        "ln1": L.init_norm(cfg.d_model, cfg.norm),
        "attn": (L.init_mla if cfg.kv_lora_rank else L.init_attention)(ks[0], cfg),
        "ln2": L.init_norm(cfg.d_model, cfg.norm),
    }
    if moe:
        p["moe"] = L.init_moe(ks[1], cfg)
        if cfg.num_shared_experts:
            p["shared"] = L.init_mlp(ks[2], cfg, cfg.num_shared_experts * cfg.moe_d_ff)
    else:
        p["mlp"] = L.init_mlp(ks[1], cfg)
    return p


def _init_cross_layer(cfg, key) -> Params:
    p = _init_layer(cfg, key)
    ks = jax.random.split(key, 2)
    p["ln_x"] = L.init_norm(cfg.d_model, cfg.norm)
    p["xattn"] = L.init_attention(ks[1], cfg)
    return p


def _stack_init(init_fn, cfg, key, n) -> Params:
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_fn(cfg, k))(keys)


def init_params(cfg, key, *, max_pos: int = 0) -> Params:
    """``max_pos`` sizes the learned position table (non-RoPE archs only)."""
    if cfg.first_k_dense and not cfg.kv_lora_rank:
        raise ValueError("leading dense layers are served on the latent-attention stack only")
    k_emb, k_layers, k_head, k_enc = jax.random.split(key, 4)
    p: Params = {
        "embed": (jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model)) * 0.02).astype(
            jnp.bfloat16
        ),
        "final_norm": L.init_norm(cfg.d_model, cfg.norm),
    }
    layer_init = _init_cross_layer if cfg.is_encdec else _init_layer
    p["layers"] = _stack_init(layer_init, cfg, k_layers, cfg.num_layers - cfg.first_k_dense)
    if cfg.first_k_dense:
        p["dense_layers"] = _stack_init(lambda c, k: _init_layer(c, k, moe=False), cfg,
                                        jax.random.fold_in(k_layers, 1), cfg.first_k_dense)
    if not cfg.tie_embeddings:
        p["lm_head"] = (
            jax.random.normal(k_head, (cfg.d_model, cfg.vocab_size)) * 0.02
        ).astype(jnp.bfloat16)
    if not cfg.use_rope:
        p["pos_embed"] = (
            jax.random.normal(k_head, (max(max_pos, 1), cfg.d_model)) * 0.01
        ).astype(jnp.bfloat16)
    if cfg.is_encdec:
        ke1, ke2 = jax.random.split(k_enc)
        p["enc"] = {
            "pos_embed": (
                jax.random.normal(ke1, (cfg.encoder_seq, cfg.d_model)) * 0.01
            ).astype(jnp.bfloat16),
            "layers": _stack_init(_init_layer, cfg, ke2, cfg.encoder_layers),
            "final_norm": L.init_norm(cfg.d_model, cfg.norm),
        }
    return p


def init_params_spec(cfg, *, max_pos: int = 0):
    """ShapeDtypeStruct pytree with the same structure (no allocation)."""
    return jax.eval_shape(lambda k: init_params(cfg, k, max_pos=max_pos), jax.random.key(0))


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _block(
    x: jax.Array,
    lp: Params,
    cfg,
    ctx: MeshContext,
    *,
    positions: jax.Array,
    kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    cache_len: Optional[jax.Array] = None,
    cache_layer: Optional[jax.Array] = None,
    causal: bool = True,
    cross: Optional[Tuple[jax.Array, jax.Array]] = None,
    cross_len: Optional[jax.Array] = None,
    cross_layer: Optional[jax.Array] = None,
    attn_chunk: int = 1024,
    flash_remat: bool = False,
    slots: Optional[jax.Array] = None,
    kv_scales: Optional[Tuple[jax.Array, jax.Array]] = None,
    valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Tuple[jax.Array, ...]], L.MoEStats]:
    """One layer; returns (x', the cache leaves it wrote, MoEStats).  Latent
    attention (``cfg.kv_lora_rank``) takes ``kv`` = the whole (ckv, kpe)
    stacks and ``cache_layer``; ``valid`` (B, S) marks the tokens MoE routes."""
    h = L.apply_norm(x, lp["ln1"], cfg)
    if cfg.kv_lora_rank:
        a, new_kv = L.mla_block(
            h, lp["attn"], cfg, positions=positions, pool=kv, cache_len=cache_len,
            layer=cache_layer, rows=slots, chunk=attn_chunk,
        )
    else:
        a, new_kv = L.attention_block(
            h, lp["attn"], cfg,
            positions=positions, kv_cache=kv, cache_len=cache_len, cache_layer=cache_layer,
            causal=causal, chunk=attn_chunk, ctx=ctx, flash_remat=flash_remat,
            slots=slots, kv_scales=kv_scales,
        )
    x = x + a
    if cross is not None:
        h = L.apply_norm(x, lp["ln_x"], cfg)
        a, _ = L.attention_block(
            h, lp["xattn"], cfg,
            positions=positions, cross_kv=cross, cross_len=cross_len,
            cross_layer=cross_layer, chunk=attn_chunk, slots=slots,
        )
        x = x + a
    h = L.apply_norm(x, lp["ln2"], cfg)
    stats = L.MoEStats(jnp.zeros((), jnp.float32), None)
    if "moe" in lp:
        m, stats = L.moe_block(h, lp["moe"], cfg, ctx, valid)
        if "shared" in lp:
            m = m + L.mlp_block(h, lp["shared"], cfg)
    else:
        m = L.mlp_block(h, lp["mlp"], cfg)
    return x + m, new_kv, stats


# ---------------------------------------------------------------------------
# Training / full-sequence forward (no cache)
# ---------------------------------------------------------------------------


def forward(
    cfg,
    params: Params,
    tokens: jax.Array,  # (B, S) int32
    ctx: MeshContext = NO_MESH,
    *,
    embeds_prefix: Optional[jax.Array] = None,  # (B, P, d) VLM patch embeddings
    enc_frames: Optional[jax.Array] = None,  # (B, F, d) whisper stub frontend
    remat: bool = False,
    attn_chunk: int = 1024,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (hidden (B, S_total, d), aux_loss). Use lm_head() for logits."""
    if cfg.kv_lora_rank:
        # latent attention runs against its cache layout; a fresh cache of
        # the sequence's length makes the full forward
        B, S = tokens.shape
        cache = make_cache(cfg, B, S, attn_chunk=min(attn_chunk, S))
        x, _, stats = decode_forward(cfg, params, cache, tokens, ctx,
                                     attn_chunk=min(attn_chunk, S))
        return x, stats.aux
    x = L.embed_lookup(params["embed"], tokens, ctx)
    if embeds_prefix is not None:
        x = jnp.concatenate([embeds_prefix.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if "pos_embed" in params:
        x = x + params["pos_embed"][positions]

    cross = cross_len = None
    if cfg.is_encdec:
        enc_out = encode(cfg, params, enc_frames, ctx, attn_chunk=attn_chunk)
        cross_len = jnp.full((B,), enc_out.shape[1], jnp.int32)
    else:
        enc_out = None

    def body(carry, lp):
        h, aux = carry
        if cfg.is_encdec:
            # cross K/V are layer-specific projections of the shared enc_out
            ck, cv = _cross_kv(lp, enc_out, cfg)
            h, _, st = _block(
                h, lp, cfg, ctx, positions=positions,
                cross=(ck, cv), cross_len=cross_len, attn_chunk=attn_chunk,
                flash_remat=remat,
            )
        else:
            h, _, st = _block(h, lp, cfg, ctx, positions=positions,
                              attn_chunk=attn_chunk, flash_remat=remat)
        return (h, aux + st.aux), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), params["layers"])
    x = L.apply_norm(x, params["final_norm"], cfg)
    return x, aux


def encode(cfg, params, frames: jax.Array, ctx: MeshContext = NO_MESH, *, attn_chunk=1024):
    """Whisper-style bidirectional encoder over stub frame embeddings."""
    enc = params["enc"]
    B, F, _ = frames.shape
    x = frames.astype(jnp.bfloat16) + enc["pos_embed"][None, :F]
    positions = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32)[None], (B, F))

    def body(h, lp):
        h, _, _ = _block(h, lp, cfg, ctx, positions=positions, causal=False,
                         attn_chunk=attn_chunk)
        return h, None

    x, _ = jax.lax.scan(body, x, enc["layers"])
    return L.apply_norm(x, enc["final_norm"], cfg)


def lm_head(cfg, params: Params, h: jax.Array) -> jax.Array:
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("bsd,dv->bsv", h, w, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Cache-based forward: prefill + SLED verification
# ---------------------------------------------------------------------------


def make_cache(cfg, batch: int, max_len: int, *, spec_only: bool = False,
               attn_chunk: int = 1024, enc_len: int = 0, kv_dtype=jnp.bfloat16):
    """Cache buffer rounded up to a multiple of the attention chunk.

    ``kv_dtype=jnp.int8`` halves the cache stream/footprint (layers.kv_quant).
    """
    max_len = -(-max_len // attn_chunk) * attn_chunk
    if cfg.kv_lora_rank:
        if kv_dtype != jnp.bfloat16:
            raise ValueError("kv_dtype int8 is not supported for latent attention (MLA): "
                             "its pool has no quantized layout; serve it with kv_dtype='bf16'")
        lead = (cfg.num_layers, batch, max_len)
        make = jax.ShapeDtypeStruct if spec_only else jnp.zeros
        return {"ckv": make(lead + (cfg.kv_lora_rank,), jnp.bfloat16),
                "kpe": make(lead + (cfg.qk_rope_head_dim,), jnp.bfloat16),
                "length": make((batch,), jnp.int32)}
    fn = kv_cache_spec if spec_only else init_kv_cache
    cache = fn(cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim,
               dtype=kv_dtype)
    if kv_dtype == jnp.int8:
        # per-(layer, row, kv-head) dequant scales, fixed at prefill (see
        # layers.kv_fresh_scale); batch on axis 1 like every cache leaf, so
        # they ride gather_slots/scatter_slots/export untouched
        sshp = (cfg.num_layers, batch, cfg.num_kv_heads)
        mk = (lambda: jax.ShapeDtypeStruct(sshp, jnp.float32)) if spec_only \
            else (lambda: jnp.ones(sshp, jnp.float32))
        cache["k_scale"] = mk()
        cache["v_scale"] = mk()
    if cfg.is_encdec:
        shp = (cfg.num_layers, batch, cfg.num_kv_heads, enc_len or cfg.encoder_seq, cfg.head_dim)
        if spec_only:
            cache["cross_k"] = jax.ShapeDtypeStruct(shp, jnp.bfloat16)
            cache["cross_v"] = jax.ShapeDtypeStruct(shp, jnp.bfloat16)
        else:
            cache["cross_k"] = jnp.zeros(shp, jnp.bfloat16)
            cache["cross_v"] = jnp.zeros(shp, jnp.bfloat16)
    return cache


def decode_forward(
    cfg,
    params: Params,
    cache: Dict[str, jax.Array],
    tokens: jax.Array,  # (B, S_new)
    ctx: MeshContext = NO_MESH,
    *,
    embeds: Optional[jax.Array] = None,  # override token embedding (VLM prefill)
    attn_chunk: int = 1024,
    slots: Optional[jax.Array] = None,  # (B,) cache is a PagedKVCache pool;
    # batch row b runs against pool row slots[b] (continuous batching)
    valid: Optional[jax.Array] = None,  # (B, S_new) real tokens: MoE routes
    # only these (None: all)
) -> Tuple[jax.Array, Dict[str, jax.Array], L.MoEStats]:
    """Run S_new tokens against the cache starting at ``cache['length']``.

    Returns (hidden (B, S_new, d), cache', stats).  ``cache'`` has the new K/V
    written but ``length`` unchanged — callers commit via kvcache.rollback
    (for SLED: after the acceptance count is known).  ``stats`` sums the MoE
    layers' MoEStats: the aux loss, and the rows each held expert computed
    (None for a stack without MoE layers).

    With ``slots``, cache leaves keep their pool shape (L, n_pool, H, S, D)
    end to end: per-row lengths come from ``length[slots]``, the K+1 fresh
    K/V rows are scattered straight into pool rows ``slots``, and attention
    streams slot-indexed chunks out of the stacked pool — no dense gathered
    sub-cache and no per-layer write-back ever exist.  This is the XLA
    mirror of the Pallas ``verify_attention_paged`` kernel's addressing.
    Latent attention's ``ckv``/``kpe`` leaves always take this path (row b
    for batch row b without ``slots``).  ``cfg.first_k_dense`` leading dense
    layers run unrolled ahead of the loop: a loop of their own would hand
    the cache to the main loop through a second cache-sized buffer.
    """
    x = L.embed_lookup(params["embed"], tokens, ctx) if embeds is None else embeds.astype(jnp.bfloat16)
    B, S, _ = x.shape
    cache_len = cache["length"] if slots is None else jnp.take(cache["length"], slots, axis=0)
    positions = cache_len[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    if "pos_embed" in params:
        x = x + params["pos_embed"][positions]
    cross_len = None
    if cfg.is_encdec:
        cross_len = jnp.full((B,), cache["cross_k"].shape[3], jnp.int32)

    # fori_loop carrying the FULL cache buffers, updated in place: a scan
    # with cache xs/ys double-buffers the whole KV cache (2x HBM for the
    # largest tensor of the serving path).  Only the S new K/V rows are
    # scattered in, and attention streams chunks straight from the stacked
    # buffer — per-step cache traffic is one read + an O(B*S_new) write,
    # which is the roofline minimum for verification.
    def idx(a, l):
        return jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False)

    if cfg.kv_lora_rank:
        names = ("ckv", "kpe")
    elif "k_scale" in cache:  # int8 cache: thread the scale leaves too
        names = ("k", "v", "k_scale", "v_scale")
    else:
        names = ("k", "v")
    resident = slots is not None or bool(cfg.kv_lora_rank)

    def body(l, carry, layers=params["layers"], offset=0):
        # slice the layer's cache out, append + attend, write back in place.
        # (Streaming chunks straight from the stacked buffer inside the
        # flash scan re-materialises the stack as a while-loop operand on
        # some backends — the per-layer slice is the portable fast path;
        # the "split" cache layout below removes even this copy.)
        h, kv, aux = carry[:3]
        lp = jax.tree.map(lambda a: idx(a, l), layers)
        cross = (idx(cache["cross_k"], l), idx(cache["cross_v"], l)) if cfg.is_encdec else None
        kw = dict(positions=positions, cache_len=cache_len, cross=cross, cross_len=cross_len,
                  attn_chunk=attn_chunk, valid=valid)
        if resident:
            # pool-resident path: hand the whole stacked pool to the block
            # (cache_layer addressing); fresh rows scatter into slot rows,
            # attention slot-indexes its chunks, nothing is written back
            # wholesale — the carry is updated only at the fresh rows.
            h, kv, st = _block(h, lp, cfg, ctx, kv=kv[:2], kv_scales=kv[2:] or None,
                               cache_layer=l + offset if offset else l, slots=slots, **kw)
        else:
            h, new_kv, st = _block(
                h, lp, cfg, ctx, kv=(idx(kv[0], l), idx(kv[1], l)),
                kv_scales=(idx(kv[2], l), idx(kv[3], l)) if len(kv) > 2 else None, **kw,
            )
            kv = tuple(jax.lax.dynamic_update_index_in_dim(a, n, l, 0) for a, n in zip(kv, new_kv))
        out = (h, tuple(kv), aux + st.aux)
        if len(carry) > 3:  # rows per held expert, summed over MoE layers
            out += (carry[3] if st.rows is None else carry[3] + st.rows,)
        return out

    carry = (x, tuple(cache[n] for n in names), jnp.zeros((), jnp.float32))
    if cfg.family == "moe":
        carry += (jnp.zeros((cfg.held[1],), jnp.int32),)
    for l in range(cfg.first_k_dense):
        carry = body(l, carry, params["dense_layers"])
    loop = body
    if cfg.first_k_dense:
        loop = lambda l, c: body(l, c, params["layers"], cfg.first_k_dense)
    carry = jax.lax.fori_loop(0, cfg.num_layers - cfg.first_k_dense, loop, carry)
    x, kv, aux = carry[:3]
    x = L.apply_norm(x, params["final_norm"], cfg)
    stats = L.MoEStats(aux, carry[3] if len(carry) > 3 else None)
    return x, {**cache, **dict(zip(names, kv))}, stats


def prefill(
    cfg,
    params: Params,
    tokens: jax.Array,  # (B, S_prompt)
    cache: Dict[str, jax.Array],
    ctx: MeshContext = NO_MESH,
    *,
    embeds_prefix: Optional[jax.Array] = None,
    enc_frames: Optional[jax.Array] = None,
    attn_chunk: int = 1024,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Fill the cache from a prompt; returns (last-position logits, cache)."""
    if cfg.is_encdec:
        enc_out = encode(cfg, params, enc_frames, ctx, attn_chunk=attn_chunk)
        cks, cvs = jax.lax.map(lambda lp: _cross_kv(lp, enc_out, cfg), params["layers"])
        cache = {**cache, "cross_k": cks, "cross_v": cvs}
    embeds = None
    if embeds_prefix is not None:
        tok_emb = params["embed"][tokens]
        embeds = jnp.concatenate([embeds_prefix.astype(tok_emb.dtype), tok_emb], axis=1)
    h, cache, _ = decode_forward(cfg, params, cache, tokens, ctx, embeds=embeds,
                                 attn_chunk=attn_chunk)
    S_total = h.shape[1]
    cache["length"] = cache["length"] + S_total
    logits = lm_head(cfg, params, h[:, -1:, :])
    return logits[:, 0], cache


def _cross_kv(lp, enc_out, cfg):
    """A layer's cross-attention K/V of the encoder output, head-major
    (B, Hkv, F, D) like every cache leaf."""
    B = enc_out.shape[0]
    return tuple((enc_out @ lp["xattn"][w]).reshape(B, -1, cfg.num_kv_heads, cfg.head_dim)
                 .swapaxes(1, 2) for w in ("wk", "wv"))
