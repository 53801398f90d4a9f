"""Zamba2-style hybrid: Mamba2 backbone + one shared (weight-tied) attention block.

The shared block is applied after every ``cfg.attn_every`` SSM layers.  Its
weights are tied across applications (the zamba2 trick that keeps the
parameter count low), but each application has its own KV cache slice.

Speculative rollback: SSM layers checkpoint per-position states (mamba2.py),
the shared-attention caches roll back via ``length`` like any KV cache.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import mamba2 as M2
from repro.models.layers import MeshContext, NO_MESH
from repro.models.transformer import _block

Params = Dict[str, Any]


def n_apps(cfg) -> int:
    return cfg.num_layers // cfg.attn_every


def _split_groups(cfg, stacked):
    """(L, ...) stacked ssm params -> ((n_apps, attn_every, ...), (tail, ...))."""
    na, ae = n_apps(cfg), cfg.attn_every
    full = na * ae

    def grp(a):
        return a[:full].reshape(na, ae, *a.shape[1:])

    def tail(a):
        return a[full:]

    return jax.tree.map(grp, stacked), jax.tree.map(tail, stacked)


def init_params(cfg, key, **_) -> Params:
    k_emb, k_ssm, k_attn, k_head = jax.random.split(key, 4)
    keys = jax.random.split(k_ssm, cfg.num_layers)
    ka1, ka2 = jax.random.split(k_attn)
    p: Params = {
        "embed": (jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model)) * 0.02).astype(jnp.bfloat16),
        "ssm_layers": jax.vmap(lambda k: M2.init_ssd_layer(cfg, k))(keys),
        "shared_attn": {
            "ln1": L.init_norm(cfg.d_model, cfg.norm),
            "attn": L.init_attention(ka1, cfg),
            "ln2": L.init_norm(cfg.d_model, cfg.norm),
            "mlp": L.init_mlp(ka2, cfg),
        },
        "final_norm": L.init_norm(cfg.d_model, cfg.norm),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = (jax.random.normal(k_head, (cfg.d_model, cfg.vocab_size)) * 0.02).astype(jnp.bfloat16)
    return p


lm_head = M2.lm_head


def make_cache(cfg, batch: int, max_len: int, *, spec_only: bool = False,
               attn_chunk: int = 1024, **_):
    max_len = -(-max_len // attn_chunk) * attn_chunk
    ssm = M2.make_cache(cfg, batch, spec_only=spec_only)
    na = n_apps(cfg)
    kv_shape = (na, batch, cfg.num_kv_heads, max_len, cfg.head_dim)
    if spec_only:
        kv = {
            "k": jax.ShapeDtypeStruct(kv_shape, jnp.bfloat16),
            "v": jax.ShapeDtypeStruct(kv_shape, jnp.bfloat16),
        }
    else:
        kv = {"k": jnp.zeros(kv_shape, jnp.bfloat16), "v": jnp.zeros(kv_shape, jnp.bfloat16)}
    return {**ssm, **kv}


def forward(cfg, params, tokens, ctx: MeshContext = NO_MESH, *, remat=False,
            attn_chunk: int = 1024, **_):
    x = L.embed_lookup(params["embed"], tokens, ctx)
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    groups, tail = _split_groups(cfg, params["ssm_layers"])

    def ssm_body(h, lp):
        return M2.ssd_layer_forward(cfg, lp, h, remat_inner=remat), None

    if remat:
        ssm_body = jax.checkpoint(ssm_body, prevent_cse=False)

    def group_body(h, grp_params):
        h, _ = jax.lax.scan(ssm_body, h, grp_params)
        h, _, _ = _block(h, params["shared_attn"], cfg, ctx, positions=positions,
                         attn_chunk=attn_chunk, flash_remat=remat)
        return h, None

    x, _ = jax.lax.scan(group_body, x, groups)
    if cfg.num_layers % cfg.attn_every:
        x, _ = jax.lax.scan(ssm_body, x, tail)
    return L.apply_norm(x, params["final_norm"], cfg), jnp.zeros((), jnp.float32)


def _run_cached(cfg, params, cache, tokens, ctx, attn_chunk, decode: bool):
    """Shared prefill/decode machinery. decode=True emits SSM checkpoints."""
    x = L.embed_lookup(params["embed"], tokens, ctx)
    B, S = tokens.shape
    cache_len = cache["length"]
    positions = cache_len[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    groups, tail = _split_groups(cfg, params["ssm_layers"])
    g_ssm, t_ssm = _split_groups(cfg, cache["ssm"])
    g_conv, t_conv = _split_groups(cfg, cache["conv"])

    def ssm_step(h, xs):
        lp, h0, c0 = xs
        if decode:
            out, h_ck, c_ck = M2.ssd_layer_decode(cfg, lp, h, h0, c0)
            return out, (h_ck, c_ck)
        out, (hf, cf) = M2.ssd_layer_forward(cfg, lp, h, h0=h0, conv0=c0, return_state=True)
        return out, (hf, cf.astype(jnp.bfloat16))

    def group_body(h, xs):
        grp, h0s, c0s, kl, vl = xs
        h, states = jax.lax.scan(ssm_step, h, (grp, h0s, c0s))
        h, new_kv, _ = _block(h, params["shared_attn"], cfg, ctx, positions=positions,
                              kv=(kl, vl), cache_len=cache_len, attn_chunk=attn_chunk)
        return h, (states, new_kv)

    x, (g_states, new_kv) = jax.lax.scan(
        group_body, x, (groups, g_ssm, g_conv, cache["k"], cache["v"])
    )
    if cfg.num_layers % cfg.attn_every:
        x, t_states = jax.lax.scan(ssm_step, x, (tail, t_ssm, t_conv))
    else:
        t_states = jax.tree.map(lambda a: a[0][:0], g_states)  # empty (0, B, ...)

    def merge(g, t):  # (na, ae, B, ...) + (tail, B, ...) -> (L, B, ...)
        return jnp.concatenate([g.reshape(-1, *g.shape[2:]), t], axis=0)

    ssm_s, conv_s = jax.tree.map(merge, g_states[0], t_states[0]), jax.tree.map(
        merge, g_states[1], t_states[1]
    )
    x = L.apply_norm(x, params["final_norm"], cfg)
    return x, ssm_s, conv_s, new_kv


def prefill(cfg, params, tokens, cache, ctx: MeshContext = NO_MESH, *,
            attn_chunk: int = 1024, **_):
    x, ssm_s, conv_s, new_kv = _run_cached(cfg, params, cache, tokens, ctx, attn_chunk, False)
    new_cache = {
        "ssm": ssm_s, "conv": conv_s, "k": new_kv[0], "v": new_kv[1],
        "length": cache["length"] + tokens.shape[1],
    }
    return lm_head(cfg, params, x[:, -1:, :])[:, 0], new_cache


def decode_forward(cfg, params, cache, tokens, ctx: MeshContext = NO_MESH, *,
                   attn_chunk: int = 1024, slots=None, **_):
    if slots is not None:
        raise NotImplementedError(
            "slot-indexed paged attention is not supported for the 'hybrid' "
            "family: its recurrent state leaves (ssm, conv) are not position-"
            "indexed K/V, so pool rows cannot be addressed in place.  Route "
            "this model through the gather/scatter fallback instead (the "
            "verify and extend steps choose it by "
            "models.kvcache.supports_paged_attention(cfg))."
        )
    x, ssm_ck, conv_ck, new_kv = _run_cached(cfg, params, cache, tokens, ctx, attn_chunk, True)
    ckpt_cache = {**cache, "k": new_kv[0], "v": new_kv[1],
                  "ssm_ckpt": ssm_ck, "conv_ckpt": conv_ck}
    return x, ckpt_cache, L.MoEStats(jnp.zeros((), jnp.float32), None)


def select_checkpoint(cache: Dict[str, jax.Array], n_commit: jax.Array) -> Dict[str, jax.Array]:
    i = (n_commit - 1).astype(jnp.int32)
    b = jnp.arange(cache["ssm_ckpt"].shape[1])

    def take(a):
        return a[:, b, i]

    return {
        "ssm": take(cache["ssm_ckpt"]).astype(jnp.float32),
        "conv": take(cache["conv_ckpt"]),
        "k": cache["k"], "v": cache["v"],
        "length": cache["length"] + n_commit.astype(jnp.int32),
    }
