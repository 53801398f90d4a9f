"""Mamba2 (SSD — state-space duality) in pure JAX.

Training/prefill use the chunked matmul form (intra-chunk quadratic +
sequential inter-chunk state pass via lax.scan — TPU-friendly: the quadratic
part is MXU matmuls, the scan carries only the (B, H, P, N) state).  Decode
is the O(1) recurrence.

Speculative verification support: SSM/conv states cannot be rolled back by
masking (unlike KV caches), so ``decode_forward`` emits per-position state
CHECKPOINTS for each of the K+1 fed tokens; ``select_checkpoint`` commits the
state at the acceptance boundary.  This is the SSM-specific piece of SLED's
server-side verify step.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.layers import MeshContext, NO_MESH

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# SSD layer
# ---------------------------------------------------------------------------


def conv_dim(cfg) -> int:
    return cfg.d_inner + 2 * cfg.ssm_state  # x, B, C share the causal conv


def init_ssd_layer(cfg, key) -> Params:
    d, di = cfg.d_model, cfg.d_inner
    H, N, cw = cfg.ssm_heads, cfg.ssm_state, cfg.conv_width
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d_in_proj = 2 * di + 2 * N + H  # z, x, B, C, dt
    std = 0.02
    out_std = std / math.sqrt(2 * max(cfg.num_layers, 1))
    return {
        "norm": L.init_norm(d, cfg.norm),
        "in_proj": (jax.random.normal(k1, (d, d_in_proj)) * std).astype(jnp.bfloat16),
        "conv_w": (jax.random.normal(k2, (cw, conv_dim(cfg))) * 0.2).astype(jnp.bfloat16),
        "conv_b": jnp.zeros((conv_dim(cfg),), jnp.bfloat16),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((H,), 0.01))).astype(jnp.float32),
        "gnorm": jnp.ones((di,), jnp.float32),
        "out_proj": (jax.random.normal(k4, (di, d)) * out_std).astype(jnp.bfloat16),
    }


def _split_in_proj(cfg, zxbcdt):
    di, N, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di : 2 * di + 2 * N]
    dt = zxbcdt[..., 2 * di + 2 * N :]
    return z, xBC, dt


def _causal_conv(u: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. u: (B, S, C), w: (cw, C)."""
    cw = w.shape[0]
    up = jnp.pad(u, ((0, 0), (cw - 1, 0), (0, 0)))
    S = u.shape[1]
    y = sum(up[:, i : i + S] * w[i][None, None] for i in range(cw))
    return y + b[None, None]


def _gated_out(cfg, lp, y: jax.Array, z: jax.Array) -> jax.Array:
    """Mamba2 RMSNormGated + out_proj. y, z: (B, S, di)."""
    y = L.rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype), lp["gnorm"])
    return y.astype(jnp.bfloat16) @ lp["out_proj"]


def ssd_chunked(
    x: jax.Array,   # (B, S, H, P)
    dt: jax.Array,  # (B, S, H) post-softplus, fp32
    A: jax.Array,   # (H,) negative, fp32
    Bm: jax.Array,  # (B, S, N)
    Cm: jax.Array,  # (B, S, N)
    chunk: int,
    h0: Optional[jax.Array] = None,  # (B, H, P, N) fp32
    remat: bool = False,  # don't save per-chunk (Q,Q) decay/score tensors
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD. Returns (y (B,S,H,P), final state (B,H,P,N))."""
    B, S, H, Pd = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    nc = Sp // Q

    def to_chunks(a):
        return jnp.moveaxis(a.reshape(B, nc, Q, *a.shape[2:]), 1, 0)

    a_log = (dt * A[None, None]).astype(jnp.float32)  # (B,Sp,H) log-decays
    xs = (to_chunks(x), to_chunks(dt), to_chunks(a_log), to_chunks(Bm), to_chunks(Cm))
    h_init = jnp.zeros((B, H, Pd, N), jnp.float32) if h0 is None else h0.astype(jnp.float32)
    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def body(h, inp):
        xq, dtq, aq, Bq, Cq = inp
        cum = jnp.cumsum(aq, axis=1)  # (B,Q,H)
        # carry-in contribution: y_i += exp(cum_i) * C_i . h
        y_off = jnp.einsum("bqn,bhpn->bqhp", Cq.astype(jnp.float32), h) * jnp.exp(cum)[..., None]
        # intra-chunk: W_ij = (C_i.B_j) exp(cum_i - cum_j) dt_j  (j <= i)
        seg = cum[:, :, None, :] - cum[:, None, :, :]  # (B,i,j,H)
        decay = jnp.where(tri[None, :, :, None], jnp.exp(seg), 0.0)
        cb = jnp.einsum("bin,bjn->bij", Cq.astype(jnp.float32), Bq.astype(jnp.float32))
        W = cb[..., None] * decay * dtq[:, None, :, :]
        y_diag = jnp.einsum("bijh,bjhp->bihp", W, xq.astype(jnp.float32))
        # chunk-end state update
        d_end = jnp.exp(cum[:, -1:, :] - cum) * dtq  # (B,Q,H)
        h_new = jnp.einsum("bqh,bqn,bqhp->bhpn", d_end, Bm_f := Bq.astype(jnp.float32), xq.astype(jnp.float32))
        h = jnp.exp(cum[:, -1])[..., None, None] * h + h_new
        return h, (y_off + y_diag).astype(x.dtype)

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    h, ys = jax.lax.scan(body, h_init, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, Sp, H, Pd)[:, :S]
    return y, h


def ssd_layer_forward(
    cfg, lp: Params, x: jax.Array, *, chunk: Optional[int] = None,
    h0: Optional[jax.Array] = None, conv0: Optional[jax.Array] = None,
    return_state: bool = False, remat_inner: bool = False,
):
    """Full-sequence SSD block. x: (B, S, d) -> (B, S, d)."""
    B, S, d = x.shape
    H, N, Pd = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim
    cw = cfg.conv_width
    zxbcdt = L.apply_norm(x, lp["norm"], cfg) @ lp["in_proj"]
    z, xBC, dt = _split_in_proj(cfg, zxbcdt)
    if conv0 is not None:
        full = jnp.concatenate([conv0.astype(xBC.dtype), xBC], axis=1)
        conv_out = _causal_conv(full, lp["conv_w"], lp["conv_b"])[:, cw - 1 :]
        new_conv = full[:, -(cw - 1) :]
    else:
        conv_out = _causal_conv(xBC, lp["conv_w"], lp["conv_b"])
        new_conv = xBC[:, -(cw - 1) :]
    xBC = jax.nn.silu(conv_out.astype(jnp.float32)).astype(jnp.bfloat16)
    di = cfg.d_inner
    x_ssm = xBC[..., :di].reshape(B, S, H, Pd)
    Bm = xBC[..., di : di + N]
    Cm = xBC[..., di + N :]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"][None, None])
    A = -jnp.exp(lp["A_log"])
    y, h = ssd_chunked(x_ssm, dt, A, Bm, Cm, chunk or cfg.ssm_chunk, h0=h0,
                       remat=remat_inner)
    y = y + lp["D"][None, None, :, None] * x_ssm.astype(jnp.float32)
    out = x + _gated_out(cfg, lp, y.reshape(B, S, di), z)
    if return_state:
        return out, (h, new_conv)
    return out


def ssd_layer_decode(
    cfg, lp: Params, x: jax.Array,  # (B, K, d) — the K+1 verify tokens
    h0: jax.Array,    # (B, H, P, N) fp32
    conv0: jax.Array,  # (B, cw-1, conv_dim)
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sequential decode over K tokens, emitting per-position checkpoints.

    Returns (out (B,K,d), h_ckpts (B,K,H,P,N) bf16, conv_ckpts (B,K,cw-1,C)).
    ``h_ckpts[:, i]`` is the SSM state after consuming token i — speculative
    rollback selects index ``n_accepted`` (see core/verification.py).
    """
    B, K, d = x.shape
    H, N, Pd = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim
    cw = cfg.conv_width
    zxbcdt = L.apply_norm(x, lp["norm"], cfg) @ lp["in_proj"]
    z, xBC, dt = _split_in_proj(cfg, zxbcdt)
    full = jnp.concatenate([conv0.astype(xBC.dtype), xBC], axis=1)  # (B, cw-1+K, C)
    conv_out = _causal_conv(full, lp["conv_w"], lp["conv_b"])[:, cw - 1 :]
    # conv checkpoints: the cw-1 window ending at each position
    idx = jnp.arange(K)[:, None] + jnp.arange(1, cw)[None]  # (K, cw-1)
    conv_ckpts = jnp.moveaxis(full[:, idx], 1, 1)  # (B, K, cw-1, C)
    xBC = jax.nn.silu(conv_out.astype(jnp.float32)).astype(jnp.bfloat16)
    di = cfg.d_inner
    x_ssm = xBC[..., :di].reshape(B, K, H, Pd).astype(jnp.float32)
    Bm = xBC[..., di : di + N].astype(jnp.float32)
    Cm = xBC[..., di + N :].astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"][None, None])  # (B,K,H)
    A = -jnp.exp(lp["A_log"])

    def step(h, inp):
        xt, dtt, Bt, Ct = inp  # (B,H,P), (B,H), (B,N), (B,N)
        decay = jnp.exp(dtt * A[None])  # (B,H)
        h = decay[..., None, None] * h + jnp.einsum(
            "bh,bn,bhp->bhpn", dtt, Bt, xt
        )
        y = jnp.einsum("bn,bhpn->bhp", Ct, h)
        return h, (y, h.astype(jnp.bfloat16))

    inps = (
        jnp.moveaxis(x_ssm, 1, 0), jnp.moveaxis(dt, 1, 0),
        jnp.moveaxis(Bm, 1, 0), jnp.moveaxis(Cm, 1, 0),
    )
    _, (ys, hs) = jax.lax.scan(step, h0.astype(jnp.float32), inps)
    y = jnp.moveaxis(ys, 0, 1)  # (B,K,H,P)
    h_ckpts = jnp.moveaxis(hs, 0, 1)  # (B,K,H,P,N)
    y = y + lp["D"][None, None, :, None] * x_ssm
    out = x + _gated_out(cfg, lp, y.reshape(B, K, di), z)
    return out, h_ckpts, conv_ckpts


# ---------------------------------------------------------------------------
# Pure-SSM model (mamba2-370m)
# ---------------------------------------------------------------------------


def init_params(cfg, key, **_) -> Params:
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    keys = jax.random.split(k_layers, cfg.num_layers)
    p: Params = {
        "embed": (jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model)) * 0.02).astype(jnp.bfloat16),
        "layers": jax.vmap(lambda k: init_ssd_layer(cfg, k))(keys),
        "final_norm": L.init_norm(cfg.d_model, cfg.norm),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = (jax.random.normal(k_head, (cfg.d_model, cfg.vocab_size)) * 0.02).astype(jnp.bfloat16)
    return p


def lm_head(cfg, params, h):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("bsd,dv->bsv", h, w, preferred_element_type=jnp.float32)


def make_cache(cfg, batch: int, max_len: int = 0, *, spec_only: bool = False, **_):
    """SSM cache: O(1) in sequence length. ``max_len`` ignored (API parity)."""
    H, N, Pd, cw = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim, cfg.conv_width
    shapes = {
        "ssm": ((cfg.num_layers, batch, H, Pd, N), jnp.float32),
        "conv": ((cfg.num_layers, batch, cw - 1, conv_dim(cfg)), jnp.bfloat16),
        "length": ((batch,), jnp.int32),
    }
    if spec_only:
        return {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in shapes.items()}
    return {k: jnp.zeros(s, d) for k, (s, d) in shapes.items()}


def forward(cfg, params, tokens, ctx: MeshContext = NO_MESH, *, remat=False, **_):
    x = L.embed_lookup(params["embed"], tokens, ctx)

    def body(h, lp):
        return ssd_layer_forward(cfg, lp, h, remat_inner=remat), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return L.apply_norm(x, params["final_norm"], cfg), jnp.zeros((), jnp.float32)


def prefill(cfg, params, tokens, cache, ctx: MeshContext = NO_MESH, **_):
    x = L.embed_lookup(params["embed"], tokens, ctx)

    def body(h, xs):
        lp, h0, c0 = xs
        out, (hf, cf) = ssd_layer_forward(cfg, lp, h, h0=h0, conv0=c0, return_state=True)
        return out, (hf, cf.astype(jnp.bfloat16))

    x, (hs, convs) = jax.lax.scan(body, x, (params["layers"], cache["ssm"], cache["conv"]))
    x = L.apply_norm(x, params["final_norm"], cfg)
    new_cache = {"ssm": hs, "conv": convs, "length": cache["length"] + tokens.shape[1]}
    return lm_head(cfg, params, x[:, -1:, :])[:, 0], new_cache


def decode_forward(cfg, params, cache, tokens, ctx: MeshContext = NO_MESH, *,
                   slots=None, **_):
    """Verify-style decode: K tokens, per-position state checkpoints.

    Returns (h (B,K,d), ckpt_cache, aux).  ``ckpt_cache['ssm']`` has an extra
    K axis: (L, B, K, H, P, N); commit with select_checkpoint(ckpt_cache, n).
    """
    if slots is not None:
        raise NotImplementedError(
            "slot-indexed paged attention is not supported for the 'ssm' family: "
            "recurrent state leaves (ssm, conv) are not position-indexed K/V, so "
            "pool rows cannot be addressed in place.  Route this model through "
            "the gather/scatter fallback instead (the verify and extend steps "
            "choose it by models.kvcache.supports_paged_attention(cfg))."
        )
    x = L.embed_lookup(params["embed"], tokens, ctx)

    def body(h, xs):
        lp, h0, c0 = xs
        out, h_ck, c_ck = ssd_layer_decode(cfg, lp, h, h0, c0)
        return out, (h_ck, c_ck)

    x, (h_cks, c_cks) = jax.lax.scan(body, x, (params["layers"], cache["ssm"], cache["conv"]))
    x = L.apply_norm(x, params["final_norm"], cfg)
    ckpt_cache = {**cache, "ssm_ckpt": h_cks, "conv_ckpt": c_cks}
    return x, ckpt_cache, L.MoEStats(jnp.zeros((), jnp.float32), None)


def select_checkpoint(cache: Dict[str, jax.Array], n_commit: jax.Array) -> Dict[str, jax.Array]:
    """Commit the state after ``n_commit`` tokens (per row), n_commit >= 1.

    ``n_commit = m + 1`` where m is the accepted-draft count (the first fed
    token is the previously-committed one, always kept).
    """
    i = (n_commit - 1).astype(jnp.int32)  # checkpoint index per row
    b = jnp.arange(cache["ssm_ckpt"].shape[1])

    def take(a):  # a: (L, B, K, ...) -> (L, B, ...)
        return a[:, b, i]

    return {
        "ssm": take(cache["ssm_ckpt"]).astype(jnp.float32),
        "conv": take(cache["conv_ckpt"]),
        "length": cache["length"] + n_commit.astype(jnp.int32),
    }
