"""Plain reference for a dense decoder-only transformer (Qwen2, Phi-3).

Written from the published architecture, in float32 with
``jax.default_matmul_precision("highest")``: token embedding, then per
layer RMSNorm -> attention (RoPE on q and k, rotate-half convention;
grouped K/V heads; causal softmax over the whole sequence) -> residual ->
RMSNorm -> SwiGLU MLP -> residual; a final RMSNorm and the output head (the
embedding, transposed, where the configuration ties them).  No cache, no
batching tricks, no kernels.  It imports nothing of the program.

The weights are made here too, from the seed: every matrix and bias is
normal with the configuration's ``initializer_range`` and every norm scale
is ``1 + normal * 0.1`` (so that biases and norm scales are checked too).
They are rounded to bfloat16, the type they are served in.  Layer ``l``
comes from ``fold_in(key, l)``, so the reference can make one layer at a
time and never hold the whole model in float32.

:func:`program_params` lays the same values out as the program's parameter
tree, in one jitted call on the device.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NORM_JITTER = 0.1


class Dims:
    """The sizes the reference needs, read from a config file's HF keys."""

    def __init__(self, c: dict):
        self.L = int(c["num_hidden_layers"])
        self.d = int(c["hidden_size"])
        self.hq = int(c["num_attention_heads"])
        self.hkv = int(c["num_key_value_heads"])
        self.hd = int(c.get("head_dim") or self.d // self.hq)
        self.f = int(c["intermediate_size"])
        self.V = int(c["vocab_size"])
        self.eps = float(c["rms_norm_eps"])
        self.theta = float(c["rope_theta"])
        self.tied = bool(c.get("tie_word_embeddings", False))
        # Qwen2's q/k/v projections carry biases by architecture; Phi-3 and
        # Llama-style configs say so with attention_bias
        self.qkv_bias = c.get("model_type") == "qwen2" or bool(c.get("attention_bias", False))
        self.std = float(c["initializer_range"])
        if c.get("hidden_act") != "silu":
            raise ValueError(f"reference implements SwiGLU (silu), not {c.get('hidden_act')!r}")


def base_key(seed: int) -> jax.Array:
    """A key from any whole number up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), (seed >> 32) & 0xFFFFFFFF)


def _bf16(x):
    return x.astype(jnp.bfloat16)


def make_layer(m: Dims, key: jax.Array) -> Dict[str, jax.Array]:
    """One layer's weights in bfloat16 (norm scales float32, bf16-valued)."""
    ks = jax.random.split(key, 12)
    n = lambda k, shp: _bf16(jax.random.normal(k, shp, jnp.float32) * m.std)
    w = {
        "ln1": _bf16(1.0 + NORM_JITTER * jax.random.normal(ks[0], (m.d,))).astype(jnp.float32),
        "ln2": _bf16(1.0 + NORM_JITTER * jax.random.normal(ks[1], (m.d,))).astype(jnp.float32),
        "wq": n(ks[2], (m.d, m.hq * m.hd)),
        "wk": n(ks[3], (m.d, m.hkv * m.hd)),
        "wv": n(ks[4], (m.d, m.hkv * m.hd)),
        "wo": n(ks[5], (m.hq * m.hd, m.d)),
        "wg": n(ks[6], (m.d, m.f)),
        "wu": n(ks[7], (m.d, m.f)),
        "wd": n(ks[8], (m.f, m.d)),
    }
    if m.qkv_bias:
        w["bq"] = n(ks[9], (m.hq * m.hd,))
        w["bk"] = n(ks[10], (m.hkv * m.hd,))
        w["bv"] = n(ks[11], (m.hkv * m.hd,))
    return w


def make_globals(m: Dims, key: jax.Array) -> Dict[str, jax.Array]:
    ke, kh, kn = jax.random.split(key, 3)
    g = {
        "embed": _bf16(jax.random.normal(ke, (m.V, m.d), jnp.float32) * m.std),
        "final_norm": _bf16(1.0 + NORM_JITTER * jax.random.normal(kn, (m.d,))).astype(jnp.float32),
    }
    if not m.tied:
        g["lm_head"] = _bf16(jax.random.normal(kh, (m.d, m.V), jnp.float32) * m.std)
    return g


def _keys(seed: int) -> Tuple[jax.Array, jax.Array]:
    kg, kl = jax.random.split(base_key(seed))
    return kg, kl


def program_params(config: dict, seed: int):
    """The program's parameter tree (``models/transformer.init_params``
    layout), made on the device in one jitted call."""
    m = Dims(config)

    @jax.jit
    def make(kg, kl):
        g = make_globals(m, kg)
        lw = jax.lax.map(lambda l: make_layer(m, jax.random.fold_in(kl, l)),
                         jnp.arange(m.L, dtype=jnp.uint32))
        attn = {k: lw[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv") if k in lw}
        p = {
            "embed": g["embed"],
            "final_norm": {"scale": g["final_norm"]},
            "layers": {
                "ln1": {"scale": lw["ln1"]},
                "attn": attn,
                "ln2": {"scale": lw["ln2"]},
                "mlp": {"wg": lw["wg"], "wu": lw["wu"], "wd": lw["wd"]},
            },
        }
        if "lm_head" in g:
            p["lm_head"] = g["lm_head"]
        return p

    return make(*_keys(seed))


# ---------------------------------------------------------------------------
# forward, one layer at a time
# ---------------------------------------------------------------------------


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (n, T, H, D) at positions pos (T,): rotate-half RoPE."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float64) * 2.0 / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _int8_matmul(x, w):
    """Control path: x per row, w per output column, both symmetric int8,
    multiplied in int8 with int32 accumulation, rescaled to float32."""
    sx = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-12) / 127.0
    sw = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-12) / 127.0
    xq = jnp.clip(jnp.round(x / sx), -127, 127).astype(jnp.int8)
    wq = jnp.clip(jnp.round(w / sw), -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def _layer(m: Dims, int8: bool):
    mm = _int8_matmul if int8 else (lambda x, w: x @ w)

    def layer(h, w):
        n_, T, _ = h.shape
        pos = jnp.arange(T, dtype=jnp.int32)
        x = _rmsnorm(h, w["ln1"], m.eps)
        q, k, v = mm(x, w["wq"]), mm(x, w["wk"]), mm(x, w["wv"])
        if "bq" in w:
            q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
        q = _rope(q.reshape(n_, T, m.hq, m.hd), pos, m.theta)
        k = _rope(k.reshape(n_, T, m.hkv, m.hd), pos, m.theta)
        v = v.reshape(n_, T, m.hkv, m.hd)
        g = m.hq // m.hkv
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / math.sqrt(m.hd)
        causal = pos[:, None] >= pos[None, :]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, axis=-1), v)
        h = h + mm(a.reshape(n_, T, m.hq * m.hd), w["wo"])
        x = _rmsnorm(h, w["ln2"], m.eps)
        return h + mm(jax.nn.silu(mm(x, w["wg"])) * mm(x, w["wu"]), w["wd"])

    return layer


def served_gaps(
    config: dict,
    seed: int,
    sequences: Sequence[np.ndarray],
    served_from: Sequence[int],
    *,
    control: bool = False,
    block: int = 8,
    rows: int = 256,
    length: int = 0,
) -> Dict[str, np.ndarray]:
    """Score every served token of every sequence.

    ``sequences[i]`` is a prompt followed by the tokens served for it, and
    ``served_from[i]`` the index of its first served token.  Position ``t``
    (for ``t >= served_from[i]``) is judged by the logits at ``t - 1``.

    Returns ``gap``: per sequence, per served token, the float32
    reference's best logit minus its logit for the served token (0 where
    the served token is the reference's own choice).  With ``control``,
    also ``control_gap``: the same reference gap for the token that an
    int8 forward (weights and activations) puts first at each of those
    positions.  Sequences run ``block`` at a time, padded to ``length``
    (or the longest, rounded up to 64), and the head scores ``rows``
    positions at a time, so each program compiles once.
    """
    m = Dims(config)
    kg, kl = _keys(seed)
    n = len(sequences)
    T = max(max(len(s) for s in sequences), length)
    T = -(-T // 64) * 64
    with jax.default_matmul_precision("highest"):
        gw = jax.jit(lambda k: make_globals(m, k))(kg)
        emb = gw["embed"].astype(jnp.float32)
        head = emb.T if m.tied else gw["lm_head"].astype(jnp.float32)
        fnorm = gw["final_norm"]
        make_l = jax.jit(lambda k, l: jax.tree.map(lambda a: a.astype(jnp.float32),
                                                   make_layer(m, jax.random.fold_in(k, l))))
        layer = jax.jit(_layer(m, False))
        layer8 = jax.jit(_layer(m, True))

        @jax.jit
        def score(hid, toks, hid8, head, fnorm):
            logits = _rmsnorm(hid, fnorm, m.eps) @ head
            best = logits.max(axis=-1)
            gap = best - jnp.take_along_axis(logits, toks[:, None], axis=-1)[:, 0]
            logits8 = _int8_matmul(_rmsnorm(hid8, fnorm, m.eps), head)
            pick = jnp.argmax(logits8, axis=-1)
            return gap, best - jnp.take_along_axis(logits, pick[:, None], axis=-1)[:, 0]

        blocks = []
        for b0 in range(0, n, block):
            idx = list(range(b0, min(b0 + block, n)))
            tk = np.zeros((block, T), np.int32)
            for r, i in enumerate(idx):
                tk[r, : len(sequences[i])] = sequences[i]
            h = emb[jnp.asarray(tk)]
            blocks.append([idx, tk, h, h])
        for l in range(m.L):  # each layer's weights are made once, for every block
            w = make_l(kl, jnp.uint32(l))
            for b in blocks:
                b[2] = layer(b[2], w)
                if control:
                    b[3] = layer8(b[3], w)
        hid, hid8, toks, owner = [], [], [], []
        for idx, tk, h, h8 in blocks:
            for r, i in enumerate(idx):
                t = np.arange(served_from[i], len(sequences[i]))
                hid.append(h[r, t - 1])
                hid8.append(h8[r, t - 1])
                toks.append(tk[r, t])
                owner.append(np.full(t.size, i))
        hid, hid8 = jnp.concatenate(hid), jnp.concatenate(hid8)
        toks, owner = np.concatenate(toks), np.concatenate(owner)
        N = toks.size
        pad = -N % rows
        hid = jnp.pad(hid, ((0, pad), (0, 0)))
        hid8 = jnp.pad(hid8, ((0, pad), (0, 0)))
        tk = jnp.asarray(np.pad(toks, (0, pad)))
        g, c = [], []
        for r0 in range(0, N + pad, rows):
            gg, cc = score(hid[r0:r0 + rows], tk[r0:r0 + rows], hid8[r0:r0 + rows], head, fnorm)
            g.append(np.asarray(gg))
            c.append(np.asarray(cc))
        g, c = np.concatenate(g)[:N], np.concatenate(c)[:N]
    out = {"gap": [g[owner == i] for i in range(n)]}
    if control:
        out["control_gap"] = [c[owner == i] for i in range(n)]
    return out
