"""Plain reference for a DeepSeek-V3 decoder (Moonlight-16B-A3B).

Written from the published architecture (HF ``modeling_deepseek.py``,
``model_type`` deepseek_v3), in float32 with
``jax.default_matmul_precision("highest")``: token embedding, then per
layer ``h = x + MLA(RMSNorm(x))``, ``x' = h + FFN(RMSNorm(h))``; a final
RMSNorm and the untied head.  No cache, no batching tricks, no kernels.  It
imports nothing of the program.

MLA (no q low rank): ``[q_nope | q_pe] = x W_q`` per head; ``[c | k_pe] =
x W_kva``; ``c~ = RMSNorm(c)``; ``[k_nope | v] = c~ W_kvb`` per head; RoPE
on q_pe and on k_pe (one head shared by all heads); scores
``(q_nope.k_nope + q_pe.k_pe) / sqrt(nope + rope)``, causal softmax; output
``concat_h(sum p v) W_o``.  This is the plain form: k_nope and v are made
for every position.  One departure: the published code de-interleaves the
rope columns of q_pe and k_pe before rotate-half; here rotate-half acts on
them as stored.  That is a fixed permutation of the rope columns of W_q and
W_kva, which weights drawn from a seed cannot tell apart.

FFN: the first ``first_k_dense_replace`` layers are a SwiGLU MLP of width
``intermediate_size``.  The others are MoE: ``s = sigmoid(x W_r)`` over all
the published experts; the top ``num_experts_per_tok`` by ``s + b`` (b the
selection bias; with one group, group-limited selection is plain top-k);
weights ``s_i / (sum_top s_j + 1e-20) * routed_scaling_factor``;
``y = sum_{i in top and held} w_i E_i(x) + S(x)``, E_i a SwiGLU of width
``moe_intermediate_size`` and S the shared SwiGLU of ``n_shared_experts``
times that width.  Experts this chip does not hold (``experts_held``) are
left out of the sum, as in the program: nothing stands in for them.

Weights come from the seed: matrices and the selection bias normal with
``initializer_range`` (the bias with ``BIAS_STD``), norm scales ``1 +
normal * 0.1``, all rounded to bfloat16, the type they are served in (the
router and the bias are served in float32, with bfloat16 values).  Layer
``l`` comes from ``fold_in(key, l)`` and routed expert ``e`` of it from
``fold_in(expert_key, e)`` with ``e`` its published index, so that the
chips of the deployment hold disjoint slices of one model, and the
reference makes one layer at a time.

:func:`program_params` lays the values out as the program's parameter tree
in one jitted call on the device.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NORM_JITTER = 0.1
BIAS_STD = 0.1  # selection bias: of the order of the score gaps, so selection != weighting


class Dims:
    """The sizes the reference needs, read from a config file's HF keys."""

    def __init__(self, c: dict):
        self.L = int(c["num_hidden_layers"])
        self.d = int(c["hidden_size"])
        self.H = int(c["num_attention_heads"])
        self.R = int(c["kv_lora_rank"])
        self.nope = int(c["qk_nope_head_dim"])
        self.rope = int(c["qk_rope_head_dim"])
        self.vd = int(c["v_head_dim"])
        self.f = int(c["intermediate_size"])
        self.fe = int(c["moe_intermediate_size"])
        self.E = int(c.get("n_routed_experts_published", c["n_routed_experts"]))
        self.first, held_end = c.get("experts_held", [0, self.E])
        self.count = held_end - self.first
        self.K = int(c["num_experts_per_tok"])
        self.n_shared = int(c["n_shared_experts"])
        self.k_dense = int(c["first_k_dense_replace"])
        self.scale = float(c["routed_scaling_factor"])
        self.V = int(c["vocab_size"])
        self.eps = float(c["rms_norm_eps"])
        self.theta = float(c["rope_theta"])
        self.std = float(c["initializer_range"])
        if c.get("q_lora_rank") is not None:
            raise ValueError("reference implements MLA without a q low rank (q_lora_rank null)")
        if (c["scoring_func"], c["topk_method"], c["norm_topk_prob"]) != ("sigmoid", "noaux_tc", True):
            raise ValueError("reference implements sigmoid scoring, noaux_tc selection, normalised top-k")
        if c["n_group"] != 1 or c["topk_group"] != 1 or c.get("hidden_act") != "silu":
            raise ValueError("reference implements one routing group and SwiGLU (silu)")
        if c.get("tie_word_embeddings", False):
            raise ValueError("reference implements an untied head")


def base_key(seed: int) -> jax.Array:
    """A key from any whole number up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), (seed >> 32) & 0xFFFFFFFF)


def _bf16(x):
    return x.astype(jnp.bfloat16)


def _norm_scale(k, n):
    return _bf16(1.0 + NORM_JITTER * jax.random.normal(k, (n,))).astype(jnp.float32)


def make_layer(m: Dims, key: jax.Array, moe: bool) -> Dict[str, jax.Array]:
    """One layer's weights in bfloat16 (norm scales, router and bias float32,
    bfloat16-valued); a MoE layer holds experts [first, first + count)."""
    ks = jax.random.split(key, 16)
    n = lambda k, shp, sd=m.std: _bf16(jax.random.normal(k, shp, jnp.float32) * sd)
    w = {
        "ln1": _norm_scale(ks[0], m.d),
        "ln2": _norm_scale(ks[1], m.d),
        "wq": n(ks[2], (m.d, m.H * (m.nope + m.rope))),
        "wkv_a": n(ks[3], (m.d, m.R + m.rope)),
        "kv_norm": _norm_scale(ks[4], m.R),
        "wkv_b": n(ks[5], (m.R, m.H * (m.nope + m.vd))),
        "wo": n(ks[6], (m.H * m.vd, m.d)),
    }
    if not moe:
        w.update(wg=n(ks[7], (m.d, m.f)), wu=n(ks[8], (m.d, m.f)), wd=n(ks[9], (m.f, m.d)))
        return w
    fs = m.n_shared * m.fe
    w.update(
        router=n(ks[10], (m.d, m.E)).astype(jnp.float32),
        router_bias=n(ks[11], (m.E,), BIAS_STD).astype(jnp.float32),
        sg=n(ks[12], (m.d, fs)), su=n(ks[13], (m.d, fs)), sd=n(ks[14], (fs, m.d)),
    )

    def expert(e):
        ke = jax.random.split(jax.random.fold_in(ks[15], e), 3)
        return n(ke[0], (m.d, m.fe)), n(ke[1], (m.d, m.fe)), n(ke[2], (m.fe, m.d))

    w["eg"], w["eu"], w["ed"] = jax.vmap(expert)(m.first + jnp.arange(m.count, dtype=jnp.uint32))
    return w


def make_globals(m: Dims, key: jax.Array) -> Dict[str, jax.Array]:
    ke, kh, kn = jax.random.split(key, 3)
    return {
        "embed": _bf16(jax.random.normal(ke, (m.V, m.d), jnp.float32) * m.std),
        "final_norm": _norm_scale(kn, m.d),
        "lm_head": _bf16(jax.random.normal(kh, (m.d, m.V), jnp.float32) * m.std),
    }


def _keys(seed: int) -> Tuple[jax.Array, jax.Array]:
    kg, kl = jax.random.split(base_key(seed))
    return kg, kl


def program_params(config: dict, seed: int):
    """The program's parameter tree (``models/transformer.init_params``
    layout for latent-attention MoE stacks), made on the device in one
    jitted call."""
    m = Dims(config)

    def attn(lw):
        return {"wq": lw["wq"], "wkv_a": lw["wkv_a"], "kv_norm": {"scale": lw["kv_norm"]},
                "wkv_b": lw["wkv_b"], "wo": lw["wo"]}

    @jax.jit
    def make(kg, kl):
        g = make_globals(m, kg)
        p = {"embed": g["embed"], "final_norm": {"scale": g["final_norm"]}, "lm_head": g["lm_head"]}
        if m.k_dense:
            dw = jax.lax.map(lambda l: make_layer(m, jax.random.fold_in(kl, l), False),
                             jnp.arange(m.k_dense, dtype=jnp.uint32))
            p["dense_layers"] = {
                "ln1": {"scale": dw["ln1"]}, "attn": attn(dw), "ln2": {"scale": dw["ln2"]},
                "mlp": {"wg": dw["wg"], "wu": dw["wu"], "wd": dw["wd"]},
            }
        lw = jax.lax.map(lambda l: make_layer(m, jax.random.fold_in(kl, l), True),
                         jnp.arange(m.k_dense, m.L, dtype=jnp.uint32))
        p["layers"] = {
            "ln1": {"scale": lw["ln1"]}, "attn": attn(lw), "ln2": {"scale": lw["ln2"]},
            "moe": {"router": lw["router"], "router_bias": lw["router_bias"],
                    "wg": lw["eg"], "wu": lw["eu"], "wd": lw["ed"]},
            "shared": {"wg": lw["sg"], "wu": lw["su"], "wd": lw["sd"]},
        }
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


def _swiglu(mm, x, wg, wu, wd):
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


def moe_ffn(m: Dims, w, x, int8: bool = False):
    """The MoE layer's FFN on normed x (..., d): the held experts' part of
    the routed sum plus the shared expert."""
    mm = _int8_matmul if int8 else (lambda a, b: a @ b)
    s = jax.nn.sigmoid(mm(x, w["router"]))  # (..., E)
    _, top = jax.lax.top_k(s + w["router_bias"], m.K)
    chosen = jax.nn.one_hot(top, m.E, dtype=jnp.float32).sum(-2)  # (..., E)
    gate = s * chosen
    gate = gate / (gate.sum(-1, keepdims=True) + 1e-20) * m.scale
    held = jnp.moveaxis(gate[..., m.first:m.first + m.count], -1, 0)  # (count, ...)

    def expert(y, e):  # one held expert, in index order; a loop keeps the program small
        g, wg, wu, wd = e
        return y + g[..., None] * _swiglu(mm, x, wg, wu, wd), None

    y, _ = jax.lax.scan(expert, _swiglu(mm, x, w["sg"], w["su"], w["sd"]),
                        (held, w["eg"], w["eu"], w["ed"]))
    return y


def _layer(m: Dims, moe: bool, int8: bool):
    mm = _int8_matmul if int8 else (lambda x, w: x @ w)

    def layer(h, w):
        n_, T, _ = h.shape
        pos = jnp.arange(T, dtype=jnp.int32)
        x = _rmsnorm(h, w["ln1"], m.eps)
        q = mm(x, w["wq"]).reshape(n_, T, m.H, m.nope + m.rope)
        q = jnp.concatenate([q[..., :m.nope], _rope(q[..., m.nope:], pos, m.theta)], axis=-1)
        kva = mm(x, w["wkv_a"])
        c = _rmsnorm(kva[..., :m.R], w["kv_norm"], m.eps)
        k_pe = _rope(kva[..., None, m.R:], pos, m.theta)  # (n, T, 1, rope)
        kv = mm(c, w["wkv_b"]).reshape(n_, T, m.H, m.nope + m.vd)
        k = jnp.concatenate([kv[..., :m.nope], jnp.broadcast_to(k_pe, (n_, T, m.H, m.rope))], axis=-1)
        v = kv[..., m.nope:]
        s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / math.sqrt(m.nope + m.rope)
        causal = pos[:, None] >= pos[None, :]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, axis=-1), v)
        h = h + mm(a.reshape(n_, T, m.H * m.vd), w["wo"])
        x = _rmsnorm(h, w["ln2"], m.eps)
        if moe:
            return h + moe_ffn(m, w, x, int8)
        return h + _swiglu(mm, x, w["wg"], w["wu"], w["wd"])

    return layer


def _hidden(m: Dims, seed: int, tokens, control: bool):
    """Final hidden states (before the last norm) of the float32 forward
    over ``tokens`` (list of (n, T) int arrays, run as separate blocks),
    and of the int8 control where ``control``; each layer's weights are
    made once for every block."""
    kg, kl = _keys(seed)
    gw = jax.jit(lambda k: make_globals(m, k))(kg)
    emb = gw["embed"].astype(jnp.float32)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    make_l = {moe: jax.jit(lambda k, l, moe=moe: f32(make_layer(m, jax.random.fold_in(k, l), moe)))
              for moe in (False, True)}
    layer = {(moe, q): jax.jit(_layer(m, moe, q)) for moe in (False, True) for q in (False, True)}
    blocks = [[emb[jnp.asarray(t)], emb[jnp.asarray(t)]] for t in tokens]
    for l in range(m.L):
        moe = l >= m.k_dense
        w = make_l[moe](kl, jnp.uint32(l))
        for b in blocks:
            b[0] = layer[(moe, False)](b[0], w)
            if control:
                b[1] = layer[(moe, True)](b[1], w)
    return gw, blocks


def logits(config: dict, seed: int, tokens: np.ndarray) -> np.ndarray:
    """Float32 logits (n, T, V) of the full forward over tokens (n, T)."""
    m = Dims(config)
    with jax.default_matmul_precision("highest"):
        gw, blocks = _hidden(m, seed, [tokens], control=False)
        return np.asarray(_rmsnorm(blocks[0][0], gw["final_norm"], m.eps) @ gw["lm_head"].astype(jnp.float32))


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
    n = len(sequences)
    T = max(max(len(s) for s in sequences), length)
    T = -(-T // 64) * 64
    toks = []
    for b0 in range(0, n, block):
        tk = np.zeros((block, T), np.int32)
        for r, i in enumerate(range(b0, min(b0 + block, n))):
            tk[r, : len(sequences[i])] = sequences[i]
        toks.append(tk)
    with jax.default_matmul_precision("highest"):
        gw, blocks = _hidden(m, seed, toks, control)
        head, fnorm = gw["lm_head"].astype(jnp.float32), gw["final_norm"]

        @jax.jit
        def score(hid, tok, hid8, head, fnorm):  # weights as arguments, not constants
            lg = _rmsnorm(hid, fnorm, m.eps) @ head
            best = lg.max(axis=-1)
            gap = best - jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
            pick = jnp.argmax(_int8_matmul(_rmsnorm(hid8, fnorm, m.eps), head), axis=-1)
            return gap, best - jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]

        hid, hid8, tok, owner = [], [], [], []
        for b, (h, h8) in enumerate(blocks):
            for r, i in enumerate(range(b * block, min(b * block + block, n))):
                t = np.arange(served_from[i], len(sequences[i]))
                hid.append(h[r, t - 1])
                hid8.append(h8[r, t - 1])
                tok.append(toks[b][r, t])
                owner.append(np.full(t.size, i))
        hid, hid8 = jnp.concatenate(hid), jnp.concatenate(hid8)
        tok, owner = np.concatenate(tok), np.concatenate(owner)
        N = tok.size
        pad = -N % rows
        hid = jnp.pad(hid, ((0, pad), (0, 0)))
        hid8 = jnp.pad(hid8, ((0, pad), (0, 0)))
        tk = jnp.asarray(np.pad(tok, (0, pad)))
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
