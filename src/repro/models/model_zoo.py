"""Dispatch: one uniform functional interface over every model family.

``build_model(cfg)`` returns a ``Model`` with:
  init_params(key, max_pos=0)          -> params pytree
  forward(params, tokens, ctx, **kw)   -> (hidden, aux)       [training]
  lm_head(params, hidden)              -> logits fp32
  make_cache(batch, max_len, ...)      -> cache pytree (or specs)
  prefill(params, tokens, cache, ctx)  -> (last logits, cache)
  decode_forward(params, cache, toks)  -> (hidden, ckpt_cache, layers.MoEStats)  [verify]
  commit(cache, n_commit)              -> cache  [speculative rollback]

The stub frontends ([audio]/[vlm]) enter via forward/prefill kwargs
(``enc_frames`` / ``embeds_prefix``) — precomputed embeddings per the
assignment ("the modality frontend is a STUB").
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import hybrid as HY
from repro.models import mamba2 as M2
from repro.models import transformer as T
from repro.models.layers import NO_MESH


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: Any
    init_params: Callable
    forward: Callable
    lm_head: Callable
    make_cache: Callable
    prefill: Callable
    decode_forward: Callable
    commit: Callable

    def init_params_spec(self, **kw):
        return jax.eval_shape(lambda k: self.init_params(k, **kw), jax.random.key(0))


def _attn_commit(cache, n_commit):
    return {
        k: v for k, v in cache.items() if not k.endswith("_ckpt")
    } | {"length": cache["length"] + n_commit.astype(jnp.int32)}


def _ssm_commit(select):
    def commit(cache, n_commit):
        return select(cache, n_commit)

    return commit


def build_model(cfg) -> Model:
    if cfg.family == "ssm":
        mod, commit = M2, M2.select_checkpoint
    elif cfg.family == "hybrid":
        mod, commit = HY, HY.select_checkpoint
    else:  # dense | moe | vlm | encdec
        mod, commit = T, _attn_commit

    return Model(
        cfg=cfg,
        init_params=lambda key, **kw: mod.init_params(cfg, key, **kw),
        forward=lambda params, tokens, ctx=NO_MESH, **kw: mod.forward(cfg, params, tokens, ctx, **kw),
        lm_head=lambda params, h: mod.lm_head(cfg, params, h),
        make_cache=lambda batch, max_len, **kw: mod.make_cache(cfg, batch, max_len, **kw),
        prefill=lambda params, tokens, cache, ctx=NO_MESH, **kw: mod.prefill(
            cfg, params, tokens, cache, ctx, **kw
        ),
        decode_forward=lambda params, cache, tokens, ctx=NO_MESH, **kw: mod.decode_forward(
            cfg, params, cache, tokens, ctx, **kw
        ),
        commit=commit,
    )


def frontend_stub(cfg, batch: int, key=None, *, spec_only: bool = False):
    """Precomputed modality embeddings for [audio]/[vlm] archs (stub frontend).

    whisper: (B, encoder_seq, d) frame embeddings.
    llava:   (B, num_patches, d) patch embeddings.
    """
    if cfg.family == "encdec":
        shape = (batch, cfg.encoder_seq, cfg.d_model)
    elif cfg.family == "vlm":
        shape = (batch, cfg.num_patches, cfg.d_model)
    else:
        return None
    if spec_only:
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    key = key if key is not None else jax.random.key(7)
    return (jax.random.normal(key, shape) * 0.02).astype(jnp.bfloat16)


def perturb_params(params, scale: float, seed: int = 7):
    """Gaussian-perturb matrix leaves (norms/scalars untouched).

    Two random-init reduced models tend to agree on greedy argmax, which
    makes speculative acceptance trivially 1.0; perturbing the draft params
    dials in realistic partial-acceptance rates for tests and benchmarks
    (scale ~0.02 gives ~0.9 acceptance on the reduced qwen2 pair).
    """
    if scale <= 0:
        return params
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), max(len(leaves), 1))
    noisy = [
        leaf + scale * jax.random.normal(key, leaf.shape, leaf.dtype)
        if hasattr(leaf, "ndim") and leaf.ndim >= 2
        else leaf
        for leaf, key in zip(leaves, keys)
    ]
    return jax.tree.unflatten(treedef, noisy)
