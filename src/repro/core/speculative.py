"""Batched lossless speculative verification (Leviathan et al. 2023), SLED-style.

Alignment invariant (see core/verification.py):
  the server feeds ``tokens_in = [prev_committed_token, d_1 .. d_K]`` and the
  target model returns ``logits[i] = p(. | context, tokens_in[:i+1])`` — so
  ``logits[i]`` is the distribution that judges draft ``d_{i+1}``, and
  ``logits[m]`` provides the correction/bonus distribution after ``m``
  accepted drafts.

Variable-length drafts (SLED's dynamic drafting sends whatever the
confidence threshold allowed) are handled with per-row ``lengths`` masks —
the batch is padded to K_max by the server's batch planner, exactly the
paper's "applies appropriate padding to equalize token lengths".

Modes:
  greedy=True   — acceptance is argmax-equality; exactly lossless and needs
                  only token ids on the wire (the SLED edge deployment mode).
  greedy=False  — Leviathan rejection sampling. Exact residual sampling needs
                  the draft distribution at the rejected position
                  (``draft_q_full``); without it we fall back to sampling the
                  correction from the target distribution (documented
                  deviation from exact rejection sampling).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

PAD_TOKEN = -1


@dataclasses.dataclass
class VerifyResult:
    n_accepted: jax.Array  # (B,) accepted draft count m in [0, K]
    n_commit: jax.Array    # (B,) committed new tokens = m + 1
    out_tokens: jax.Array  # (B, K+1): d_1..d_m, extra, PAD...
    extra_token: jax.Array  # (B,) correction (rejected) or bonus (all accepted)
    accepted_mask: jax.Array  # (B, K)
    rejected: jax.Array    # (B,) True if a draft was rejected (m < length)
    # (count,) rows each held expert computed this call, summed over layers;
    # only from a verify step built with expert_rows=True (telemetry on)
    expert_rows: Optional[jax.Array] = None


jax.tree_util.register_dataclass(
    VerifyResult,
    data_fields=["n_accepted", "n_commit", "out_tokens", "extra_token",
                 "accepted_mask", "rejected", "expert_rows"],
    meta_fields=[],
)


def speculative_verify(
    draft_tokens: jax.Array,   # (B, K) int32 (padded with anything past length)
    target_logits: jax.Array,  # (B, K+1, V) fp32
    key: jax.Array,
    *,
    lengths: Optional[jax.Array] = None,  # (B,) in [0, K]; None -> all K
    draft_q: Optional[jax.Array] = None,  # (B, K) q(d_i) from the draft model
    draft_q_full: Optional[jax.Array] = None,  # (B, K, V) full draft dists
    temperature: float = 1.0,
    greedy: bool = False,
) -> VerifyResult:
    B, K = draft_tokens.shape
    V = target_logits.shape[-1]
    if lengths is None:
        lengths = jnp.full((B,), K, jnp.int32)
    lengths = lengths.astype(jnp.int32)
    b_idx = jnp.arange(B)

    if greedy:
        tgt_choice = jnp.argmax(target_logits[:, :K], axis=-1)  # (B, K)
        accept = tgt_choice == draft_tokens
    else:
        assert draft_q is not None, "sampling mode needs draft token probabilities"
        logp = jax.nn.log_softmax(target_logits[:, :K] / temperature, axis=-1)
        p_sel = jnp.exp(jnp.take_along_axis(logp, draft_tokens[..., None], axis=-1))[..., 0]
        k_acc, key = jax.random.split(key)
        u = jax.random.uniform(k_acc, (B, K))
        accept = u < p_sel / jnp.maximum(draft_q, 1e-20)

    valid = jnp.arange(K)[None, :] < lengths[:, None]
    accept = accept & valid
    # first failure = acceptance count m (positions past length auto-fail)
    fail = ~accept
    m = jnp.where(fail.any(axis=1), jnp.argmax(fail, axis=1), K).astype(jnp.int32)
    rejected = m < lengths

    extra_logits = target_logits[b_idx, m]  # (B, V)
    if greedy:
        extra = jnp.argmax(extra_logits, axis=-1).astype(draft_tokens.dtype)
    else:
        p_m = jax.nn.softmax(extra_logits / temperature, axis=-1)
        if draft_q_full is not None:
            q_m = draft_q_full[b_idx, jnp.minimum(m, K - 1)]
            resid = jnp.maximum(p_m - q_m, 0.0)
            rs = resid.sum(-1, keepdims=True)
            resid = jnp.where(rs > 1e-9, resid / jnp.maximum(rs, 1e-9), p_m)
            dist = jnp.where(rejected[:, None], resid, p_m)
        else:
            dist = p_m  # target-fallback residual (approximate; see module doc)
        k_extra, key = jax.random.split(key)
        extra = jax.random.categorical(
            k_extra, jnp.log(jnp.maximum(dist, 1e-30))
        ).astype(draft_tokens.dtype)

    # committed tokens: accepted drafts, then the extra token, then PAD
    pos = jnp.arange(K + 1)[None, :]
    drafts_p1 = jnp.pad(draft_tokens, ((0, 0), (0, 1)))
    out = jnp.where(pos < m[:, None], drafts_p1, PAD_TOKEN)
    out = jnp.where(pos == m[:, None], extra[:, None], out)

    return VerifyResult(
        n_accepted=m,
        n_commit=m + 1,
        out_tokens=out,
        extra_token=extra,
        accepted_mask=accept,
        rejected=rejected,
    )


def sample_token(logits: jax.Array, key: jax.Array, temperature: float = 1.0,
                 greedy: bool = False):
    """Sample (token, prob-of-token, full-dist) from (B, V) logits."""
    probs = jax.nn.softmax(logits.astype(jnp.float32) / max(temperature, 1e-6), axis=-1)
    if greedy or temperature <= 0.0:
        tok = jnp.argmax(logits, axis=-1)
    else:
        tok = jax.random.categorical(key, logits.astype(jnp.float32) / temperature, axis=-1)
    p = jnp.take_along_axis(probs, tok[..., None], axis=-1)[..., 0]
    return tok.astype(jnp.int32), p, probs
