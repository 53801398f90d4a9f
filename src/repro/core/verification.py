"""Server-side batched verification (SLED §III-B) — the serve_step we deploy.

Invariant (shared with core/drafting.py):
  * ``cache.length`` counts K/V-committed tokens = (#committed tokens) - 1.
  * ``verify_step`` feeds ``tokens_in = [prev_committed, d_1 .. d_K]``
    (K+1 tokens), so ``logits[i]`` judges ``d_{i+1}`` and ``logits[m]`` is
    the correction/bonus distribution (core/speculative.py).
  * commit: attention caches set ``length += n_commit``; SSM/hybrid caches
    select the per-position state checkpoint (models emit them).

This module builds the jittable step functions that the serving engine runs:
the target model's compute is one chunked-attention forward over (B, K+1)
tokens against (B, S) caches — SLED's entire server-side hot loop.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.core.speculative import VerifyResult, speculative_verify
from repro.models.kvcache import gather_slots, scatter_slots, supports_paged_attention
from repro.models.layers import MeshContext, NO_MESH


def make_verify_batch(prev_token, draft_tokens, lengths, draft_q=None, seed=0):
    """Assemble the padded verification request batch (host or device side)."""
    B, K = draft_tokens.shape
    batch = {
        "tokens_in": jnp.concatenate([prev_token[:, None], draft_tokens], axis=1),
        "draft_tokens": draft_tokens.astype(jnp.int32),
        "lengths": lengths.astype(jnp.int32),
        "seed": jnp.asarray(seed, jnp.uint32),
    }
    if draft_q is not None:
        batch["draft_q"] = draft_q
    return batch


def verify_batch_spec(batch_size: int, k_max: int, *, sampling: bool = False):
    """ShapeDtypeStruct stand-ins for the verification request (lowering
    without data)."""
    spec = {
        "tokens_in": jax.ShapeDtypeStruct((batch_size, k_max + 1), jnp.int32),
        "draft_tokens": jax.ShapeDtypeStruct((batch_size, k_max), jnp.int32),
        "lengths": jax.ShapeDtypeStruct((batch_size,), jnp.int32),
        "seed": jax.ShapeDtypeStruct((), jnp.uint32),
    }
    if sampling:
        spec["draft_q"] = jax.ShapeDtypeStruct((batch_size, k_max), jnp.float32)
    return spec


def make_verify_step(
    model,
    *,
    ctx: MeshContext = NO_MESH,
    greedy: bool = True,
    temperature: float = 1.0,
    attn_chunk: int = 1024,
):
    """Returns verify_step(params, cache, batch) -> (VerifyResult, cache')."""

    def verify_step(params, cache, batch) -> Tuple[VerifyResult, Any]:
        h, ck_cache, _ = model.decode_forward(
            params, cache, batch["tokens_in"], ctx, attn_chunk=attn_chunk
        )
        logits = model.lm_head(params, h)  # (B, K+1, V) fp32
        key = jax.random.key(batch["seed"])
        res = speculative_verify(
            batch["draft_tokens"],
            logits,
            key,
            lengths=batch["lengths"],
            draft_q=batch.get("draft_q"),
            draft_q_full=batch.get("draft_q_full"),
            temperature=temperature,
            greedy=greedy,
        )
        new_cache = model.commit(ck_cache, res.n_commit)
        return res, new_cache

    return verify_step


def make_paged_verify_step(
    model,
    *,
    scratch_slot: int,
    ctx: MeshContext = NO_MESH,
    greedy: bool = True,
    temperature: float = 1.0,
    attn_chunk: int = 1024,
    expert_rows: bool = False,
):
    """Slot-indexed verify step for continuous batching over a row pool.

    Returns ``verify_step(params, pool, slots, batch) -> (VerifyResult, pool')``
    where ``pool`` is a PagedKVCache.cache pytree, ``slots`` is (B_bucket,)
    int32 pool-row indices, and ``batch`` is a padded verify request of the
    same bucket size.  The jitted shapes depend only on (bucket, k_max),
    never on which devices happen to be scheduled, so heterogeneous partial
    fills reuse one executable per bucket.

    The model's cache kind picks one of two paths
    (``kvcache.supports_paged_attention``, the module note there); the step's
    ``slot_indexed`` attribute says which:

      * slot-indexed, for attention and latent-attention caches: the forward
        runs directly against the pool — ``decode_forward(slots=)`` writes
        the K+1 fresh K/V rows into pool rows and attention streams
        slot-indexed chunks, so no cache leaf is gathered or scattered;
        commit is an O(B) ``length`` update at the slot rows (rollback stays
        O(1)).
      * gather/scatter, for SSM and hybrid caches (their recurrent state
        leaves cannot be slot-indexed): rows are gathered into a dense
        sub-cache, verified by the model's ordinary decode_forward/commit
        path, and scattered back.

    Padding convention (both paths): unused entries point at
    ``scratch_slot`` with ``lengths = 0``; the step resets the scratch row's
    committed length so repeated padding can never walk scratch state off
    the end of the buffer.  MoE models route only the real tokens (a real
    row's first ``lengths + 1`` positions) through their experts; with
    ``expert_rows`` the result carries the rows each held expert computed
    (``VerifyResult.expert_rows``).
    """
    slot_indexed = supports_paged_attention(model.cfg)
    moe = model.cfg.family == "moe"

    def _routed(slots, batch):
        if not moe:
            return {}
        pos = jnp.arange(batch["tokens_in"].shape[1], dtype=jnp.int32)
        return {"valid": (slots != scratch_slot)[:, None] & (pos[None] <= batch["lengths"][:, None])}

    def _with_rows(res, stats):
        return dataclasses.replace(res, expert_rows=stats.rows) if expert_rows else res

    def _verify_logits(params, h, batch) -> VerifyResult:
        logits = model.lm_head(params, h)  # (B_bucket, K+1, V) fp32
        key = jax.random.key(batch["seed"])
        return speculative_verify(
            batch["draft_tokens"],
            logits,
            key,
            lengths=batch["lengths"],
            draft_q=batch.get("draft_q"),
            draft_q_full=batch.get("draft_q_full"),
            temperature=temperature,
            greedy=greedy,
        )

    def paged_verify_step(params, pool, slots, batch) -> Tuple[VerifyResult, Any]:
        base_len = jnp.take(pool["length"], slots, axis=0)
        h, new_pool, stats = model.decode_forward(
            params, pool, batch["tokens_in"], ctx, attn_chunk=attn_chunk, slots=slots,
            **_routed(slots, batch),
        )
        res = _with_rows(_verify_logits(params, h, batch), stats)
        # commit = per-slot length bump; duplicate scratch entries race, but
        # the scratch row is reset right after (and never read as committed)
        length = new_pool["length"].at[slots].set(
            (base_len + res.n_commit).astype(jnp.int32)
        )
        length = length.at[scratch_slot].set(0)
        return res, {**new_pool, "length": length}

    def gather_verify_step(params, pool, slots, batch) -> Tuple[VerifyResult, Any]:
        sub = gather_slots(pool, slots)
        h, ck_sub, stats = model.decode_forward(
            params, sub, batch["tokens_in"], ctx, attn_chunk=attn_chunk, **_routed(slots, batch)
        )
        res = _with_rows(_verify_logits(params, h, batch), stats)
        new_sub = model.commit(ck_sub, res.n_commit)
        new_pool = scatter_slots(pool, slots, new_sub)
        new_pool["length"] = new_pool["length"].at[scratch_slot].set(0)
        return res, new_pool

    verify_step = paged_verify_step if slot_indexed else gather_verify_step
    verify_step.slot_indexed = slot_indexed
    return verify_step


def make_force_extend_step(model, *, ctx: MeshContext = NO_MESH, attn_chunk: int = 1024):
    """Slot-indexed forced cache extension (no verification, no sampling).

    Returns ``extend_step(params, pool, slots, tokens_in, n) -> pool'`` that
    appends ``n[i]`` tokens of ``tokens_in[i]`` (padded to a fixed width) to
    pool row ``slots[i]``.  Used by the transport server to resync a stream
    after a §III-A timeout fallback: the device already released its local
    drafts to the user, so the server force-commits those exact tokens into
    the stream's row and verification resumes from the new tail — lossy by
    construction (that is the paper's fallback trade), but state-consistent.

    The same path as ``make_paged_verify_step`` for the model's cache kind:
    pool-resident slot-indexed forward on attention families, gather/scatter
    otherwise.
    """
    slot_indexed = supports_paged_attention(model.cfg)

    def paged_extend_step(params, pool, slots, tokens_in, n):
        base_len = jnp.take(pool["length"], slots, axis=0)
        _, new_pool, _ = model.decode_forward(
            params, pool, tokens_in, ctx, attn_chunk=attn_chunk, slots=slots
        )
        length = new_pool["length"].at[slots].set(
            (base_len + n).astype(jnp.int32)
        )
        return {**new_pool, "length": length}

    def gather_extend_step(params, pool, slots, tokens_in, n):
        sub = gather_slots(pool, slots)
        _, ck_sub, _ = model.decode_forward(
            params, sub, tokens_in, ctx, attn_chunk=attn_chunk
        )
        new_sub = model.commit(ck_sub, n.astype(jnp.int32))
        return scatter_slots(pool, slots, new_sub)

    extend_step = paged_extend_step if slot_indexed else gather_extend_step
    extend_step.slot_indexed = slot_indexed
    return extend_step


def make_prefill_step(model, *, ctx: MeshContext = NO_MESH, attn_chunk: int = 1024,
                      with_frontend: bool = False):
    """Returns prefill_step(params, cache, tokens, [stub_embeds]) for serving.

    Leaves the cache at ``length = prompt_len - 1`` and returns the last
    prompt token separately — satisfying the "all committed but the last"
    invariant so the first verify round can feed it.
    """

    def prefill_step(params, cache, tokens, stub=None):
        kw = {}
        if with_frontend and model.cfg.family == "encdec":
            kw["enc_frames"] = stub
        if with_frontend and model.cfg.family == "vlm":
            kw["embeds_prefix"] = stub
        logits, cache = model.prefill(params, tokens[:, :-1], cache, ctx,
                                      attn_chunk=attn_chunk, **kw)
        return logits, cache, tokens[:, -1]

    return prefill_step
