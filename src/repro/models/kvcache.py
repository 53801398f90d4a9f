"""KV-cache pytree with speculative-rollback semantics.

The cache buffer index IS the absolute token position, and a per-row
``length`` marks how many entries are committed.  Speculative rollback after
verification never moves data: the server just sets
``length = base + n_accepted (+1 for the corrected/bonus token)`` — entries
past ``length`` are masked out of attention and overwritten by the next
verify round.  SSM states can't be masked retroactively, so SSM layers store
per-position state checkpoints during verification instead (see mamba2.py).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp


def init_kv_cache(
    num_layers: int,
    batch: int,
    max_len: int,
    num_kv_heads: int,
    head_dim: int,
    dtype=jnp.bfloat16,
) -> Dict[str, jax.Array]:
    """K/V leaves head-major, (L, B, Hkv, S, D): one head's positions of a
    row are contiguous, the order the verify step's layer loop reads and
    writes, so the step updates the pool where it lies (models/layers.py)."""
    shape = (num_layers, batch, num_kv_heads, max_len, head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def kv_cache_spec(num_layers, batch, max_len, num_kv_heads, head_dim, dtype=jnp.bfloat16):
    """ShapeDtypeStruct stand-in (no allocation)."""
    shape = (num_layers, batch, num_kv_heads, max_len, head_dim)
    return {
        "k": jax.ShapeDtypeStruct(shape, dtype),
        "v": jax.ShapeDtypeStruct(shape, dtype),
        "length": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }


def rollback(cache: Dict[str, jax.Array], new_length: jax.Array) -> Dict[str, jax.Array]:
    """O(1) rollback: commit only ``new_length`` entries per row."""
    return {**cache, "length": new_length.astype(jnp.int32)}


# ---------------------------------------------------------------------------
# Paged (slot-pool) cache: continuous batching over a fixed row pool
# ---------------------------------------------------------------------------
#
# Every cache family in this repo shares one layout convention: ``length`` is
# (B,) and every other leaf carries the batch on axis 1 (k/v: (L, B, H, S, D),
# ssm: (L, B, H, P, N), conv: (L, B, cw-1, C), cross_k/v: (L, B, H, F, D),
# int8 dequant scales k_scale/v_scale: (L, B, Hkv)).  Because the convention
# covers the scale leaves too, a quantized pool needs no special casing here:
# gather_slots / scatter_slots / write_slot / ExportStream move the int8 rows
# AND their scales together, bit-exactly.
# That makes "a device's cache" a fixed set of rows, so continuous batching
# reduces to a slot allocator over a pool of rows.  Two verify paths share
# the pool, and the model's cache kind picks one (supports_paged_attention):
#
#   * slot-indexed (attention and latent-attention caches): the forward runs
#     DIRECTLY against the pool — per-row lengths come from length[slots],
#     fresh K/V rows scatter into pool rows, and attention streams
#     slot-indexed chunks (transformer.decode_forward(slots=...), mirrored
#     on TPU by kernels/verify_attn.verify_attention_paged's
#     scalar-prefetched index maps).  Pool traffic per round is one read of
#     the scheduled rows plus an O(B * (K+1)) fresh-row write.
#   * gather/scatter (SSM and hybrid caches): the scheduled subset is
#     materialised into a dense sub-batch, verified, and scattered back.
#     Their recurrent state leaves (ssm, conv, checkpoints) are not
#     position-indexed K/V — those leaves are tiny next to the attention
#     pool, so the gather tax is bounded.
#
# Either way compiled shapes depend only on the bucket size — devices can
# join, leave, or idle without recompiles.


def supports_paged_attention(cfg) -> bool:
    """True when every cache leaf the verify forward touches is attention-
    shaped (k/v/cross buffers, or latent attention's ckv/kpe, + length), so
    the slot-indexed path runs against the pool.  SSM and hybrid caches carry
    recurrent state leaves and take the gather/scatter path.  The verify and
    extend steps choose their path here and nowhere else."""
    return getattr(cfg, "family", None) not in ("ssm", "hybrid")


def _batch_axis(leaf: jax.Array) -> int:
    return 0 if leaf.ndim == 1 else 1  # "length" vs stacked per-layer leaves


def gather_slots(cache: Dict[str, jax.Array], slots: jax.Array) -> Dict[str, jax.Array]:
    """Dense sub-cache holding pool rows ``slots`` (jit-traceable)."""
    return jax.tree.map(lambda a: jnp.take(a, slots, axis=_batch_axis(a)), cache)


@functools.partial(jax.jit, donate_argnums=(0,))
def write_row(
    pool: Dict[str, jax.Array], slot: jax.Array, row: Dict[str, jax.Array]
) -> Dict[str, jax.Array]:
    """``pool`` with the batch-1 cache ``row`` in pool row ``slot``: one
    dynamic_update_slice per leaf.  The pool is donated, so the program
    writes the one row where the pool lies and copies nothing else."""

    def put(p, r):
        start = [0] * p.ndim
        start[_batch_axis(p)] = slot
        return jax.lax.dynamic_update_slice(p, r, start)

    return jax.tree.map(put, pool, row)


def scatter_slots(
    pool: Dict[str, jax.Array], slots: jax.Array, sub: Dict[str, jax.Array]
) -> Dict[str, jax.Array]:
    """Write dense sub-cache rows back into pool rows ``slots``.

    Duplicate slot ids are allowed (the verify step pads partial batches with
    the scratch slot); which duplicate wins is undefined, which is fine
    because scratch contents are never read as committed state.
    """

    def put(p, s):
        if p.ndim == 1:
            return p.at[slots].set(s)
        return p.at[:, slots].set(s)

    return jax.tree.map(put, pool, sub)


class SlotExhausted(RuntimeError):
    """No free cache row: admission must wait for a stream to retire."""


class SlotAllocator:
    """Host-side free-list over ``n_slots`` cache rows (LIFO reuse)."""

    def __init__(self, n_slots: int):
        self.n_slots = n_slots
        self._free: List[int] = list(range(n_slots - 1, -1, -1))
        self._used: set = set()

    def alloc(self) -> int:
        if not self._free:
            raise SlotExhausted(f"all {self.n_slots} cache slots in use")
        slot = self._free.pop()
        self._used.add(slot)
        return slot

    def free(self, slot: int) -> None:
        if slot not in self._used:
            raise ValueError(f"slot {slot} is not allocated")
        self._used.remove(slot)
        self._free.append(slot)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return len(self._used)


class PagedKVCache:
    """Fixed pool of cache rows + slot map: the server-side state behind
    continuous-batching verification.

    The pool holds ``n_slots`` device rows plus ONE scratch row (index
    ``n_slots``) that jitted steps use to pad partial batches up to a bucket
    size — padding rows gather scratch, compute garbage, and scatter it back
    to scratch, so real rows are untouched by fill.

    Works for every model family because it only relies on the shared cache
    layout convention (see module comment); rollback semantics stay the
    model's own (``model.commit`` runs on the gathered dense sub-cache).
    """

    def __init__(self, model: Any, n_slots: int, max_len: int,
                 device: Optional[jax.Device] = None, **cache_kw):
        self.model = model
        self.n_slots = n_slots
        self.scratch_slot = n_slots
        self.max_len = max_len
        self.cache_kw = dict(cache_kw)
        # allocate on ``device`` directly, then commit the pool to it
        with jax.default_device(device) if device is not None else contextlib.nullcontext():
            self.cache = model.make_cache(n_slots + 1, max_len, **cache_kw)
        if device is not None:
            self.cache = jax.device_put(self.cache, device)
        self.allocator = SlotAllocator(n_slots)

    def pool_bytes(self) -> int:
        """Device bytes held by the pool (all leaves, incl. scratch row and
        any int8 dequant-scale leaves) — the capacity-planning number behind
        the ``engine_kv_pool_bytes`` gauge."""
        return sum(
            int(a.size) * a.dtype.itemsize for a in jax.tree.leaves(self.cache)
        )

    def bytes_per_slot(self) -> int:
        """Pool bytes amortised per device slot: with ``kv_dtype=int8`` this
        is ~half the bf16 figure, i.e. ~2x admitted streams per HBM byte."""
        return self.pool_bytes() // (self.n_slots + 1)

    def alloc(self) -> int:
        return self.allocator.alloc()

    def free(self, slot: int) -> None:
        self.allocator.free(slot)

    @property
    def n_free(self) -> int:
        return self.allocator.n_free

    def make_row_cache(self) -> Dict[str, jax.Array]:
        """Fresh dense batch-1 cache shaped to scatter into one pool row
        (prefill target: same max_len, so trailing dims line up)."""
        return self.model.make_cache(1, self.max_len, **self.cache_kw)

    def write_slot(self, slot: int, row_cache: Dict[str, jax.Array]) -> None:
        """Install a prefilled batch-1 cache into pool row ``slot`` (in
        place: the old pool buffers are donated to the write)."""
        self.cache = write_row(self.cache, jnp.int32(slot), row_cache)

    def lengths(self) -> jax.Array:
        return self.cache["length"][: self.n_slots]
