"""Pallas TPU kernels: SLED batched-verification attention (dense + paged).

The server's hot loop attends Sq = K+1 fresh tokens per request against a
long KV cache.  TPU adaptation (vs the CUDA "append attention" kernels GPU
serving engines use):

  * the MXU wants >= 8 x 128 tiles, but Sq is tiny (5).  We PACK the GQA
    group dimension into the query rows: rows = Sq * G (granite MQA: 5 x 48
    = 240 rows — full MXU occupancy from what would be a 5-row matmul);
  * the KV cache streams HBM->VMEM once in ``block_k`` chunks along the
    sequence — verification at small K is HBM-bound, so one pass over the
    cache IS the roofline;
  * online-softmax state (m, l, acc) lives in fp32 VMEM scratch across the
    kv-chunk grid axis (TPU grids iterate the last axis sequentially);
  * the causal offset mask (query i sits at absolute position
    kv_valid - Sq + i) is computed from iota over packed rows — no mask
    tensor is ever materialised;
  * ``Skv`` need not divide ``block_k``: the final partial chunk is handled
    by masking the out-of-range lanes (scores forced to NEG_INF, the
    corresponding V rows zeroed so unspecified out-of-bounds data can never
    poison the accumulator).

Two entry points share that math:

``verify_attention_packed`` — dense layout: each batch row owns its own
contiguous (Skv, Hkv, D) K/V buffer.  The lock-step server path.

``verify_attention_paged`` — pool layout for continuous batching: K/V live
in one shared pool of cache rows shaped ``(n_slots + 1, Skv, Hkv, D)`` (the
+1 row is the scratch slot that pads partial batches), and a ``(B,)``
``slots`` vector names which pool row each batch entry attends against.
``slots`` is a *scalar-prefetch* operand (``pltpu.PrefetchScalarGridSpec``):
it lands in SMEM before the kernel body runs, so the BlockSpec index maps
can compute each K/V tile's HBM address as ``(slots[b], j, 0, 0)`` — the
grid walks ``(B, n_blk)``, each tile carries all Hkv heads, and every chunk
DMA reads straight out of the pool row the slot map points at.  Nothing is ever gathered into a dense
sub-batch and nothing but the O(K+1) fresh rows is ever written back, which
deletes the gather/scatter paging tax the engine's verify step used to pay.
Duplicate slot ids are legal (padding rows all point at the scratch slot);
their outputs are garbage by construction and discarded by the caller.

The gather path still exists for model families whose caches hold
non-attention leaves (Mamba2 SSM state / conv windows, hybrid checkpoints):
those leaves are recurrent state, not position-indexed K/V, so they cannot
be slot-indexed by this kernel and keep riding ``kvcache.gather_slots`` —
they are tiny next to the attention pool.

Layouts: q is pre-packed to (B, Hkv, Sq*G, D) by ops.py (tiny transpose);
k/v stay (B, Skv, Hkv, D) / (n_slots+1, Skv, Hkv, D) — a K/V tile takes
every head of its block_k positions, so the multi-GB cache is never
transposed.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attend_chunk(kv_valid, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, block_k: int, sq: int, skv: int, scale: float,
                  k_scales=None, v_scales=None):
    """One online-softmax step over the current kv chunk (grid axis 1), for
    every kv head of the tile.

    Shared by the dense and paged kernels — only how the chunk was addressed
    differs (BlockSpec index maps), never the math.  Requires
    ``kv_valid >= sq`` (the Sq fresh rows are in the cache), which makes the
    first chunk contain at least one valid position for every packed row.

    The K/V tile holds all Hkv heads, ``(1, block_k, Hkv, D)``: Mosaic needs
    a block's second-to-last dim to be a multiple of 8 or the whole array
    dim, so a one-head tile ``(1, block_k, 1, D)`` does not compile.  The
    head loop is unrolled here instead.

    ``k_scales``/``v_scales`` (per-head f32 scalars for this slot) switch on
    the int8 path: the K/V tiles arrive quantized and are dequantized HERE,
    on the VMEM-resident chunk — the HBM stream is int8, so the cache read
    halves, and no bf16 pool copy ever exists.  The dequant arithmetic
    mirrors layers.kv_dequant (int8 -> f32 * scale -> bf16) so the kernel
    tracks the XLA serving path's numerics.
    """
    j_blk = pl.program_id(1)
    n_blk = pl.num_programs(1)

    @pl.when(j_blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    hkv, rows = q_ref.shape[1], q_ref.shape[2]
    # packed row r -> query index i = r // G; abs position = kv_valid - Sq + i
    g = rows // sq
    i_vec = jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0) // g
    j_vec = jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 1) + j_blk * block_k
    mask = (j_vec <= (kv_valid - sq + i_vec)) & (j_vec < skv)
    # Partial tail chunk: lanes past Skv read unspecified data (NaN in
    # interpret mode).  Their weights are exactly 0, but 0 * NaN = NaN would
    # still poison acc — zero the out-of-range V rows explicitly.
    col = jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0) + j_blk * block_k
    v_live = col < skv

    for h in range(hkv):
        q = q_ref[0, h]  # (rows, D) rows = Sq*G
        k = k_ref[0, :, h, :]  # (block_k, D)
        v = v_ref[0, :, h, :]
        if k_scales is not None:
            k = (k.astype(jnp.float32) * k_scales[h]).astype(jnp.bfloat16)
            v = (v.astype(jnp.float32) * v_scales[h]).astype(jnp.bfloat16)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (rows, block_k)
        s = jnp.where(mask, s, NEG_INF)
        v = jnp.where(v_live, v, jnp.zeros((), v.dtype))

        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[h] = m_new

    @pl.when(j_blk == n_blk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _kernel(kv_valid_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, block_k: int, sq: int, skv: int, scale: float):
    b = pl.program_id(0)
    _attend_chunk(kv_valid_ref[b], q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  acc_ref, block_k=block_k, sq=sq, skv=skv, scale=scale)


def _paged_kernel(slots_ref, kv_valid_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref,
                  *, block_k: int, sq: int, skv: int, scale: float):
    # slots_ref is consumed by the BlockSpec index maps (scalar prefetch);
    # the body only needs the per-request valid length.
    del slots_ref
    b = pl.program_id(0)
    _attend_chunk(kv_valid_ref[b], q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  acc_ref, block_k=block_k, sq=sq, skv=skv, scale=scale)


def _paged_quant_kernel(slots_ref, kv_valid_ref, k_scale_ref, v_scale_ref,
                        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                        *, block_k: int, sq: int, skv: int, scale: float):
    # int8 pool: the per-(slot, head) dequant scales ride scalar prefetch
    # next to slots/kv_valid — SMEM-resident before the body runs, looked up
    # here with the same slot map the index maps use for the K/V tiles.
    b = pl.program_id(0)
    row = slots_ref[b]
    hkv = q_ref.shape[1]
    _attend_chunk(kv_valid_ref[b], q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  acc_ref, block_k=block_k, sq=sq, skv=skv, scale=scale,
                  k_scales=[k_scale_ref[row, h] for h in range(hkv)],
                  v_scales=[v_scale_ref[row, h] for h in range(hkv)])


def _scratch(hkv: int, rows: int, d: int):
    """Online-softmax state for every kv head of one batch row.

    VMEM per grid step at block_k=512, D=128: each K or V tile is
    block_k x (Hkv rounded up to the sublane tile: 8 f32 / 16 bf16 /
    32 int8 rows) x D, which is 2 MiB for Hkv <= that tile in every dtype;
    K+V double-buffered is 8 MiB.  q and o blocks plus this scratch add
    about 3 x Hkv x rows x D x 4 bytes (under 0.2 MiB at Hkv=2, rows=30).
    """
    return [
        pltpu.VMEM((hkv, rows, 1), jnp.float32),   # m
        pltpu.VMEM((hkv, rows, 1), jnp.float32),   # l
        pltpu.VMEM((hkv, rows, d), jnp.float32),   # acc
    ]


def verify_attention_packed(
    q: jax.Array,        # (B, Hkv, rows=Sq*G, D)
    k: jax.Array,        # (B, Skv, Hkv, D)
    v: jax.Array,
    kv_valid: jax.Array,  # (B,) int32
    *,
    sq: int,
    scale: Optional[float] = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Hkv, rows, D = q.shape
    Skv = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    block_k = min(block_k, Skv)
    n_blk = -(-Skv // block_k)  # partial tail chunk is masked in-kernel

    kernel = functools.partial(_kernel, block_k=block_k, sq=sq, skv=Skv,
                               scale=float(scale))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # kv_valid
        grid=(B, n_blk),
        in_specs=[
            pl.BlockSpec((1, Hkv, rows, D), lambda b, j, kvv: (b, 0, 0, 0)),    # q
            pl.BlockSpec((1, block_k, Hkv, D), lambda b, j, kvv: (b, j, 0, 0)),  # k
            pl.BlockSpec((1, block_k, Hkv, D), lambda b, j, kvv: (b, j, 0, 0)),  # v
        ],
        out_specs=pl.BlockSpec((1, Hkv, rows, D), lambda b, j, kvv: (b, 0, 0, 0)),
        scratch_shapes=_scratch(Hkv, rows, D),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, D), q.dtype),
        interpret=interpret,
    )(kv_valid.astype(jnp.int32), q, k, v)


def verify_attention_paged(
    q: jax.Array,        # (B, Hkv, rows=Sq*G, D)
    k_pool: jax.Array,   # (n_slots+1, Skv, Hkv, D) — the PagedKVCache pool
    v_pool: jax.Array,
    slots: jax.Array,     # (B,) int32 pool row per batch entry (dups legal)
    kv_valid: jax.Array,  # (B,) int32 valid entries incl. the Sq fresh rows
    *,
    sq: int,
    scale: Optional[float] = None,
    block_k: int = 512,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # (n_slots+1, Hkv) f32 dequant
    v_scale: Optional[jax.Array] = None,  # scales for an int8 pool
) -> jax.Array:
    """Slot-indexed verification attention over a shared cache-row pool.

    ``slots`` and ``kv_valid`` ride scalar prefetch: the index maps address
    each (block_k, Hkv, D) K/V tile as ``(slots[b], j, 0, 0)`` directly in
    the pool, so the chunk DMAs stream exactly the scheduled rows — no dense
    gather ever exists (see module docstring).

    With an int8 pool, pass the PagedKVCache's per-(slot, head) dequant
    scales as ``k_scale``/``v_scale``: they join the scalar-prefetch
    operands and each chunk is dequantized IN-KERNEL on its VMEM tile
    (``_attend_chunk``), so HBM streams the cache at 1 byte/element —
    that halved stream is the whole point of the quantized pool.
    """
    B, Hkv, rows, D = q.shape
    Skv = k_pool.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    block_k = min(block_k, Skv)
    n_blk = -(-Skv // block_k)

    quant = k_pool.dtype == jnp.int8
    if quant and (k_scale is None or v_scale is None):
        raise ValueError("int8 k_pool/v_pool require k_scale/v_scale operands")

    # index maps see the grid indices, then every scalar-prefetch operand
    def q_map(b, j, *prefetch):
        return (b, 0, 0, 0)

    def kv_map(b, j, slots, *prefetch):
        return (slots[b], j, 0, 0)

    body, n_prefetch = (_paged_quant_kernel, 4) if quant else (_paged_kernel, 2)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,  # slots, kv_valid (+ k_scale, v_scale)
        grid=(B, n_blk),
        in_specs=[
            pl.BlockSpec((1, Hkv, rows, D), q_map),
            pl.BlockSpec((1, block_k, Hkv, D), kv_map),
            pl.BlockSpec((1, block_k, Hkv, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, Hkv, rows, D), q_map),
        scratch_shapes=_scratch(Hkv, rows, D),
    )
    prefetch = [slots.astype(jnp.int32), kv_valid.astype(jnp.int32)]
    if quant:
        prefetch += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    return pl.pallas_call(
        functools.partial(body, block_k=block_k, sq=sq, skv=Skv, scale=float(scale)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, D), q.dtype),
        interpret=interpret,
    )(*prefetch, q, k_pool, v_pool)
