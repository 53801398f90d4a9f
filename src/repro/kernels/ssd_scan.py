"""Pallas TPU kernel: Mamba2 SSD chunked scan (state-space duality).

One program instance owns one (batch, head group) pair and walks the
sequence in ``chunk``-sized steps along the LAST grid axis (TPU grids
iterate it sequentially), carrying each head's (P, N) SSM state in fp32 VMEM
scratch.  Per chunk the body loops over the group's heads (unrolled, static
tile slices):

  * intra-chunk: the quadratic-in-chunk part is two MXU matmuls
    (C B^T ∘ decay) X — chunk x chunk scores never touch HBM;
  * inter-chunk: h <- exp(sum a) h + (decay-to-end ⊙ dt ⊙ B)^T X, again an
    MXU matmul, state stays resident in VMEM across the whole sequence;
  * per-chunk log-decay cumsums are fp32 masked reductions on the VPU
    (Mosaic has no cumsum).

The matmuls whose operands are not bf16 values (the state, the decay-weighted
scores and B) run at ``Precision.HIGHEST``: at the default single bf16 pass
the f32 state is rounded to bf16 on every chunk.

This is the TPU-native re-blocking of the Mamba2 paper's GPU kernel: the
GPU version tiles over (chunk, head, batch) thread-blocks with warp-level
softplus/cumsum; here the systolic array does the GEMMs and the VPU the
cumsum, with the sequential chunk axis mapped onto the grid instead of a
persistent CTA loop.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST

def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref, y_ref, hout_ref,
            h_ref, *, chunk: int):
    cidx = pl.program_id(2)
    n_chunks = pl.num_programs(2)
    n_heads, p_dim = x_ref.shape[2], x_ref.shape[3]

    @pl.when(cidx == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    Bm = b_ref[0].astype(jnp.float32)             # (Q, N), shared by heads
    Cm = c_ref[0].astype(jnp.float32)             # (Q, N)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    r = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri, eye = r >= c, r == c

    def diag_sum(v, axis):
        """Row <-> column through the diagonal: no transpose needed."""
        return jnp.where(eye, jnp.broadcast_to(v, (chunk, chunk)), 0.0).sum(
            axis=axis, keepdims=True)

    for h in range(n_heads):  # unrolled: each head is a static tile slice
        x = x_ref[0, :, h, :].astype(jnp.float32)  # (Q, P)
        dt = dt_ref[0, h:h + 1, :].astype(jnp.float32)  # (1, Q)
        alog = dt * a_ref[h:h + 1, :]              # (1, Q) per-step log decay
        # inclusive cumsum as a masked reduction (Mosaic has no cumsum):
        # cum_i = sum_{j <= i} alog_j as a column, then as a row
        cum = jnp.where(tri, jnp.broadcast_to(alog, (chunk, chunk)), 0.0).sum(
            axis=1, keepdims=True)                 # (Q, 1)
        cum_row = diag_sum(cum, 0)                 # (1, Q)
        cum_end = cum[chunk - 1:chunk, :]          # (1, 1)
        hs = h_ref[h]                              # (P, N)

        # carry-in: y_off_i = exp(cum_i) * C_i . h
        y_off = jax.lax.dot_general(
            Cm, hs, (((1,), (1,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32,
        ) * jnp.exp(cum)                           # (Q, P)

        # intra-chunk: W_ij = (C_i.B_j) exp(cum_i - cum_j) dt_j for j <= i
        decay = jnp.where(tri, jnp.exp(cum - cum_row), 0.0)
        W = cb * decay * dt
        y_diag = jax.lax.dot_general(W, x, (((1,), (0,)), ((), ())), precision=_HI,
                                     preferred_element_type=jnp.float32)
        y_ref[0, :, h * p_dim:(h + 1) * p_dim] = (y_off + y_diag).astype(y_ref.dtype)

        # state update: h <- exp(cum_Q) h + sum_j exp(cum_Q - cum_j) dt_j x_j B_j^T
        d_end = jnp.exp(cum_end - cum) * diag_sum(dt, 1)  # (Q, 1)
        h_new = jax.lax.dot_general(
            x, Bm * d_end, (((0,), (0,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32,
        )                                          # (P, N)
        h_ref[h] = jnp.exp(cum_end) * hs + h_new

    @pl.when(cidx == n_chunks - 1)
    def _emit_state():
        hout_ref[0] = h_ref[...]


def _heads_per_block(H: int, P: int) -> int:
    return 8 if H % 8 == 0 and (8 * P) % 128 == 0 else H


def ssd_scan_chunked(
    x: jax.Array,    # (B, S, H, P)
    dt: jax.Array,   # (B, S, H)
    A: jax.Array,    # (H,)
    Bm: jax.Array,   # (B, S, N)
    Cm: jax.Array,   # (B, S, N)
    h0: jax.Array,   # (B, H, P, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """On the chip ``chunk`` must be a multiple of 128 or the whole sequence:
    it is the lane dim of the dt tile."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, "pad sequence to a chunk multiple upstream"
    n_chunks = S // chunk

    # A program takes a group of G heads: Mosaic needs a block's last two
    # dims to be multiples of the (8, 128) tile or the whole array dims, so
    # a one-head x tile (1, chunk, 1, P) does not compile.  G = 8 where the
    # y tile's G * P lanes fill whole 128-lane tiles, else all H heads; the
    # body unrolls its G heads.  dt rides head-major, (B, H, S), and A as
    # (H, 1), so their tiles put G in the sublane dim.  VMEM per grid step
    # at mamba2-370m widths (chunk 256, G 8, P 64, N 128): x 256 x 8 x 128
    # (P padded to the lane tile) x 2 B = 512 KiB, y 256 x 512 x 2 B =
    # 256 KiB, B and C 64 KiB each, dt 8 KiB, h0 and h_out 8 x 64 x 128 x
    # 4 B = 256 KiB each; double-buffered about 2.8 MiB, plus the 256 KiB
    # state scratch and the body's (chunk, chunk) f32 temporaries (256 KiB
    # each).
    G = _heads_per_block(H, P)
    kernel = functools.partial(_kernel, chunk=chunk)
    y, h = pl.pallas_call(
        kernel,
        grid=(B, H // G, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, G, P), lambda b, g, c: (b, c, g, 0)),  # x
            pl.BlockSpec((1, G, chunk), lambda b, g, c: (b, g, c)),        # dt
            pl.BlockSpec((G, 1), lambda b, g, c: (g, 0)),                  # A
            pl.BlockSpec((1, chunk, N), lambda b, g, c: (b, c, 0)),        # B
            pl.BlockSpec((1, chunk, N), lambda b, g, c: (b, c, 0)),        # C
            pl.BlockSpec((1, G, P, N), lambda b, g, c: (b, g, 0, 0)),      # h0
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, G * P), lambda b, g, c: (b, c, g)),    # y
            pl.BlockSpec((1, G, P, N), lambda b, g, c: (b, g, 0, 0)),      # h_out
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H * P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((G, P, N), jnp.float32)],
        interpret=interpret,
    )(x, jnp.swapaxes(dt, 1, 2), A.reshape(H, 1), Bm, Cm, h0)
    return y.reshape(B, S, H, P), h
