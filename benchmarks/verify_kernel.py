"""SLED verification-attention kernel: modeled HBM traffic vs the XLA path.

No TPU in this container, so the comparison is structural: we lower the
pure-XLA flash verification attention, walk its HLO with the trip-aware
cost model, and compare bytes moved against the Pallas kernel's analytic
minimum (stream KV exactly once + write O(Sq) output).  Correctness of the
kernel itself is covered by tests/test_kernels.py (interpret-mode sweeps).

``--engine`` lowers THREE verify-step variants for matched bucket shapes
and compares trip-aware HLO bytes:

  dense        lock-step verify over a dense (bucket,)-batched cache — the
               floor continuous batching is measured against
  gather-paged the PR-1 fallback: gather the scheduled pool rows into a
               dense sub-cache, verify, scatter everything back (the
               "paging tax" — still what SSM/hybrid caches pay)
  slot-paged   the slot-indexed fast path: the forward runs directly
               against the pool, attention streams slot-indexed chunks and
               only the K+1 fresh rows are written back
               (verification.make_paged_verify_step(paged_attention=True),
               mirrored on TPU by kernels/verify_attn.verify_attention_paged)

``--json PATH`` records the rows as a BENCH JSON artifact so CI can track
the paging-tax trajectory across PRs.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timed
from repro.models.layers import flash_attention
from repro.roofline.hlo_cost import HloCostModel

KV_DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32, "int8": jnp.int8}


def run(quick: bool = False, kv_dtype: str = "bf16") -> list:
    rows = []
    kdt = KV_DTYPES[kv_dtype]
    shapes = [
        (8, 5, 48, 1, 4096, 128),   # granite-34b-like MQA verify
        (8, 5, 32, 4, 4096, 128),   # qwen3-moe-like GQA verify
    ] if not quick else [(4, 5, 8, 1, 1024, 64)]
    for (B, Sq, Hq, Hkv, Skv, D) in shapes:
        q = jax.ShapeDtypeStruct((B, Sq, Hq, D), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((B, Hkv, Skv, D), kdt)  # the model's head-major K/V
        v = jax.ShapeDtypeStruct((B, Hkv, Skv, D), kdt)
        kv_valid = jax.ShapeDtypeStruct((B,), jnp.int32)

        def xla_path(q, k, v, kv_valid):
            q_pos = kv_valid[:, None] - Sq + jnp.arange(Sq)[None]
            return flash_attention(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                                   chunk=min(1024, Skv))

        lowered = jax.jit(xla_path).lower(q, k, v, kv_valid)
        costs = HloCostModel(lowered.compile().as_text()).totals()
        # kernel floor: stream K and V exactly once at the CACHE dtype
        # (int8-quantized caches stream half the bf16 bytes), read q + write
        # o once at the activation dtype
        kv_bytes = 2 * B * Skv * Hkv * D * jnp.dtype(kdt).itemsize
        out_bytes = 2 * B * Sq * Hq * D * jnp.dtype(jnp.bfloat16).itemsize
        kernel_min = kv_bytes + out_bytes
        rows.append({
            "shape": f"B{B}xSq{Sq}xHq{Hq}/{Hkv}xS{Skv}xD{D}",
            "kv_dtype": kv_dtype,
            "xla_bytes_mb": round(costs["bytes"] / 1e6, 1),
            "kernel_min_mb": round(kernel_min / 1e6, 1),
            "traffic_ratio": round(costs["bytes"] / kernel_min, 2),
            "mxu_rows_packed": Sq * (Hq // Hkv),
        })
    emit(rows, "verify_kernel")
    return rows


def run_engine(quick: bool = False) -> list:
    """Lower dense vs gather-paged vs slot-indexed-paged verify steps for
    matched bucket shapes and compare trip-aware HLO bytes.  The gather
    variant's surplus is the row gather/scatter paging tax; the slot-indexed
    variant must collapse to ~the dense step's traffic (acceptance: within
    ~1.1x at every bucket size)."""
    from repro.configs.base import get_config
    from repro.core import verification
    from repro.models.model_zoo import build_model

    vocab = 128
    cfg = dataclasses.replace(get_config("qwen2-1.5b").reduced(), vocab_size=vocab)
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    n_slots, k_max, max_len = (4, 4, 64) if quick else (8, 4, 128)

    rows = []
    for bucket in ((2,) if quick else (2, 4, 8)):
        pool = model.make_cache(n_slots + 1, max_len, attn_chunk=32)
        dense_cache = model.make_cache(bucket, max_len, attn_chunk=32)
        batch = verification.verify_batch_spec(bucket, k_max)
        batch = {k: jnp.zeros(v.shape, v.dtype) for k, v in batch.items()}
        slots = jnp.arange(bucket, dtype=jnp.int32)

        dense = verification.make_verify_step(model, greedy=True, attn_chunk=32)
        gather = verification.make_paged_verify_step(
            model, scratch_slot=n_slots, greedy=True, attn_chunk=32,
            paged_attention=False,
        )
        paged = verification.make_paged_verify_step(
            model, scratch_slot=n_slots, greedy=True, attn_chunk=32,
            paged_attention=True,
        )
        assert paged.paged_attention and not gather.paged_attention

        def lowered_bytes(fn, *args):
            hlo = jax.jit(fn).lower(*args).compile().as_text()
            return HloCostModel(hlo).totals()["bytes"]

        d_bytes = lowered_bytes(dense, params, dense_cache, batch)
        g_bytes = lowered_bytes(gather, params, pool, slots, batch)
        p_bytes = lowered_bytes(paged, params, pool, slots, batch)
        rows.append({
            "bucket": bucket,
            "pool_slots": n_slots,
            "dense_bytes_mb": round(d_bytes / 1e6, 2),
            "gather_bytes_mb": round(g_bytes / 1e6, 2),
            "slot_bytes_mb": round(p_bytes / 1e6, 2),
            "gather_tax": round(g_bytes / max(d_bytes, 1), 2),
            "slot_tax": round(p_bytes / max(d_bytes, 1), 2),
        })
    emit(rows, "engine_verify_step")
    return rows


def run_bandwidth(quick: bool = False) -> list:
    """Roofline-predicted vs MEASURED verify bandwidth, bf16 vs int8 pools.

    For each pool dtype the slot-indexed paged verify step is lowered (the
    trip-aware HLO byte count is the roofline traffic prediction) and then
    actually run under ``timed`` — the achieved GB/s is predicted bytes over
    measured wall time.  On a bandwidth-bound verify the int8 pool's HLO
    bytes drop to ~the storage ratio while the achieved bandwidth stays in
    the same regime, which is exactly the capacity-per-HBM-byte claim; both
    columns land side by side in the BENCH artifact so CI tracks them.
    """
    from repro.configs.base import get_config
    from repro.core import verification
    from repro.models.kvcache import PagedKVCache
    from repro.models.model_zoo import build_model

    cfg = dataclasses.replace(get_config("qwen2-1.5b").reduced(), vocab_size=128)
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    n_slots, k_max, max_len = (4, 4, 64) if quick else (8, 4, 256)
    bucket = 2 if quick else 4

    rows = []
    for kv_dtype in ("bf16", "int8"):
        cache_kw = {"attn_chunk": 32}
        if kv_dtype == "int8":
            cache_kw["kv_dtype"] = jnp.int8
        pool = PagedKVCache(model, n_slots, max_len, **cache_kw)
        step = jax.jit(verification.make_paged_verify_step(
            model, scratch_slot=pool.scratch_slot, greedy=True, attn_chunk=32,
        ))
        batch = verification.verify_batch_spec(bucket, k_max)
        batch = {k: jnp.zeros(v.shape, v.dtype) for k, v in batch.items()}
        slots = jnp.arange(bucket, dtype=jnp.int32)
        hlo = jax.jit(step).lower(params, pool.cache, slots, batch).compile().as_text()
        pred_bytes = HloCostModel(hlo).totals()["bytes"]

        def run_step(c):
            res, c2 = step(params, pool.cache, slots, batch)
            jax.block_until_ready(c2["length"])
            return c2

        _, dt = timed(run_step, pool.cache, warmup=2, iters=5)
        rows.append({
            "kv_dtype": kv_dtype,
            "bucket": bucket,
            "pool_bytes": pool.pool_bytes(),
            "bytes_per_slot": pool.bytes_per_slot(),
            "roofline_bytes_mb": round(pred_bytes / 1e6, 2),
            "us_per_call": round(dt * 1e6, 1),
            "achieved_gbs": round(pred_bytes / max(dt, 1e-9) / 1e9, 3),
        })
    bf16, int8 = rows
    rows.append({
        "kv_dtype": "int8/bf16",
        "bytes_per_slot_ratio": round(bf16["bytes_per_slot"] / int8["bytes_per_slot"], 2),
        "roofline_bytes_ratio": round(
            int8["roofline_bytes_mb"] / max(bf16["roofline_bytes_mb"], 1e-9), 2
        ),
        "time_ratio": round(int8["us_per_call"] / max(bf16["us_per_call"], 1e-9), 2),
    })
    emit([dict(r) for r in rows], "verify_bandwidth")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", action="store_true",
                    help="compare dense/gather-paged/slot-paged verify-step HLO traffic")
    ap.add_argument("--bandwidth", action="store_true",
                    help="roofline-predicted vs measured verify bandwidth, bf16 vs int8 pools")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--kv-dtype", choices=sorted(KV_DTYPES), default="bf16",
                    help="cache dtype for the kernel-vs-XLA comparison "
                         "(kernel_min derives from it — int8 halves the floor)")
    ap.add_argument("--json", type=str, default="",
                    help="also write the rows as a BENCH JSON artifact")
    a = ap.parse_args()
    if a.engine:
        rows = run_engine(quick=a.quick)
        name = "engine_verify_step"
    elif a.bandwidth:
        rows = run_bandwidth(quick=a.quick)
        name = "verify_bandwidth"
    else:
        rows = run(quick=a.quick, kv_dtype=a.kv_dtype)
        name = "verify_kernel"
    if a.json:
        with open(a.json, "w") as f:
            json.dump({"benchmark": name, "quick": a.quick, "rows": rows}, f, indent=2)
        print(f"wrote {a.json}")


if __name__ == "__main__":
    main()
