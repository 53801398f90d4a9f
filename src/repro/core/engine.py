"""Engine core: the pure single-replica verify stepper (no admission logic).

This is the bottom layer of the serving stack (SLED §III-B compute only):
a :class:`PagedKVCache` row pool plus the jitted prefill / bucketed
slot-indexed verify / force-extend steps that run against it.  Everything
policy-shaped — who is admitted, which requests batch together, when the
planner fires — lives one layer up (core/admission.py + core/server_engine.py),
and replica placement lives above that (cluster/router.py).  The core only
answers "verify THESE slots with THIS padded batch" and "append THESE tokens
to THAT slot", which is exactly the unit a cluster router schedules.

The jitted step bundle (:class:`VerifySteps`) is deliberately separable from
the pool so N replicas of the same model share one set of compiled
executables: compiled shapes depend only on (bucket, k_max, pool geometry),
so a replica fleet costs the same XLA compilation as one engine.
"""

from __future__ import annotations

import dataclasses
import logging
import re
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core import verification
from repro.models.kvcache import PagedKVCache, gather_slots, supports_paged_attention, write_row
from repro.models.layers import NO_MESH, MeshContext

log = logging.getLogger(__name__)

# pool storage dtypes the engine understands; "int8" adds per-(slot, head)
# dequant-scale leaves and roughly halves bytes-per-slot (models/layers.py)
KV_DTYPES = {"bf16": jnp.bfloat16, "int8": jnp.int8}


def kv_dtype_name(kv_dtype) -> str:
    """Normalise a ``kv_dtype`` (spec string or jnp dtype) to its spec name."""
    if isinstance(kv_dtype, str):
        if kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"unknown kv_dtype {kv_dtype!r} (one of {sorted(KV_DTYPES)})"
            )
        return kv_dtype
    return "int8" if kv_dtype == jnp.int8 else "bf16"


@dataclasses.dataclass
class Verdict:
    """Per-request outcome of one engine round (device resume protocol).

    ``accept_rate`` / ``queue_depth`` are the closed-loop feedback fields:
    THIS round's draft-acceptance ratio and the replica's planner queue
    depth right after dispatch.  Devices use them to adapt their speculation
    length online (serving/speclen.py — its EWMA does the smoothing, so the
    raw per-round signal stays responsive to regime shifts); they ride the
    wire in Verdict frames (transport/codec.py).
    """

    device_id: int
    n_accepted: int
    tokens: np.ndarray  # committed this round: accepted drafts + extra
    next_prev: int  # correction/bonus token the device feeds next round
    accept_rate: float = 0.0  # this round's accepted/drafted
    queue_depth: int = 0  # replica queue depth after this dispatch
    # server-timing breakdown (always populated — cheap host floats): how
    # long this round waited in the admission queue and how long its verify
    # step took, so receivers can attribute latency to queue vs verify vs wire
    queue_s: float = 0.0
    verify_s: float = 0.0  # includes the device time: ends when results reach the host


@dataclasses.dataclass
class RoundStats:
    time: float
    size: int  # batch fill (requests verified)
    bucket: int  # padded jit batch size
    queue_depth: int  # planner queue after dispatch
    n_commit: int  # tokens committed this round
    step_seconds: float  # verify call through its results on the host (device time included)


@dataclasses.dataclass
class EngineStats:
    """Aggregate serving stats; field names mirror simulator.SimResult.

    The wire fields (bytes/frames both directions, drops) are zero for the
    in-process driver and filled in by transport.server.TransportServer from
    its link stats, so benchmarks emit one uniform record either way.
    """

    wstgr: float
    per_device_rate: float
    server_busy_frac: float
    rounds: int
    timeouts: int
    fallback_tokens: int
    mean_batch_fill: float
    mean_round_latency: float
    server_rounds_per_s: float
    partial_rounds: int = 0
    streams_served: int = 0
    acceptance_rate: float = 0.0
    mean_queue_depth: float = 0.0
    # wire stats (transport runtime only)
    bytes_tx: int = 0
    bytes_rx: int = 0
    frames_tx: int = 0
    frames_rx: int = 0
    frames_dropped: int = 0
    fallback_rounds: int = 0
    replicas: int = 1  # >1 only for cluster-merged records

    def to_json(self) -> dict:
        """The uniform stats record (json.dumps-safe) every driver and BENCH
        artifact emits — same shape in-process, over the wire, or merged."""
        return dataclasses.asdict(self)

    def as_dict(self):
        return self.to_json()

    @classmethod
    def merge(cls, stats: Sequence["EngineStats"]) -> "EngineStats":
        """Aggregate per-replica stats into one cluster-level record.

        Replicas serve concurrently, so count and throughput fields
        (rounds, wstgr, server_rounds_per_s, wire bytes/frames) sum; mean
        fields (batch fill, round latency, queue depth) and acceptance_rate
        are weighted by each replica's round count; busy fractions sum and
        are capped at 1.0 only in the sense that callers interpret >1 as
        "more than one replica's worth of compute" (single event loop runs
        them back to back).  ``per_device_rate`` is recomputed from the
        merged throughput over the summed stream counts (reconstructed from
        wstgr / per_device_rate per replica, falling back to streams_served).
        """
        stats = list(stats)
        if not stats:
            raise ValueError("EngineStats.merge needs at least one record")
        if len(stats) == 1:
            return dataclasses.replace(stats[0])
        rounds = [s.rounds for s in stats]
        total_rounds = sum(rounds)

        def wmean(vals):
            # idle replicas (0 rounds) carry no weight in the means
            if total_rounds == 0:
                return float(sum(vals) / len(vals))
            return float(sum(v * r for v, r in zip(vals, rounds)) / total_rounds)

        n_streams = []
        for s in stats:
            if s.per_device_rate > 0:
                n_streams.append(s.wstgr / s.per_device_rate)
            else:  # idle replica: contributes its served count (possibly 0)
                n_streams.append(float(s.streams_served))
        wstgr = sum(s.wstgr for s in stats)
        return cls(
            wstgr=wstgr,
            per_device_rate=wstgr / max(sum(n_streams), 1e-9),
            server_busy_frac=sum(s.server_busy_frac for s in stats),
            rounds=sum(rounds),
            timeouts=sum(s.timeouts for s in stats),
            fallback_tokens=sum(s.fallback_tokens for s in stats),
            mean_batch_fill=wmean([s.mean_batch_fill for s in stats]),
            mean_round_latency=wmean([s.mean_round_latency for s in stats]),
            server_rounds_per_s=sum(s.server_rounds_per_s for s in stats),
            partial_rounds=sum(s.partial_rounds for s in stats),
            streams_served=sum(s.streams_served for s in stats),
            acceptance_rate=wmean([s.acceptance_rate for s in stats]),
            mean_queue_depth=wmean([s.mean_queue_depth for s in stats]),
            bytes_tx=sum(s.bytes_tx for s in stats),
            bytes_rx=sum(s.bytes_rx for s in stats),
            frames_tx=sum(s.frames_tx for s in stats),
            frames_rx=sum(s.frames_rx for s in stats),
            frames_dropped=sum(s.frames_dropped for s in stats),
            fallback_rounds=sum(s.fallback_rounds for s in stats),
            replicas=sum(s.replicas for s in stats),
        )


# an HLO instruction line: its name, its result shape(s), its opcode
_HLO_OP = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s+([\w\-]+)\(", re.M)


def pool_copies(hlo: str, pool: Dict[str, Any]) -> List[str]:
    """Names of the instructions in a compiled program's HLO
    (``compiled.as_text()``) that copy a whole pool leaf: ``copy`` and
    ``copy-start`` whose result has a K/V (or latent) leaf's shape.  Empty
    when the program updates the pool where it lies."""
    dims = {"[" + ",".join(map(str, a.shape)) + "]" for a in jax.tree.leaves(pool) if a.ndim >= 3}
    return [
        name for name, result, op in _HLO_OP.findall(hlo)
        if op in ("copy", "copy-start") and any(d in result for d in dims)
    ]


def _pad_to(a: np.ndarray, n: int, fill=0) -> np.ndarray:
    if a.shape[0] == n:
        return a
    pad = np.full((n - a.shape[0],) + a.shape[1:], fill, a.dtype)
    return np.concatenate([a, pad], axis=0)


class VerifySteps:
    """The jitted step bundle for one (model, serving config): prefill,
    bucketed slot-indexed verify, force-extend.

    Build it once and hand it to every :class:`EngineCore` replica of that
    model — jax.jit caches on the wrapped closure, so replicas sharing a
    bundle share compiled executables (same shapes, same functions) instead
    of each paying the full warmup.

    ``verify`` and ``extend`` take the pool donated (argument 1): the new
    pool aliases the old one, which the caller must not read again.
    """

    def __init__(
        self,
        model: Any,
        *,
        scratch_slot: int,
        ctx: MeshContext = NO_MESH,
        greedy: bool = True,
        temperature: float = 1.0,
        attn_chunk: int = 32,
        kv_dtype: Any = "bf16",
    ):
        self.model = model
        # MoE steps report the rows each held expert computed
        # (engine_moe_expert_rows_total), only while telemetry is on
        self.expert_rows = telemetry.enabled() and model.cfg.family == "moe"
        self.greedy = greedy
        self.temperature = temperature
        self.scratch_slot = scratch_slot
        self.attn_chunk = attn_chunk
        # recorded for shared-bundle validation only: the jitted steps are
        # dtype-polymorphic (they retrace on leaf dtypes), but a fleet mixing
        # pool dtypes behind one bundle would silently compile everything
        # twice, defeating the shared-warmup contract
        self.kv_dtype = kv_dtype_name(kv_dtype)
        self.verify = jax.jit(
            verification.make_paged_verify_step(
                model,
                scratch_slot=scratch_slot,
                ctx=ctx,
                greedy=greedy,
                temperature=temperature,
                attn_chunk=attn_chunk,
                expert_rows=self.expert_rows,
            ),
            donate_argnums=(1,),
        )
        self.prefill = jax.jit(
            verification.make_prefill_step(model, ctx=ctx, attn_chunk=attn_chunk)
        )
        self.extend = jax.jit(
            verification.make_force_extend_step(model, ctx=ctx, attn_chunk=attn_chunk),
            donate_argnums=(1,),
        )


class EngineCore:
    """Pure single-replica verify stepper: row pool + bucketed verification.

    Owns the :class:`PagedKVCache` pool and runs padded verify batches
    against arbitrary slot subsets.  It knows nothing about device streams,
    admission, planners, or policies — callers hand it slot ids and padded
    request arrays and get a VerifyResult back.  That separation is what
    lets a cluster router treat replicas as schedulable capacity.
    """

    def __init__(
        self,
        model: Any,
        params: Any,
        *,
        n_slots: int,
        max_len: int,
        k_max: int,
        greedy: bool = True,
        temperature: float = 1.0,
        attn_chunk: int = 32,
        ctx: MeshContext = NO_MESH,
        buckets: Optional[Sequence[int]] = None,
        batch_cap: Optional[int] = None,
        steps: Optional[VerifySteps] = None,
        kv_dtype: Any = "bf16",
        device: Optional[jax.Device] = None,
    ):
        # ``device`` commits this replica's params and pool to one chip, so
        # replicas in one process each get their own; every jitted step then
        # runs where its committed operands live.  None: the default device.
        self.device = device
        if device is not None:
            params = jax.device_put(params, device)
        self.model = model
        self.params = params
        self.k_max = k_max
        self.greedy = greedy
        self.kv_dtype = kv_dtype_name(kv_dtype)
        cache_kw: Dict[str, Any] = {"attn_chunk": attn_chunk}
        if self.kv_dtype == "int8":
            if not supports_paged_attention(model.cfg):
                raise ValueError(
                    f"kv_dtype='int8' is not supported for the "
                    f"{model.cfg.family!r} family: its recurrent-state cache "
                    "leaves ride the gather/scatter fallback "
                    "(models/kvcache.py), which has no quantized layout — "
                    "serve it with kv_dtype='bf16'"
                )
            cache_kw["kv_dtype"] = KV_DTYPES["int8"]
        self.pool = PagedKVCache(model, n_slots, max_len, device=device, **cache_kw)
        if steps is not None:
            # a mismatched shared bundle would fail (or recompile every
            # bucket behind warmup's back) deep inside step(); fail at the
            # constructor with the actual disagreement instead
            mismatches = [
                (name, got, want)
                for name, got, want in (
                    ("scratch_slot", steps.scratch_slot, self.pool.scratch_slot),
                    ("model", steps.model, model),
                    ("greedy", steps.greedy, greedy),
                    ("temperature", steps.temperature, temperature),
                    ("attn_chunk", steps.attn_chunk, attn_chunk),
                    ("kv_dtype", steps.kv_dtype, self.kv_dtype),
                )
                if got is not want and got != want
            ]
            if mismatches:
                raise ValueError(
                    "shared VerifySteps bundle does not match this engine "
                    "(replicas must be homogeneous to share compiled steps): "
                    + ", ".join(f"{n}: bundle={g!r} engine={w!r}" for n, g, w in mismatches)
                )
        self.steps = steps or VerifySteps(
            model,
            scratch_slot=self.pool.scratch_slot,
            ctx=ctx,
            greedy=greedy,
            temperature=temperature,
            attn_chunk=attn_chunk,
            kv_dtype=self.kv_dtype,
        )
        if telemetry.enabled():
            # pool capacity gauges: the memory-ceiling story (ISSUE: int8
            # roughly halves bytes_per_slot, doubling slots per HBM byte)
            reg = telemetry.registry()
            reg.gauge("engine_kv_pool_bytes").set(float(self.pool.pool_bytes()))
            reg.gauge("engine_bytes_per_slot").set(float(self.pool.bytes_per_slot()))
        cap = batch_cap or n_slots
        self.batch_cap = cap
        if buckets is None:
            buckets, b = [], 1
            while b < cap:
                buckets.append(b)
                b *= 2
            buckets.append(cap)
        self.buckets = sorted(set(buckets))
        self.compile_log: Dict[int, float] = {}  # bucket -> warmup seconds
        self._seed = 0

    # -- slot lifecycle ------------------------------------------------------

    def alloc_slot(self) -> int:
        """Free pool row for a new stream; raises SlotExhausted when full."""
        return self.pool.alloc()

    def free_slot(self, slot: int) -> None:
        self.pool.free(slot)

    @property
    def n_free(self) -> int:
        return self.pool.n_free

    def prefill_slot(self, slot: int, prompt: jax.Array) -> int:
        """Prefill ``prompt`` into pool row ``slot``; returns the last prompt
        token (the stream's first ``prev_token``)."""
        with telemetry.span("prefill", "engine_prefill_seconds"):
            row = self.pool.make_row_cache()
            prompt = jnp.asarray(prompt, jnp.int32)
            _, row, prev = self.steps.prefill(self.params, row, prompt[None, :])
            with telemetry.span("pool_write"):
                self.pool.write_slot(slot, row)
            return int(prev[0])

    def export_row(self, slot: int) -> Dict[str, jax.Array]:
        """Dense batch-1 copy of pool row ``slot`` (stream migration: the
        row moves to another replica's pool bit-identically)."""
        return gather_slots(self.pool.cache, jnp.asarray([slot], jnp.int32))

    def import_row(self, slot: int, row_cache: Dict[str, jax.Array]) -> None:
        """Install an exported row into pool row ``slot`` (moved onto this
        replica's device first when the row comes from another chip)."""
        if self.device is not None:
            row_cache = jax.device_put(row_cache, self.device)
        self.pool.write_slot(slot, row_cache)

    # -- compute -------------------------------------------------------------

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def warmup(self, buckets: Optional[Sequence[int]] = None) -> Dict[int, float]:
        """Compile the verify step for bucket sizes up front (batches of
        scratch-slot rows), so measured runs never pay a mid-serving compile.
        Safe anytime: scratch contents are never read as committed state.

        ``buckets`` selects a subset of ``self.buckets`` (deployments budget
        startup by warming only the fills they expect; the rest compile
        lazily on first dispatch).  Returns ``{bucket: compile_seconds}``
        for this call — also accumulated in ``self.compile_log`` and logged
        at INFO so startup budgets are observable (ROADMAP "bucket
        compilation budget").  With telemetry on it also records
        ``engine_pool_copies`` (:meth:`_record_pool_copies`)."""
        if buckets is None:
            selected = list(self.buckets)
        else:
            selected = sorted(set(int(b) for b in buckets))
            unknown = [b for b in selected if b not in self.buckets]
            if unknown:
                raise ValueError(
                    f"unknown warmup buckets {unknown}; engine buckets are {self.buckets}"
                )
        times: Dict[int, float] = {}
        for b in selected:
            t0 = time.perf_counter()
            slots = jnp.full((b,), self.pool.scratch_slot, jnp.int32)
            _, self.pool.cache = self.steps.verify(
                self.params, self.pool.cache, slots, self._scratch_batch(b)
            )
            jax.block_until_ready(self.pool.cache["length"])
            times[b] = time.perf_counter() - t0
            log.info("warmup: bucket %d verify step ready in %.2fs", b, times[b])
        self.compile_log.update(times)
        if telemetry.enabled():
            self._record_pool_copies(selected)
        return times

    def _scratch_batch(self, b: int) -> Dict[str, jax.Array]:
        return verification.make_verify_batch(
            jnp.zeros((b,), jnp.int32),
            jnp.zeros((b, self.k_max), jnp.int32),
            jnp.zeros((b,), jnp.int32),
            draft_q=None if self.greedy else jnp.zeros((b, self.k_max), jnp.float32),
            seed=np.uint32(0),
        )

    def _record_pool_copies(self, buckets: Sequence[int]) -> None:
        """Gauge ``engine_pool_copies{program,bucket}``: whole-pool copies
        in the compiled verify (per bucket), extend and admission-write
        programs, which should update the pool in place (0).  Each program
        is compiled once more to read its HLO, so only with telemetry on."""
        pool = self.pool.cache
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        programs = [
            ("verify", b, self.steps.verify.lower(self.params, pool, i32(b), self._scratch_batch(b)))
            for b in buckets
        ]
        programs.append(("extend", 1, self.steps.extend.lower(
            self.params, pool, i32(1), i32(1, self.k_max + 1), i32(1))))
        programs.append(("write", 1, write_row.lower(
            pool, i32(), jax.eval_shape(self.pool.make_row_cache))))
        reg = telemetry.registry()
        for program, bucket, lowered in programs:
            n = len(pool_copies(lowered.compile().as_text(), pool))
            reg.gauge("engine_pool_copies", labels={"program": program, "bucket": bucket}).set(n)
            if n:
                log.warning("%s program (bucket %d) copies a whole pool leaf %d times: "
                            "the pool is not updated in place", program, bucket, n)

    def verify(
        self,
        slots: np.ndarray,
        prev: np.ndarray,
        toks: np.ndarray,
        qs: Optional[np.ndarray],
        lens: np.ndarray,
    ) -> Tuple[Any, int, int]:
        """Launch one bucketed verify pass over pool rows ``slots``.

        Inputs are the un-padded per-request arrays; the core pads them to
        the enclosing bucket (scratch-slot rows for the fill) and commits
        the accepted prefixes into the pool.  Returns ``(VerifyResult,
        bucket, fill)``, fill being the real requests before padding.  The
        result's arrays are still on the device: the caller's first read of
        them waits for the device, so it times the verify step, this call
        only its launch.
        """
        with telemetry.span("pack"):
            bucket = self.bucket_for(slots.shape[0])
            slots_p = _pad_to(np.asarray(slots, np.int32), bucket, fill=self.pool.scratch_slot)
            vb = verification.make_verify_batch(
                jnp.asarray(_pad_to(prev, bucket)),
                jnp.asarray(_pad_to(toks, bucket)),
                jnp.asarray(_pad_to(lens, bucket)),
                draft_q=jnp.asarray(_pad_to(qs, bucket)) if qs is not None else None,
                seed=np.uint32(self._seed),
            )
            slots_d = jnp.asarray(slots_p)
        with telemetry.span("launch"):
            res, self.pool.cache = self.steps.verify(self.params, self.pool.cache, slots_d, vb)
        self._seed += 1
        if telemetry.enabled():
            telemetry.observe(
                "engine_verify_fill", slots.shape[0], buckets=telemetry.K_BUCKETS
            )
        return res, bucket, int(slots.shape[0])

    def force_extend(self, slot: int, feed: np.ndarray) -> None:
        """Append ``feed`` (already shifted to satisfy the KV invariant) to
        pool row ``slot`` without verification (§III-A fallback resync)."""
        with telemetry.span("force_extend", "engine_commit_seconds"):
            self._force_extend(slot, feed)

    def _force_extend(self, slot: int, feed: np.ndarray) -> None:
        padded = np.zeros((self.k_max + 1,), np.int32)
        padded[: feed.size] = feed
        self.pool.cache = self.steps.extend(
            self.params,
            self.pool.cache,
            jnp.asarray([slot], jnp.int32),
            jnp.asarray(padded[None, :]),
            jnp.asarray([feed.size], jnp.int32),
        )
