"""Single-replica verification engine: EngineCore + AdmissionControl composed.

This is the real-model counterpart of serving/simulator.py's server loop
(SLED §III-B): verification requests from heterogeneous edge devices queue
in a BatchPlanner, and whenever the policy fires the engine verifies the
scheduled SUBSET of device streams in one forward pass — partial fills,
heterogeneous draft lengths, devices joining and leaving mid-stream.

Layering (the engine-core refactor):

  core/engine.py      EngineCore — the pure verify stepper: PagedKVCache row
                      pool, jitted prefill/verify/extend steps (a shareable
                      VerifySteps bundle), bucket selection, warmup.
  core/admission.py   AdmissionControl — stream registry, one-inflight-round
                      queue discipline, BatchPlanner dispatch policies.
  here                ServerEngine — composes the two behind the original
                      single-replica API, and adds the serving stats.
  cluster/router.py   Router — N ServerEngine replicas behind a placement
                      policy (admission becomes a placement decision).

Per-round and aggregate stats mirror serving/simulator.SimResult field names
so discrete-event predictions can be cross-checked against real-model runs
(benchmarks/wstgr.py --engine does exactly that).

EdgeDeviceKit/EdgeDevice are the host-side stand-ins for device drafting
loops (batch-1 draft model per device, shared jitted step), used by
launch/serve.py, transport/client.py, and the tests.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core import drafting, verification
from repro.core.admission import AdmissionControl, DeviceStream
from repro.core.engine import (
    EngineCore,
    EngineStats,
    RoundStats,
    Verdict,
    VerifySteps,
)
from repro.models.kvcache import SlotExhausted
from repro.models.layers import NO_MESH, MeshContext

__all__ = [
    "DeviceStream",
    "EdgeDevice",
    "EdgeDeviceKit",
    "EngineStats",
    "RoundStats",
    "ServerEngine",
    "Verdict",
]

log = logging.getLogger(__name__)


class ServerEngine:
    """Admission + step loop for ONE replica: PagedKVCache pool, BatchPlanner
    policies, bucketed slot-indexed verification.

    Typical driver loop (see launch/serve.py)::

        engine = ServerEngine(target, tp, n_slots=8, max_len=256, k_max=4)
        engine.admit(device_id, prompt, now)          # joins a free slot
        engine.submit(device_id, draft_tokens, now)   # device -> server hop
        verdicts = engine.step(now)                   # policy may dispatch
        engine.retire(device_id)                      # frees the slot

    Pass a shared :class:`~repro.core.engine.VerifySteps` via ``steps`` to
    make replicas of the same model share compiled executables
    (cluster/router.py does this for its whole replica set).
    """

    def __init__(
        self,
        model: Any,
        params: Any,
        *,
        n_slots: int,
        max_len: int,
        k_max: int,
        policy: str = "continuous",
        batch_size: Optional[int] = None,
        max_wait: float = 0.050,
        straggler_timeout: float = 1.0,
        greedy: bool = True,
        temperature: float = 1.0,
        attn_chunk: int = 32,
        ctx: MeshContext = NO_MESH,
        buckets: Optional[Sequence[int]] = None,
        steps: Optional[VerifySteps] = None,
        kv_dtype: Any = "bf16",
        device: Optional[jax.Device] = None,
    ):
        cap = batch_size or n_slots
        self.core = EngineCore(
            model,
            params,
            n_slots=n_slots,
            max_len=max_len,
            k_max=k_max,
            greedy=greedy,
            temperature=temperature,
            attn_chunk=attn_chunk,
            ctx=ctx,
            buckets=buckets,
            batch_cap=cap,
            steps=steps,
            kv_dtype=kv_dtype,
            device=device,
        )
        self.admission = AdmissionControl(
            batch_size=cap,
            k_max=k_max,
            policy=policy,
            max_wait=max_wait,
            straggler_timeout=straggler_timeout,
            greedy=greedy,
        )
        self.k_max = k_max
        self.greedy = greedy
        self._batch_cap = cap
        self.round_log: List[RoundStats] = []
        # telemetry: full per-round trace (grows only while telemetry is on)
        # plus the bounded flight recorder that crash/eviction/drain dumps
        self.trace: List[telemetry.TraceEvent] = []
        self.flight = telemetry.FlightRecorder()
        self._round_seq: Dict[int, int] = {}  # device_id -> next round seq
        self._t0: Optional[float] = None
        self._t_last = 0.0
        self._committed_total = 0
        self._busy_seconds = 0.0
        self._latencies: List[float] = []
        self._drafted = 0
        self._accepted = 0
        self._fallback_tokens = 0
        self._fallback_rounds = 0

    # -- composition surface (back-compat aliases) ---------------------------

    @property
    def model(self):
        return self.core.model

    @property
    def params(self):
        return self.core.params

    @property
    def pool(self):
        return self.core.pool

    @property
    def steps(self) -> VerifySteps:
        return self.core.steps

    @property
    def device(self):
        return self.core.device

    @property
    def kv_dtype(self) -> str:
        return self.core.kv_dtype

    @property
    def buckets(self):
        return self.core.buckets

    @property
    def compile_log(self):
        return self.core.compile_log

    @property
    def planner(self):
        return self.admission.planner

    @property
    def streams(self) -> Dict[int, DeviceStream]:
        return self.admission.streams

    @property
    def drafted_tokens(self) -> int:
        """Lifetime draft tokens verified (benchmark calibration surface)."""
        return self._drafted

    @property
    def accepted_tokens(self) -> int:
        """Lifetime draft tokens accepted (benchmark calibration surface)."""
        return self._accepted

    @property
    def _timeouts(self) -> int:
        return self.admission.timeouts

    @property
    def _streams_served(self) -> int:
        return self.admission.streams_served

    def warmup(self, buckets: Optional[Sequence[int]] = None) -> Dict[int, float]:
        return self.core.warmup(buckets)

    # -- admission -----------------------------------------------------------

    def admit(self, device_id: int, prompt: jax.Array, now: float = 0.0) -> Optional[DeviceStream]:
        """Prefill ``prompt`` into a free pool slot; None when the pool is full
        (the device retries once a stream retires)."""
        if device_id in self.streams:
            raise ValueError(f"device {device_id} already admitted")
        try:
            slot = self.core.alloc_slot()
        except SlotExhausted:
            return None
        prev = self.core.prefill_slot(slot, prompt)
        stream = self.admission.register(device_id, slot, prev, now)
        if self._t0 is None:
            self._t0 = now
        return stream

    def retire(self, device_id: int) -> DeviceStream:
        """Stream finished (or left): free its slot for the next admission.
        Any still-queued request from the device is discarded."""
        stream = self.admission.release(device_id, served=True)
        self.core.free_slot(stream.slot)
        self._round_seq.pop(device_id, None)
        return stream

    # -- stream migration (cluster router) -----------------------------------

    def export_stream(self, device_id: int):
        """Detach a quiescent stream for migration to another replica.

        Returns ``(stream, row_cache)`` — the server-side stream state plus a
        bit-exact dense copy of its pool row.  Refuses while a request is in
        flight (the verdict must land first; the row would otherwise change
        under the copy)."""
        if self.admission.has_inflight(device_id):
            raise ValueError(f"device {device_id} has a request in flight; cannot migrate")
        row = self.core.export_row(self.streams[device_id].slot)
        stream = self.admission.release(device_id, served=False)
        self.core.free_slot(stream.slot)
        return stream, row

    def import_stream(self, stream: DeviceStream, row_cache) -> DeviceStream:
        """Adopt a stream exported from another replica: allocate a slot,
        install the row bit-identically, register the stream."""
        slot = self.core.alloc_slot()  # raises SlotExhausted when full
        self.core.import_row(slot, row_cache)
        stream.slot = slot
        self.admission.adopt(stream)
        if self._t0 is None:
            self._t0 = stream.admitted_at
        return stream

    # -- request queue -------------------------------------------------------

    def submit(
        self,
        device_id: int,
        draft_tokens: np.ndarray,
        now: float,
        draft_q: Optional[np.ndarray] = None,
    ) -> None:
        self.admission.submit(device_id, draft_tokens, now, draft_q=draft_q)

    def cancel_request(self, device_id: int) -> bool:
        """Withdraw the device's queued request (transport fallback protocol:
        the device timed out and released its drafts locally).  Returns False
        when nothing is queued — i.e. the request was already verified and a
        verdict is on its way, which the caller must treat as authoritative."""
        return self.admission.cancel(device_id)

    def force_extend(self, device_id: int, tokens: np.ndarray) -> int:
        """Append ``tokens`` to the stream unverified (§III-A fallback resync:
        the device already released them to the user).  Returns the stream's
        new prev token; the device drafts from there next round."""
        stream = self.streams[device_id]
        if self.admission.has_inflight(device_id):
            raise ValueError(f"device {device_id} still has a request in flight")
        toks = np.asarray(tokens, np.int32).reshape(-1)
        if toks.size == 0:
            return stream.prev_token
        if toks.size > self.k_max + 1:
            raise ValueError(f"fallback run of {toks.size} exceeds k_max+1")
        # KV invariant: the last committed token is never in the cache, so we
        # feed [prev, t_1 .. t_{n-1}] and the new prev becomes t_n
        feed = np.concatenate([[stream.prev_token], toks[:-1]]).astype(np.int32)
        self.core.force_extend(stream.slot, feed)
        stream.committed.extend(int(t) for t in toks)
        stream.prev_token = int(toks[-1])
        self._committed_total += toks.size
        self._fallback_tokens += toks.size
        self._fallback_rounds += 1
        if telemetry.enabled():
            telemetry.count("engine_fallback_rounds_total")
            seq = self._round_seq.get(device_id, 0)
            self._round_seq[device_id] = seq + 1
            ev = telemetry.TraceEvent(
                device_id=device_id, round=seq, t=self._t_last,
                k=0, n_accepted=0, n_commit=toks.size, fallback=True,
            )
            self.trace.append(ev)
            self.flight.record(ev)
        return stream.prev_token

    def has_inflight(self, device_id: int) -> bool:
        """True while the device has a queued (unverdicted) request."""
        return self.admission.has_inflight(device_id)

    @property
    def queue_depth(self) -> int:
        return self.admission.queue_depth

    def next_event_hint(self, now: float) -> Optional[float]:
        """Earliest future planner deadline/straggler event (step-loop wake)."""
        return self.admission.next_event_hint(now)

    # -- the serving hot loop ------------------------------------------------

    def step(self, now: float) -> Optional[List[Verdict]]:
        """Ask the planner for a batch; if the policy fires, verify that row
        subset and commit.  Returns per-request verdicts, or None."""
        with telemetry.span("plan"):
            batch = self.admission.next_batch(now)
            if batch is None:
                return None
            prev, toks, qs, lens = batch.padded_arrays()
            slots = np.asarray(
                [self.streams[r.device_id].slot for r in batch.requests], np.int32
            )
        # step_seconds runs until the results are on the host, so it holds
        # the device's time as well as the dispatch's
        t0 = time.perf_counter()
        with telemetry.span("verify"):
            res, bucket, _ = self.core.verify(
                slots,
                prev,
                toks,
                qs if any(r.draft_q is not None for r in batch.requests) else None,
                lens,
            )
            with telemetry.span("sync"):
                out_tokens = np.asarray(res.out_tokens)
                n_accepted = np.asarray(res.n_accepted)
                n_commit = np.asarray(res.n_commit)
                extra = np.asarray(res.extra_token)
        if res.expert_rows is not None:
            first = self.core.model.cfg.held[0]
            for e, n in enumerate(np.asarray(res.expert_rows).tolist()):
                telemetry.count("engine_moe_expert_rows_total", n, labels={"expert": first + e})
        step_seconds = time.perf_counter() - t0
        telemetry.observe("engine_verify_seconds", step_seconds)
        with telemetry.span("commit"):
            depth_after = self.queue_depth
            verdicts = []
            committed_round = 0
            traced = telemetry.enabled()
            for i, req in enumerate(batch.requests):
                stream = self.streams[req.device_id]
                self.admission.resolve(req.device_id)
                self._drafted += int(lens[i])
                self._accepted += int(n_accepted[i])
                stream.drafted += int(lens[i])
                stream.accepted += int(n_accepted[i])
                n = int(n_commit[i])
                toks_i = out_tokens[i, :n]
                stream.committed.extend(int(t) for t in toks_i)
                stream.prev_token = int(extra[i])
                stream.rounds += 1
                committed_round += n
                queue_s = now - req.arrival
                self._latencies.append(queue_s)
                verdicts.append(
                    Verdict(
                        device_id=req.device_id,
                        # per-ROUND acceptance, not the lifetime ratio: a lifetime
                        # average takes O(rounds) to register a regime shift, so
                        # the device-side controller would keep burning k_max
                        # verify tokens long after drafts stopped landing (the
                        # client's EWMA does the smoothing)
                        n_accepted=int(n_accepted[i]),
                        tokens=toks_i,
                        next_prev=int(extra[i]),
                        accept_rate=int(n_accepted[i]) / max(int(lens[i]), 1),
                        queue_depth=depth_after,
                        # server-timing breakdown: populated unconditionally (two
                        # host floats per request) so the client-side attribution
                        # works whether or not this process collects telemetry
                        queue_s=queue_s,
                        verify_s=step_seconds,
                    )
                )
                if traced:
                    seq = self._round_seq.get(req.device_id, 0)
                    self._round_seq[req.device_id] = seq + 1
                    ev = telemetry.TraceEvent(
                        device_id=req.device_id, round=seq, t=now,
                        k=int(lens[i]), n_accepted=int(n_accepted[i]), n_commit=n,
                        queue_s=queue_s, verify_s=step_seconds,
                    )
                    self.trace.append(ev)
                    self.flight.record(ev)
                    telemetry.observe("engine_round_latency_seconds", queue_s + step_seconds)
                    telemetry.observe("engine_k", int(lens[i]), buckets=telemetry.K_BUCKETS)
            self._busy_seconds += step_seconds
            self._committed_total += committed_round
            self._t_last = max(self._t_last, now)
            self.round_log.append(
                RoundStats(
                    time=now,
                    size=batch.size,
                    bucket=bucket,
                    queue_depth=depth_after,
                    n_commit=committed_round,
                    step_seconds=step_seconds,
                )
            )
            return verdicts

    # -- stats ---------------------------------------------------------------

    def stats(self, now: Optional[float] = None) -> EngineStats:
        elapsed = max((now if now is not None else self._t_last) - (self._t0 or 0.0), 1e-9)
        fills = [r.size for r in self.round_log]
        n_streams = max(self._streams_served + len(self.streams), 1)
        return EngineStats(
            wstgr=self._committed_total / elapsed,
            per_device_rate=self._committed_total / n_streams / elapsed,
            server_busy_frac=self._busy_seconds / elapsed,
            rounds=len(self.round_log),
            timeouts=self._timeouts,
            fallback_tokens=self._fallback_tokens,  # transport resyncs land here
            mean_batch_fill=float(np.mean(fills)) if fills else 0.0,
            mean_round_latency=float(np.mean(self._latencies)) if self._latencies else 0.0,
            server_rounds_per_s=len(self.round_log) / elapsed,
            partial_rounds=sum(1 for r in self.round_log if r.size < self._batch_cap),
            streams_served=self._streams_served,
            acceptance_rate=self._accepted / max(self._drafted, 1),
            mean_queue_depth=(
                float(np.mean([r.queue_depth for r in self.round_log]))
                if self.round_log
                else 0.0
            ),
            fallback_rounds=self._fallback_rounds,
        )

    def telemetry_payload(self) -> dict:
        """This replica's telemetry as one JSON-shaped record: the process
        metrics snapshot plus the flight recorder's last-N rounds.  Empty
        while telemetry is off — this is what a worker ships back inside
        codec v3 ``ReplicaStats.telemetry_json``."""
        if not telemetry.enabled():
            return {}
        # refresh the pool capacity gauges at read time: telemetry may have
        # been switched on after engine construction, and `repro top` reads
        # kv_pool_bytes / bytes_per_slot off this snapshot per replica
        reg = telemetry.registry()
        reg.gauge("engine_kv_pool_bytes").set(float(self.pool.pool_bytes()))
        reg.gauge("engine_bytes_per_slot").set(float(self.pool.bytes_per_slot()))
        return {
            "snapshot": reg.snapshot(),
            "flight": self.flight.dump(),
        }


# ---------------------------------------------------------------------------
# Device side: batch-1 drafting loops sharing one jitted step
# ---------------------------------------------------------------------------


class EdgeDeviceKit:
    """Shared jitted draft/prefill steps for a fleet of batch-1 edge devices.

    One kit per (draft model, drafting config): every EdgeDevice spawned from
    it reuses the same compiled functions, so a 64-device fleet costs the
    same compilation as one device.
    """

    def __init__(
        self,
        draft_model: Any,
        draft_params: Any,
        *,
        k_max: int,
        c_th: float = 0.0,
        greedy: bool = True,
        temperature: float = 1.0,
        attn_chunk: int = 32,
    ):
        self.model = draft_model
        self.params = draft_params
        self.k_max = k_max
        self.c_th = float(c_th)
        self._prefill = jax.jit(
            verification.make_prefill_step(draft_model, attn_chunk=attn_chunk)
        )
        # c_th rides as a TRACED scalar argument (it only feeds a jnp compare
        # inside the scan), so the confidence controller can move the bar
        # round to round without ever triggering a recompile
        self._draft = jax.jit(
            lambda p, cache, prev, key, c_th: drafting.draft_round(
                draft_model,
                p,
                cache,
                prev,
                key,
                k_max=k_max,
                c_th=c_th,
                temperature=temperature,
                greedy=greedy,
                keep_q_full=not greedy,
                attn_chunk=attn_chunk,
            )
        )

        # greedy next-token peek (no cache commit): the device's own guess at
        # the bonus token, which seeds pipelined draft-ahead rounds
        def _peek_fn(p, cache, tok):
            h, _, _ = draft_model.decode_forward(p, cache, tok[:, None], attn_chunk=attn_chunk)
            return jnp.argmax(draft_model.lm_head(p, h)[:, 0], axis=-1).astype(jnp.int32)

        self._peek = jax.jit(_peek_fn)
        # draft-ahead replays the post-acceptance state exactly; attention
        # caches roll back by length, but ssm/hybrid recurrences would need
        # checkpoint surgery mid-round — those kits draft strictly in-order
        self.supports_pipeline = greedy and draft_model.cfg.family not in ("ssm", "hybrid")
        self._attn_chunk = attn_chunk

    def spawn(self, device_id: int, prompt: jax.Array, *, max_len: int, seed: int = 0):
        return EdgeDevice(self, device_id, prompt, max_len=max_len, seed=seed)


def _clamp_draft(dres: drafting.DraftResult, k: Optional[int]) -> drafting.DraftResult:
    """Cap a drafting round at ``k`` proposal tokens (adaptive spec length).

    The draft scan always runs the jitted fixed-``k_max`` shape; clamping
    ``lengths`` host-side truncates the *proposal* — greedy drafting is
    autoregressive, so the first ``k`` tokens are exactly what a k-length
    round would have produced, and rollback/resume key off ``lengths`` and
    ``n_accepted`` only, never off the extra scanned positions.
    """
    if k is None or k < 1:
        return dres
    return dataclasses.replace(dres, lengths=jnp.minimum(dres.lengths, jnp.int32(k)))


class EdgeDevice:
    """One edge device's drafting loop (SLED §III-A), batch size 1.

    Supports pipelined draft-ahead (SpecEdge-style): after submitting a round
    the device may keep drafting on the assumption that every token will be
    accepted, seeding the ahead round with its own greedy guess at the bonus
    token.  If the verdict confirms both (full acceptance AND the bonus guess
    was right), the pre-drafted round is submitted with zero draft latency —
    and because greedy drafting is deterministic from (cache, prev), those
    tokens are bit-identical to what a fresh round would have produced, so
    pipelining never changes outputs.  On any miss the ahead work is simply
    discarded (JAX caches are immutable pytrees; rollback is keeping the old
    reference).

    ``draft(k=...)`` caps the proposal length below the kit's ``k_max`` —
    the adaptive spec-length controller (serving/speclen.py) moves that cap
    round to round from the server's verdict feedback.
    """

    def __init__(self, kit: EdgeDeviceKit, device_id: int, prompt, *, max_len: int, seed: int):
        self.kit = kit
        self.device_id = device_id
        cache = kit.model.make_cache(1, max_len, attn_chunk=kit._attn_chunk)
        prompt = jnp.asarray(prompt, jnp.int32)
        _, self.cache, self.prev = kit._prefill(kit.params, cache, prompt[None, :])
        self.key = jax.random.key(seed)
        self.committed: List[int] = []
        self._pending: Optional[drafting.DraftResult] = None
        self._ahead: Optional[tuple] = None  # (bonus_guess, cache_acc, dres)
        self.pending_q: Optional[np.ndarray] = None
        self.pipeline_hits = 0
        self.pipeline_misses = 0
        self.fallback_tokens = 0
        self.drafted = 0
        self.draft_seconds = 0.0  # wall time inside draft() — calibrates
        # the simulator's device_rate against real measured drafting

    def draft(self, k: Optional[int] = None, c_th: Optional[float] = None) -> np.ndarray:
        """Draft up to min(k, k_max) tokens; returns the variable-length
        proposal.  ``pending_q`` holds the matching q(token) row for
        sampling-mode submits (engine.submit(..., draft_q=dev.pending_q)).
        ``c_th`` overrides the kit's confidence bar for this round (the
        adaptive confidence controller moves it from verdict feedback)."""
        assert self._pending is None, "previous round still awaiting a verdict"
        t = time.perf_counter()
        cc = self.kit.c_th if c_th is None else float(c_th)
        self.key, kk = jax.random.split(self.key)
        dres = _clamp_draft(self.kit._draft(self.kit.params, self.cache, self.prev, kk, cc), k)
        self._set_pending(dres)
        n = int(dres.lengths[0])
        toks = np.asarray(dres.tokens[0, :n])  # materialize: honest timing
        self.draft_seconds += time.perf_counter() - t
        self.drafted += n
        return toks

    def _set_pending(self, dres: drafting.DraftResult) -> None:
        self._pending = dres
        n = int(dres.lengths[0])
        self.pending_q = np.asarray(dres.q_sel[0, :n])

    def draft_ahead(
        self, k: Optional[int] = None, c_th: Optional[float] = None
    ) -> Optional[np.ndarray]:
        """Pre-draft the next round while the current one is in flight.

        Returns the ahead proposal (or None if unsupported); it becomes live
        only if on_verdict() confirms the speculation.
        """
        assert self._pending is not None, "draft_ahead needs a round in flight"
        if self._ahead is not None or not self.kit.supports_pipeline:
            return None
        cc = self.kit.c_th if c_th is None else float(c_th)
        pend = self._pending
        n = int(pend.lengths[0])
        last = pend.tokens[:, n - 1]
        # peek at the draft model's bonus-position prediction: feed d_n against
        # the cache rolled to just-before-d_n (no commit — logits only)
        peek_cache = {**pend.cache, "length": pend.base_length + n}
        bonus_guess = int(self.kit._peek(self.kit.params, peek_cache, last)[0])
        # state as if all n drafts were accepted; identical transform to the
        # full-acceptance verdict path, so a hit replays the exact fresh state
        cache_acc = drafting.resume_after_verify(self.kit.model, pend, jnp.asarray([n], jnp.int32))
        self.key, kk = jax.random.split(self.key)
        prev_guess = jnp.asarray([bonus_guess], jnp.int32)
        dres = _clamp_draft(self.kit._draft(self.kit.params, cache_acc, prev_guess, kk, cc), k)
        self._ahead = (bonus_guess, cache_acc, dres)
        m = int(dres.lengths[0])
        return np.asarray(dres.tokens[0, :m])

    def on_verdict(self, verdict: Verdict) -> Optional[np.ndarray]:
        """Roll the draft cache back to the verified prefix and resync.

        Returns the next round's proposal when pipelined draft-ahead was
        confirmed (submit it immediately — the device is already drafting
        ahead of the server), else None (call draft() as usual).
        """
        assert self._pending is not None
        pend = self._pending
        n = int(pend.lengths[0])
        self.committed.extend(int(t) for t in verdict.tokens)
        if self._ahead is not None:
            bonus_guess, cache_acc, ahead = self._ahead
            self._ahead = None
            if verdict.n_accepted == n and verdict.next_prev == bonus_guess:
                self.pipeline_hits += 1
                self.cache = cache_acc
                self.prev = jnp.asarray([bonus_guess], jnp.int32)
                self._set_pending(ahead)
                m = int(ahead.lengths[0])
                return np.asarray(ahead.tokens[0, :m])
            self.pipeline_misses += 1
        self.cache = drafting.resume_after_verify(
            self.kit.model, pend, jnp.asarray([verdict.n_accepted], jnp.int32)
        )
        self.prev = jnp.asarray([verdict.next_prev], jnp.int32)
        self._pending = None
        return None

    def fallback_release(self) -> np.ndarray:
        """§III-A timeout fallback: release the in-flight drafts locally and
        continue as if they were committed.  The caller must resync the
        server (engine.force_extend / transport Fallback frame) with the
        returned tokens before the next verification round."""
        assert self._pending is not None
        pend = self._pending
        n = int(pend.lengths[0])
        toks = np.asarray(pend.tokens[0, :n])
        # accept n-1 drafts cache-side, then the nth rides as prev_token —
        # preserving the "last committed token is never in the KV" invariant
        self.cache = drafting.resume_after_verify(
            self.kit.model, pend, jnp.asarray([n - 1], jnp.int32)
        )
        self.prev = jnp.asarray([int(toks[-1])], jnp.int32)
        self.committed.extend(int(t) for t in toks)
        self.fallback_tokens += n
        self._pending = None
        self._ahead = None
        return toks

    @property
    def awaiting(self) -> bool:
        return self._pending is not None
