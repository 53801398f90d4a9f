"""Cluster router: replica-sharded verification behind one serving surface.

SLED's capacity story (paper Table I) is one shared target model serving many
heterogeneous drafters; at production scale that target tier is N engine
replicas behind a placement layer, not one engine object.  The
:class:`Router` owns N replicas and turns admission into a placement
decision:

  * **placement** — a pluggable :class:`PlacementPolicy` (BatchPlanner-style
    registry: ``least-loaded`` / ``affinity`` / ``round-robin``) picks the
    replica for each new stream among live replicas with a free pool slot;
  * **migration** — when a stream retires and frees a slot, the router may
    migrate an active stream over from the most-loaded replica
    (``migrate_on_retire``).  A migrated KV row is copied bit-exactly
    (``export_stream``/``import_stream``), so migration never changes a
    stream's tokens — only which replica's batches it rides in;
  * **aggregation** — cluster stats are ``EngineStats.merge`` over live
    replicas, and verdicts carry replica-local queue-depth feedback.

Replicas come in two flavors behind one driver surface:

  :class:`LocalReplica`   — wraps an in-process
      :class:`~repro.core.server_engine.ServerEngine`; fleets share one
      jitted VerifySteps bundle, so N replicas cost one XLA compilation.
  RemoteReplica (cluster/remote.py) — proxies the same surface to a
      ``repro worker`` process over codec v3 control frames; the Router
      steps its remotes CONCURRENTLY on a thread pool (each worker verifies
      in its own process, so cluster throughput scales with processes), and
      a transport failure mid-RPC evicts the replica (``_evict``) rather
      than stalling the fleet.

Migration is flavor-guarded: local<->local moves copy the row in memory;
remote<->remote moves ride ExportStream/ImportStream frames (both workers
rebuilt params from the same spec seed, so the row stays bit-valid); a
MIXED local<->remote move raises :class:`MigrationError`, because the two
sides' parameters have different provenance (in-process object vs
spec-seed rebuild) and bit-identity across the move cannot be verified.

The router mirrors the full ServerEngine driver surface (admit / submit /
step / retire / cancel_request / force_extend / stats / warmup), so the
transport server and the in-process serving loops drive a replica fleet by
holding a Router where they held an engine.

**Supervision** is governed by a :class:`~repro.api.spec.FaultPolicy`
(default: today's evict-only behavior).  With ``respawn`` on, an evicted
replica is revived in place — respawn the worker (or redial a dial-only
address), re-place its spec, re-warmup — under a capped, seeded-jitter
:class:`~repro.cluster.faults.Backoff` and a ``max_respawns`` budget; dead
replicas are also redialed periodically from the step loop, and all-dead
becomes retry-until-``all_dead_deadline_s`` instead of instantly fatal.
With ``recover_streams`` on, the streams that went down with a replica are
re-admitted to a surviving (or freshly revived) replica by DEVICE REPLAY:
the router shadows each stream's prompt, committed tokens, and last
unanswered submit, so recovery is admit + chunked ``force_extend`` of the
committed history (runs of <= k_max+1) + re-submit — greedy continuation
stays token-identical to the fault-free run.  Only streams that exceed the
surviving capacity are shed into ``lost_devices``.  A ``heartbeat_interval_s``
 > 0 starts a background Ping monitor that marks silent peers ``suspect``
within seconds instead of waiting out the 120 s control-RPC timeout.
"""

from __future__ import annotations

import logging
import threading
import time
from collections.abc import Mapping
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Set, Tuple

import jax
import numpy as np

from repro import telemetry
from repro.cluster.faults import Backoff
from repro.core.admission import DeviceStream
from repro.core.engine import EngineStats, Verdict
from repro.core.server_engine import ServerEngine

log = logging.getLogger(__name__)


class MigrationError(RuntimeError):
    """A stream move that cannot preserve bit-identity was requested."""


class LocalReplica:
    """In-process replica: a ServerEngine behind the replica driver surface.

    Everything not listed here (admit/submit/step/...) delegates straight to
    the engine; the explicit members are the bits the Router needs uniform
    across flavors (liveness, capacity, fingerprint, lifecycle).
    """

    flavor = "local"

    def __init__(self, engine: ServerEngine):
        self.engine = engine
        self.dead = False
        self.suspect = False
        self._killed = False  # chaos: delegated calls fail like a dead worker

    @property
    def n_free(self) -> int:
        return self.engine.pool.n_free

    @property
    def max_len(self) -> int:
        return self.engine.pool.max_len

    @property
    def fingerprint(self) -> tuple:
        e = self.engine
        # kv_dtype is part of the identity: an int8 row scattered into a
        # bf16 pool (or vice versa) would silently cast and corrupt the cache
        return (e.k_max, e.pool.max_len, e.greedy, e.kv_dtype)

    def chaos_kill(self) -> None:
        """Fault injection: every delegated call now raises ConnectionError,
        which is exactly what a crashed worker looks like to the Router —
        the in-process path exercises the same evict/recover machinery."""
        self._killed = True

    def can_revive(self) -> bool:
        return self._killed  # only a chaos-killed local can come back

    def revive(self) -> None:
        """Undo a chaos kill: the engine object was never actually broken,
        so revival is clearing the flag and retiring the dead incarnation's
        streams (a real respawn starts with an empty pool too)."""
        self._killed = False
        for dev in list(self.engine.streams):
            try:
                self.engine.cancel_request(dev)
            except Exception:
                pass
            try:
                self.engine.retire(dev)
            except Exception:
                pass
        self.dead = False
        self.suspect = False

    def drain(self) -> None:  # lifecycle parity with RemoteReplica
        pass

    def close(self) -> None:
        pass

    def __getattr__(self, name: str):
        if self.__dict__.get("_killed"):
            raise ConnectionError(f"local replica is chaos-killed ({name!r})")
        return getattr(self.engine, name)


class PlacementPolicy:
    """Chooses the replica for a new stream; None when every pool is full."""

    name = "base"

    def choose(self, router: "Router", device_id: int) -> Optional[int]:
        raise NotImplementedError

    @staticmethod
    def _open(router: "Router") -> List[int]:
        return [
            i for i, r in enumerate(router.replicas) if not r.dead and r.n_free > 0
        ]


class LeastLoadedPlacement(PlacementPolicy):
    """Fewest active streams wins (ties break toward the lowest replica id):
    keeps per-replica batch fill even under staggered arrivals."""

    name = "least-loaded"

    def choose(self, router: "Router", device_id: int) -> Optional[int]:
        open_ = self._open(router)
        if not open_:
            return None
        return min(open_, key=lambda i: (len(router.replicas[i].streams), i))


class AffinityPlacement(PlacementPolicy):
    """Deterministic device->replica hash (session/cache affinity); falls
    over to least-loaded when the home replica is full or gone."""

    name = "affinity"

    def choose(self, router: "Router", device_id: int) -> Optional[int]:
        home = device_id % len(router.replicas)
        r = router.replicas[home]
        if not r.dead and r.n_free > 0:
            return home
        return LeastLoadedPlacement().choose(router, device_id)


class RoundRobinPlacement(PlacementPolicy):
    """Cycle through replicas, skipping full pools and dead replicas."""

    name = "round-robin"

    def __init__(self) -> None:
        self._next = 0

    def choose(self, router: "Router", device_id: int) -> Optional[int]:
        n = len(router.replicas)
        for off in range(n):
            i = (self._next + off) % n
            r = router.replicas[i]
            if not r.dead and r.n_free > 0:
                self._next = i + 1
                return i
        return None


class ClassAffinityPlacement(PlacementPolicy):
    """Home a heterogeneous fleet's device CLASSES on replicas (class i ->
    replica i % n): same-class streams share a verify batch, so each
    replica's rounds keep one (k, c_th, draft-model) shape instead of
    interleaving a Jetson's 4-token rounds with an RPi's singletons.  Spills
    to least-loaded when the home replica is full or dead.

    ``class_of`` maps device_id -> class index; System supplies it from the
    fleet spec.  Without a map (bare Router construction) it degrades to
    per-device affinity.
    """

    name = "class-affinity"

    def __init__(self, class_of: Optional[Callable[[int], int]] = None) -> None:
        self.class_of = class_of

    def choose(self, router: "Router", device_id: int) -> Optional[int]:
        cls = self.class_of(device_id) if self.class_of is not None else device_id
        home = cls % len(router.replicas)
        r = router.replicas[home]
        if not r.dead and r.n_free > 0:
            return home
        return LeastLoadedPlacement().choose(router, device_id)


PLACEMENT_POLICIES = {
    p.name: p for p in (
        LeastLoadedPlacement, AffinityPlacement, RoundRobinPlacement,
        ClassAffinityPlacement,
    )
}


def make_placement(policy: str) -> PlacementPolicy:
    if policy not in PLACEMENT_POLICIES:
        raise ValueError(
            f"unknown placement policy {policy!r} (one of {sorted(PLACEMENT_POLICIES)})"
        )
    return PLACEMENT_POLICIES[policy]()


class _StreamView(Mapping):
    """Read-only dict-like view over every replica's streams.

    Membership and lookup go through the router's placement map (O(1) per
    frame in the transport hot path) instead of merging N dicts per access.
    """

    def __init__(self, router: "Router"):
        self._router = router

    def __contains__(self, device_id) -> bool:
        return device_id in self._router._where

    def __getitem__(self, device_id) -> DeviceStream:
        return self._router._replica(device_id).streams[device_id]

    def __iter__(self) -> Iterator[int]:
        return iter(self._router._where)

    def __len__(self) -> int:
        return len(self._router._where)


class Router:
    """N replicas (local and/or remote) + placement: the cluster object."""

    def __init__(
        self,
        replicas: Sequence[Any],
        *,
        placement: str | PlacementPolicy = "least-loaded",
        migrate_on_retire: bool = True,
        faults: Optional[Any] = None,
    ):
        if not replicas:
            raise ValueError("Router needs at least one replica")
        wrapped = [
            LocalReplica(r) if isinstance(r, ServerEngine) else r for r in replicas
        ]
        k_maxes = {r.k_max for r in wrapped}
        max_lens = {r.max_len for r in wrapped}
        if len(k_maxes) > 1 or len(max_lens) > 1:
            raise ValueError(
                f"replicas must be homogeneous for migration: k_max {k_maxes}, "
                f"max_len {max_lens}"
            )
        if faults is None:
            from repro.api.spec import FaultPolicy  # lazy: api sits above cluster

            faults = FaultPolicy()
        self.replicas: List[Any] = wrapped
        self.placement = (
            placement if isinstance(placement, PlacementPolicy) else make_placement(placement)
        )
        self.migrate_on_retire = migrate_on_retire
        self.faults = faults
        self.chaos: Optional[Any] = None  # ChaosInjector, attached by System/tests
        self.migrations = 0
        self.evictions = 0
        self.respawns = 0
        self.recovered_streams = 0
        self.shed_streams = 0
        self.steps_taken = 0  # cluster step counter (chaos schedule clock)
        self.lost_devices: List[int] = []  # streams shed with evicted replicas
        self._where: Dict[int, int] = {}  # device_id -> replica index
        self._pool: Optional[ThreadPoolExecutor] = None  # remote step fan-out
        # router-side shadow flight recorders, one ring per replica: fed from
        # the verdicts the router itself merges, so a post-mortem survives a
        # worker process that died without answering another RPC
        self.flight: Dict[int, telemetry.FlightRecorder] = {
            i: telemetry.FlightRecorder() for i in range(len(wrapped))
        }
        self.flight_dumps: Dict[int, List[dict]] = {}  # idx -> dump at eviction
        self._round_seq: Dict[int, int] = {}  # device_id -> round seq
        self._last_k: Dict[int, int] = {}  # device_id -> last submitted len
        # device-replay shadows: everything needed to rebuild a stream on
        # another replica after its worker dies (prompt + committed history +
        # the round that was in flight, if any)
        self._prompts: Dict[int, np.ndarray] = {}
        self._admit_now: Dict[int, float] = {}
        self._committed: Dict[int, List[int]] = {}
        self._last_submit: Dict[int, Tuple] = {}  # dev -> (tokens, now, draft_q)
        # respawn bookkeeping
        self._backoff: Dict[int, Backoff] = {}
        self._respawn_count: Dict[int, int] = {}
        self._redial_at: Dict[int, float] = {}
        self._hb: Optional[_HeartbeatMonitor] = None

    @classmethod
    def build(
        cls,
        model: Any,
        params: Any,
        *,
        replicas: int,
        n_slots: int,
        placement: str | PlacementPolicy = "least-loaded",
        migrate_on_retire: bool = True,
        faults: Optional[Any] = None,
        **engine_kw,
    ) -> "Router":
        """N homogeneous in-process replicas (``n_slots`` rows each) sharing
        one jitted VerifySteps bundle.  Pass ``steps=`` to share an
        ALREADY-compiled bundle from another homogeneous fleet (spec sweeps
        build every replica count on the same executables).  Remote fleets
        are assembled by repro.api's System.build instead (spawn/dial +
        PlaceReplica, then ``Router``).

        On a host with several chips, replica i commits its params and pool
        to ``jax.local_devices()[i % n]``, so each chip holds its own
        replica; with one device everything stays on the default device."""
        if replicas < 1:
            raise ValueError(f"need at least 1 replica, got {replicas}")
        devices = jax.local_devices()
        steps = engine_kw.pop("steps", None)
        engines: List[ServerEngine] = []
        for i in range(replicas):
            engines.append(ServerEngine(
                model, params, n_slots=n_slots, steps=steps,
                device=devices[i % len(devices)] if len(devices) > 1 else None,
                **engine_kw,
            ))
            steps = engines[0].steps
        return cls(
            engines,
            placement=placement,
            migrate_on_retire=migrate_on_retire,
            faults=faults,
        )

    # -- introspection -------------------------------------------------------

    @property
    def n_replicas(self) -> int:
        return len(self.replicas)

    @property
    def alive(self) -> List[Any]:
        return [r for r in self.replicas if not r.dead]

    @property
    def k_max(self) -> int:
        return self.replicas[0].k_max

    @property
    def streams(self) -> Mapping:
        """Lazy device->stream mapping across replicas (read-only): O(1)
        membership/lookup via the placement map, no per-access dict merge."""
        return _StreamView(self)

    @property
    def queue_depth(self) -> int:
        return sum(r.queue_depth for r in self.alive)

    @property
    def n_free(self) -> int:
        return sum(r.n_free for r in self.alive)

    def replica_of(self, device_id: int) -> int:
        return self._where[device_id]

    def loads(self) -> List[int]:
        """Active stream count per replica (placement test surface)."""
        out = []
        for r in self.replicas:
            try:
                out.append(len(r.streams))
            except ConnectionError:  # chaos-killed local: unreachable engine
                out.append(0)
        return out

    def _replica(self, device_id: int):
        return self.replicas[self._where[device_id]]

    # -- supervision ---------------------------------------------------------

    def _evict(self, idx: int) -> None:
        """A replica's worker is unreachable: mark it dead, harvest the
        streams that went down with it, and keep serving on the survivors.
        Under the default FaultPolicy that is the whole story (a one-shot
        RPC retry happens below this layer, guarded by the worker's v4
        replay cache); with ``respawn``/``recover_streams`` on, the replica
        is revived in place and its streams are re-placed by device replay —
        only what exceeds the surviving capacity is shed."""
        replica = self.replicas[idx]
        if replica.dead:
            return
        replica.dead = True
        lost = [d for d, i in self._where.items() if i == idx]
        for d in lost:
            del self._where[d]
        self.evictions += 1
        # the worker may be gone without a goodbye: dump the router-side
        # shadow ring so the loss report carries the replica's last N rounds
        dump = self.flight[idx].dump()
        self.flight_dumps[idx] = dump
        log.warning(
            "evicting replica %d (%s): streams down %s; flight recorder "
            "holds %d round(s)",
            idx, getattr(replica, "flavor", "local"), lost, len(dump),
        )
        for row in dump[-8:]:
            log.warning("  flight[replica %d]: %s", idx, row)
        telemetry.count("router_evictions_total")
        replica.close()
        if self.faults.respawn or self.faults.recover_streams:
            recovered = self._recover(idx, lost)
            lost = [d for d in lost if d not in recovered]
        for d in lost:
            self._shed(d)
        if not self.alive:
            raise RuntimeError(
                f"all {len(self.replicas)} replicas evicted; cluster has no capacity"
            )

    def _shed(self, dev: int) -> None:
        """Give up on one stream: record the loss and drop its shadows."""
        self.lost_devices.append(dev)
        self.shed_streams += 1
        for shadow in (
            self._prompts, self._admit_now, self._committed,
            self._last_submit, self._round_seq, self._last_k,
        ):
            shadow.pop(dev, None)
        telemetry.count("router_shed_streams_total")

    # -- recovery: respawn + device replay ------------------------------------

    def _recover(self, idx: int, lost: List[int]) -> Set[int]:
        """Post-eviction recovery: revive the dead replica (policy
        permitting), then re-place each lost stream by device replay.
        Returns the devices that made it back."""
        p = self.faults
        if p.respawn:
            self._try_revive(idx)
        if not self.alive:
            if p.respawn:
                self._revive_until_deadline()  # raises when the fleet is gone
            else:
                return set()
        if not p.recover_streams or not lost:
            return set()
        recovered: Set[int] = set()
        with telemetry.span("router_recovery", "router_recovery_seconds"):
            for dev in lost:
                if self._readmit(dev):
                    recovered.add(dev)
                    self.recovered_streams += 1
                    telemetry.count("router_recovered_streams_total")
                else:
                    log.warning("device %d could not be re-placed; shedding", dev)
        log.info(
            "recovered %d/%d stream(s) after evicting replica %d",
            len(recovered), len(lost), idx,
        )
        return recovered

    def _readmit(self, dev: int) -> bool:
        """Re-place one orphaned stream by DEVICE REPLAY: admit the original
        prompt, force_extend the committed history in runs of <= k_max+1
        (the engine's fallback-run ceiling), then re-submit the round that
        was in flight.  The rebuilt engine state matches the fault-free
        stream exactly, so greedy continuation is token-identical."""
        prompt = self._prompts.get(dev)
        if prompt is None:
            return False
        committed = list(self._committed.get(dev, ()))
        stream = self.admit(dev, prompt, self._admit_now.get(dev, 0.0))
        if stream is None:
            return False  # every surviving pool is full: shed
        run = self.k_max + 1
        try:
            idx = self._where[dev]
            for i in range(0, len(committed), run):
                chunk = np.asarray(committed[i : i + run], np.int32)
                with self._guard(idx):
                    self.replicas[idx].force_extend(dev, chunk)
            pending = self._last_submit.get(dev)
            if pending is not None:
                tokens, t_sub, draft_q = pending
                with self._guard(self._where[dev]):
                    self.replicas[self._where[dev]].submit(
                        dev, tokens, t_sub, draft_q=draft_q
                    )
        except ConnectionError:
            # the target died mid-replay; ITS eviction recursed into
            # recovery, so the stream is either fully re-placed or lost
            return dev in self._where
        return True

    def _try_revive(self, idx: int, *, wait: bool = True) -> bool:
        """One supervised revive attempt: seeded-jitter backoff (skipped on
        the periodic-redial path, which is paced by ``redial_interval_s``),
        a ``max_respawns`` budget, and the replica's own revive() doing the
        respawn-or-redial + re-place + re-warmup."""
        replica = self.replicas[idx]
        if not replica.dead:
            return True
        if not getattr(replica, "can_revive", lambda: False)():
            return False
        p = self.faults
        n = self._respawn_count.get(idx, 0)
        if n >= p.max_respawns:
            return False
        bo = self._backoff.get(idx)
        if bo is None:
            bo = self._backoff[idx] = Backoff(
                p.backoff_base_s, p.backoff_max_s, p.backoff_jitter, seed=idx
            )
        if wait:
            time.sleep(bo.attempt())
        self._respawn_count[idx] = n + 1
        try:
            with telemetry.span("router_respawn", "router_respawn_seconds"):
                replica.revive()
        except Exception as e:
            log.warning(
                "revive of replica %d failed (attempt %d/%d): %s",
                idx, n + 1, p.max_respawns, e,
            )
            return False
        bo.reset()
        self.respawns += 1
        telemetry.count("router_respawns_total")
        log.info("replica %d revived (respawn %d/%d)", idx, n + 1, p.max_respawns)
        return True

    def _revive_until_deadline(self) -> None:
        """Every replica is dead but respawn is on: keep trying to bring one
        back until ``all_dead_deadline_s`` runs out, then raise."""
        p = self.faults
        deadline = time.monotonic() + p.all_dead_deadline_s
        while time.monotonic() < deadline:
            eligible = [
                i
                for i, r in enumerate(self.replicas)
                if r.dead
                and getattr(r, "can_revive", lambda: False)()
                and self._respawn_count.get(i, 0) < p.max_respawns
            ]
            if not eligible:
                break
            for i in eligible:
                if self._try_revive(i):
                    return
        raise RuntimeError(
            f"all {len(self.replicas)} replicas evicted and none revived within "
            f"{p.all_dead_deadline_s:.1f}s; cluster has no capacity"
        )

    def _maybe_redial(self) -> None:
        """Step-loop supervision tick: periodically retry dead replicas that
        can come back (dial-only peers whose partition may have healed,
        spawned workers under their respawn budget)."""
        if not self.faults.respawn:
            return
        t = time.monotonic()
        for i, r in enumerate(self.replicas):
            if not r.dead:
                continue
            if not getattr(r, "can_revive", lambda: False)():
                continue
            if self._respawn_count.get(i, 0) >= self.faults.max_respawns:
                continue
            if t < self._redial_at.get(i, 0.0):
                continue
            self._redial_at[i] = t + self.faults.redial_interval_s
            self._try_revive(i, wait=False)

    def _check_suspects(self) -> None:
        """Evict replicas the heartbeat monitor marked suspect (they stopped
        answering Pings); eviction runs the normal recovery path."""
        for i, r in enumerate(self.replicas):
            if not r.dead and getattr(r, "suspect", False):
                log.warning("replica %d failed heartbeat; evicting", i)
                self._evict(i)

    def _guard(self, idx: int):
        """Context for one replica RPC: ReplicaGone -> evict, re-raised so
        the caller can decide whether the operation is retryable."""
        return _EvictOnGone(self, idx)

    # -- admission as placement ----------------------------------------------

    def admit(self, device_id: int, prompt: jax.Array, now: float = 0.0) -> Optional[DeviceStream]:
        """Place the stream on a replica chosen by the policy; None when
        every live replica's pool is full (caller queues and retries on
        retire).  Admission IS retried after an eviction — the worker dying
        before acking means the stream was never placed anywhere."""
        if device_id in self._where:
            raise ValueError(f"device {device_id} already admitted")
        while True:
            idx = self.placement.choose(self, device_id)
            if idx is None:
                return None
            try:
                with telemetry.span("router_place", "router_place_seconds"):
                    stream = self.replicas[idx].admit(device_id, prompt, now)
            except ConnectionError:
                self._evict(idx)
                continue  # re-place on the survivors
            if stream is None:  # policy raced a concurrent admit; treat as full
                return None
            self._where[device_id] = idx
            self._prompts[device_id] = np.asarray(prompt, np.int32).reshape(-1)
            self._admit_now[device_id] = now
            self._committed.setdefault(device_id, [])
            log.info(
                "placed device %d on replica %d (%s, %d free slot(s) left)",
                device_id, idx, self.replicas[idx].flavor, self.replicas[idx].n_free,
            )
            return stream

    def retire(self, device_id: int) -> DeviceStream:
        idx = self._where.pop(device_id)
        for shadow in (
            self._round_seq, self._last_k, self._prompts,
            self._admit_now, self._committed, self._last_submit,
        ):
            shadow.pop(device_id, None)
        with self._guard(idx):
            stream = self.replicas[idx].retire(device_id)
        if self.migrate_on_retire:
            self._rebalance_into(idx)
        return stream

    def migrate(self, device_id: int, dst: int) -> None:
        """Move a quiescent stream to replica ``dst`` bit-identically: the
        KV row is copied exactly between same-flavor replicas with matching
        fingerprints, so the stream's future tokens are unchanged — only its
        batch-mates are.  Local->local moves share params by object; a
        remote->remote move is valid because both workers rebuilt params
        from the same spec seed.  Mixed flavors raise MigrationError."""
        src = self._where[device_id]
        if src == dst:
            return
        src_r, dst_r = self.replicas[src], self.replicas[dst]
        if dst_r.dead:
            raise MigrationError(f"replica {dst} was evicted; cannot migrate into it")
        if src_r.flavor != dst_r.flavor:
            raise MigrationError(
                f"cannot migrate device {device_id} from {src_r.flavor} replica "
                f"{src} to {dst_r.flavor} replica {dst}: parameters on the two "
                f"sides have different provenance (in-process object vs worker "
                f"spec-seed rebuild), so bit-identity across the move cannot be "
                f"guaranteed"
            )
        if src_r.fingerprint != dst_r.fingerprint:
            raise MigrationError(
                f"replica fingerprints differ ({src_r.fingerprint} vs "
                f"{dst_r.fingerprint}); migration would change the stream's tokens"
            )
        with telemetry.span("router_migrate", "router_migrate_seconds"):
            with self._guard(src):
                stream, row = src_r.export_stream(device_id)
            try:
                with self._guard(dst):
                    dst_r.import_stream(stream, row)
            except ConnectionError:
                # dst died mid-import: put the stream back where it came from
                src_r.import_stream(stream, row)
                self._where[device_id] = src
                raise
            except Exception:
                # roll back: the stream must never be lost mid-migration
                src_r.import_stream(stream, row)
                raise
        self._where[device_id] = dst
        self.migrations += 1
        telemetry.count("router_migrations_total")
        log.info("migrated device %d: replica %d -> %d", device_id, src, dst)

    def _rebalance_into(self, dst: int) -> None:
        """After a retirement freed a slot on ``dst``: pull one quiescent
        SAME-FLAVOR stream over from the most-loaded replica when the
        imbalance is ≥2 (moving one stream then strictly improves balance)."""
        dst_r = self.replicas[dst]
        if dst_r.dead or dst_r.n_free == 0:
            return
        loads = self.loads()
        candidates = [
            i
            for i, r in enumerate(self.replicas)
            if i != dst and not r.dead and r.flavor == dst_r.flavor
        ]
        if not candidates:
            return
        src = max(candidates, key=lambda i: (loads[i], -i))
        if loads[src] - loads[dst] < 2:
            return
        replica = self.replicas[src]
        movable = [d for d in replica.streams if not replica.has_inflight(d)]
        if not movable:
            return
        self.migrate(movable[0], dst)

    # -- request path (delegated via placement map) --------------------------

    def submit(
        self,
        device_id: int,
        draft_tokens: np.ndarray,
        now: float,
        draft_q: Optional[np.ndarray] = None,
    ) -> None:
        tokens = np.asarray(draft_tokens)
        self._last_k[device_id] = int(tokens.shape[0])
        self._last_submit[device_id] = (tokens, now, draft_q)
        idx = self._where[device_id]
        try:
            self.replicas[idx].submit(device_id, tokens, now, draft_q=draft_q)
        except ConnectionError:
            self._evict(idx)
            if device_id not in self._where:
                raise  # the stream was shed with the replica
            # recovery re-placed the stream AND re-submitted this round (it
            # was already in _last_submit), so the caller's submit succeeded

    def cancel_request(self, device_id: int) -> bool:
        idx = self._where[device_id]
        try:
            ok = self.replicas[idx].cancel_request(device_id)
        except ConnectionError:
            self._evict(idx)
            if device_id not in self._where:
                raise
            # recovered elsewhere (pending round re-submitted); re-cancel it
            with self._guard(self._where[device_id]):
                ok = self.replicas[self._where[device_id]].cancel_request(device_id)
        if ok:
            self._last_submit.pop(device_id, None)
        return ok

    def force_extend(self, device_id: int, tokens: np.ndarray) -> int:
        toks = np.asarray(tokens, np.int32).reshape(-1)
        idx = self._where[device_id]
        try:
            prev = self.replicas[idx].force_extend(device_id, toks)
        except ConnectionError:
            self._evict(idx)
            if device_id not in self._where:
                raise
            # recovered (committed shadow did NOT include these tokens, so
            # the replay stopped short of them); apply them on the new home
            with self._guard(self._where[device_id]):
                prev = self.replicas[self._where[device_id]].force_extend(
                    device_id, toks
                )
        self._committed.setdefault(device_id, []).extend(int(t) for t in toks)
        return prev

    def has_inflight(self, device_id: int) -> bool:
        return device_id in self._where and self._replica(device_id).has_inflight(device_id)

    def next_event_hint(self, now: float) -> Optional[float]:
        hints = [h for r in self.alive if (h := r.next_event_hint(now)) is not None]
        return min(hints) if hints else None

    # -- the serving hot loop ------------------------------------------------

    def step(self, now: float) -> Optional[List[Verdict]]:
        """Step every replica whose policy fires; one merged verdict list.

        Local replicas step back to back in this process (they contend for
        the same accelerator anyway); REMOTE replicas are stepped
        concurrently on a thread pool — each RPC blocks only on its worker's
        verification, so N workers verify in parallel and admitted-stream
        capacity scales with processes.  Verdicts merge in replica order
        regardless of completion order, and each verdict's queue-depth
        feedback stays replica-local — that is the congestion signal for the
        streams riding that replica.  A worker that fails mid-step is
        evicted and the surviving replicas' verdicts are still returned.

        This is also the supervision tick: the chaos schedule fires against
        the step counter, suspect (heartbeat-silent) replicas are evicted,
        and dead replicas get their periodic redial attempt.
        """
        self.steps_taken += 1
        if self.chaos is not None:
            self.chaos.on_step(self.steps_taken)
        if self._hb is None and self.faults.heartbeat_interval_s > 0:
            self._hb = _HeartbeatMonitor(self, self.faults)
            self._hb.start()
        self._check_suspects()
        self._maybe_redial()
        remote_idx = [
            i
            for i, r in enumerate(self.replicas)
            if not r.dead and r.flavor == "remote"
        ]
        futures = {}
        with telemetry.span("router_step", "router_step_seconds"):
            if len(remote_idx) > 1:
                if self._pool is None:
                    self._pool = ThreadPoolExecutor(
                        max_workers=len(self.replicas), thread_name_prefix="router-step"
                    )
                futures = {
                    i: self._pool.submit(self.replicas[i].step, now) for i in remote_idx
                }
            results: Dict[int, Optional[List[Verdict]]] = {}
            failed: List[int] = []
            for i, replica in enumerate(self.replicas):
                if replica.dead or i in futures:
                    continue
                try:
                    results[i] = replica.step(now)
                except ConnectionError:
                    failed.append(i)
            for i, fut in futures.items():
                try:
                    results[i] = fut.result()
                except ConnectionError:
                    failed.append(i)
            # evictions run AFTER every step future resolved: recovery may
            # re-admit streams onto surviving replicas, and their control
            # channels must be idle first (they are not thread-safe)
            for i in failed:
                self._evict(i)
        verdicts: List[Verdict] = []
        for i in sorted(results):
            out = results[i]
            if not out:
                continue
            ring = self.flight[i]
            for v in out:
                # shadow ring: recorded unconditionally (a deque append per
                # verdict) so eviction post-mortems exist even when metrics
                # collection is off
                seq = self._round_seq.get(v.device_id, 0)
                self._round_seq[v.device_id] = seq + 1
                ring.record(
                    telemetry.TraceEvent(
                        device_id=v.device_id,
                        round=seq,
                        t=now,
                        k=self._last_k.get(v.device_id, 0),
                        n_accepted=v.n_accepted,
                        n_commit=len(v.tokens),
                        queue_s=v.queue_s,
                        verify_s=v.verify_s,
                        replica=i,
                    )
                )
                # device-replay shadow: the delivered verdict's tokens are
                # committed history now, and its round is no longer in flight
                if len(v.tokens):
                    self._committed.setdefault(v.device_id, []).extend(
                        int(t) for t in v.tokens
                    )
                self._last_submit.pop(v.device_id, None)
            verdicts.extend(out)
        return verdicts or None

    def warmup(self, buckets=None) -> Dict[int, float]:
        """Warm one local replica per device (in-process replicas share a
        VerifySteps bundle, and a compiled executable serves every replica
        on its device) plus EVERY remote replica — each worker process has
        its own compile cache, and an un-warmed worker would pay XLA
        compilation inside its first timed step."""
        out: Dict[int, float] = {}
        warmed_devices = set()
        for r in self.alive:
            if r.flavor == "local":
                if r.device in warmed_devices:
                    continue
                warmed_devices.add(r.device)
            secs = r.warmup(buckets)
            for k, v in secs.items():
                out[k] = max(out.get(k, 0.0), v)
        return out

    def drain(self) -> None:
        """Ask every remote worker to exit (reaping spawned processes);
        local replicas are no-ops.  Idempotent."""
        if self._hb is not None:
            self._hb.stop()
            self._hb = None
        for r in self.replicas:
            if not r.dead:
                r.drain()
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None

    # -- stats ---------------------------------------------------------------

    def stats(self, now: Optional[float] = None) -> EngineStats:
        return EngineStats.merge(self.replica_stats(now))

    def replica_stats(self, now: Optional[float] = None) -> List[EngineStats]:
        out = []
        for i, r in enumerate(self.replicas):
            if r.dead:
                continue
            try:
                out.append(r.stats(now))
            except ConnectionError:
                self._evict(i)
        return out

    def telemetry_payload(self) -> dict:
        """Cluster-level telemetry record, same keys as the single-engine
        ``ServerEngine.telemetry_payload``: this process's metrics snapshot
        plus the shadow flight rings (flattened, each event tagged with its
        replica), with per-remote worker payloads and eviction dumps
        attached when present."""
        if not telemetry.enabled():
            return {}
        flight = [ev.to_json() for ring in self.flight.values() for ev in ring.events()]
        flight.sort(key=lambda e: e["t"])
        out = {"snapshot": telemetry.registry().snapshot(), "flight": flight}
        # per-replica pool capacity: local replicas read their pool directly;
        # remote workers ship engine_kv_pool_bytes / engine_bytes_per_slot
        # gauges inside their own telemetry snapshot (ReplicaStats payload)
        pools = {}
        for i, r in enumerate(self.replicas):
            if r.dead:
                continue
            eng = getattr(r, "engine", None)
            if eng is not None:
                pools[str(i)] = {
                    "kv_dtype": eng.kv_dtype,
                    "kv_pool_bytes": eng.pool.pool_bytes(),
                    "bytes_per_slot": eng.pool.bytes_per_slot(),
                }
            else:
                snap = (getattr(r, "last_telemetry", None) or {}).get("snapshot") or {}
                g = snap.get("gauges", {})
                if "engine_kv_pool_bytes" in g:
                    spec = getattr(r, "spec", None)
                    pools[str(i)] = {
                        "kv_dtype": getattr(spec, "kv_dtype", "bf16"),
                        "kv_pool_bytes": int(g["engine_kv_pool_bytes"]),
                        "bytes_per_slot": int(g.get("engine_bytes_per_slot", 0)),
                    }
        if pools:
            out["pools"] = pools
        workers = {}
        for i, r in enumerate(self.replicas):
            try:
                payload = getattr(r, "last_telemetry", None)
            except ConnectionError:  # chaos-killed local: nothing to report
                payload = None
            if payload:
                workers[str(i)] = payload
        if workers:
            out["workers"] = workers
        if self.flight_dumps:
            out["evicted"] = {str(i): d for i, d in self.flight_dumps.items()}
        if self.evictions or self.respawns or self.shed_streams:
            out["supervision"] = {
                "evictions": self.evictions,
                "respawns": self.respawns,
                "recovered_streams": self.recovered_streams,
                "shed_streams": self.shed_streams,
                "lost_devices": list(self.lost_devices),
            }
        return out


class _HeartbeatMonitor(threading.Thread):
    """Background Ping loop over every remote replica's dedicated heartbeat
    channel: ``heartbeat_misses`` consecutive unanswered Pings mark the
    replica ``suspect``, and the Router evicts suspects at the top of its
    next step — a partitioned or SIGSTOPped worker is detected in seconds
    instead of waiting out the 120 s control-RPC timeout.  Replicas without
    a ``ping`` method (locals) are skipped."""

    def __init__(self, router: Router, policy: Any):
        super().__init__(daemon=True, name="router-heartbeat")
        self.router = router
        self.policy = policy
        self.misses: Dict[int, int] = {}
        self._stopped = threading.Event()

    def stop(self) -> None:
        self._stopped.set()

    def run(self) -> None:
        while not self._stopped.wait(self.policy.heartbeat_interval_s):
            self.sweep()

    def sweep(self) -> None:
        """One pass over the fleet (separated from run() for tests)."""
        for i, r in enumerate(self.router.replicas):
            ping = getattr(r, "ping", None)
            if r.dead or getattr(r, "suspect", False) or ping is None:
                continue
            try:
                ok = ping(timeout=self.policy.heartbeat_timeout_s)
            except Exception:
                ok = False
            if ok:
                self.misses[i] = 0
                continue
            self.misses[i] = self.misses.get(i, 0) + 1
            if self.misses[i] >= self.policy.heartbeat_misses:
                log.warning(
                    "replica %d missed %d consecutive heartbeat(s); marking suspect",
                    i, self.misses[i],
                )
                r.suspect = True
                self.misses[i] = 0


class _EvictOnGone:
    """``with router._guard(idx):`` — evict replica ``idx`` if the body dies
    with a transport failure (ReplicaGone is a ConnectionError), then
    re-raise so the caller sees the loss."""

    def __init__(self, router: Router, idx: int):
        self.router = router
        self.idx = idx

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None and issubclass(exc_type, ConnectionError):
            self.router._evict(self.idx)
        return False
