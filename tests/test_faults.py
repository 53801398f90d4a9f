"""Fault tolerance: supervised respawn, device-replay recovery, chaos.

Fast tier only — worker "processes" are WorkerCores behind fake in-process
channels (full codec encode/decode, no sockets), and local-replica chaos
goes through the same evict/recover machinery, so the PR's acceptance pair
runs in seconds:

  * with respawn + stream recovery enabled, killing 1 of 2 replicas
    mid-serve completes every session GREEDY-TOKEN-IDENTICAL to the
    fault-free run, with zero shed streams;
  * with recovery disabled, the same seeded kill schedule reproduces the
    evict-only behavior: the dead replica's streams land in
    ``lost_devices`` and their sessions end shed, not hung.

The real-subprocess variant (SIGKILL of a spawned ``repro worker``) rides
the slow tier in this file.
"""

import dataclasses

import numpy as np
import pytest

from repro.api import (
    ClusterSpec,
    FaultPolicy,
    FaultSpec,
    ModelSpec,
    SchedulerSpec,
    ServeSpec,
    System,
    build_models,
)
from repro.cluster import (
    Backoff,
    ChaosInjector,
    FaultyChannel,
    RemoteReplica,
    ReplicaGone,
    Router,
)
from repro.cluster.router import _HeartbeatMonitor
from repro.core.server_engine import ServerEngine
from repro.transport import codec
from repro.transport.worker import WorkerCore

V = 64


def _spec(**kw) -> ServeSpec:
    base = dict(
        backend="cluster",
        model=ModelSpec(vocab_size=V, target_layers=2, draft_layers=1, draft_noise=0.03),
        cluster=ClusterSpec(replicas=2),
        scheduler=SchedulerSpec(slots=2, stagger_ticks=1),
        devices=4,
        prompt_len=6,
        max_new=6,
        k_max=3,
        c_th=0.3,
    )
    base.update(kw)
    return ServeSpec(**base)


class FakeChannel:
    """ControlChannel stand-in: every RPC rides the full codec encode ->
    WorkerCore.handle -> decode path; ``killed`` fails like a dead peer."""

    def __init__(self, core=None):
        self.core = core or WorkerCore()
        self.address = "fake:0"
        self.killed = False
        self.connected = True
        self._seq = 0

    def next_seq(self):
        self._seq += 1
        return self._seq

    def request(self, msg, *, timeout=None):
        if self.killed:
            raise ReplicaGone("worker killed (fake)")
        wire, _ = codec.decode_frame(codec.encode_frame(msg))
        reply, _ = codec.decode_frame(codec.encode_frame(self.core.handle(wire)))
        if isinstance(reply, codec.ErrorReply):
            from repro.cluster import WorkerError

            raise WorkerError(reply.message)
        return reply

    def kill(self):
        self.killed = True

    def close(self):
        pass

    def connect(self):
        if self.killed:
            raise ReplicaGone("worker dead (fake)")

    def reconnect(self):
        if self.killed:
            raise ReplicaGone("worker still dead (fake)")


@pytest.fixture(scope="module")
def models():
    return build_models(_spec().model)


@pytest.fixture(scope="module")
def engine_factory(models):
    spec = _spec()
    shared = {}

    def make() -> ServerEngine:
        e = ServerEngine(
            models.target,
            models.target_params,
            n_slots=2,
            max_len=spec.max_len,
            k_max=spec.k_max,
            greedy=True,
            steps=shared.get("steps"),
        )
        shared.setdefault("steps", e.steps)
        return e

    return make


def _fake_remote(engine) -> RemoteReplica:
    remote = RemoteReplica(FakeChannel(WorkerCore(engine)))
    remote._placed = True
    remote._n_slots = engine.pool.n_slots
    remote.k_max = engine.k_max
    remote.max_len = engine.pool.max_len
    remote.greedy = engine.greedy
    return remote


# ---------------------------------------------------------------------------
# primitives: Backoff, FaultyChannel, replay cache, heartbeat
# ---------------------------------------------------------------------------


def test_backoff_deterministic_and_capped():
    a = Backoff(base_s=0.1, max_s=1.0, jitter=0.2, seed=7)
    b = Backoff(base_s=0.1, max_s=1.0, jitter=0.2, seed=7)
    seq_a = [a.attempt() for _ in range(8)]
    seq_b = [b.attempt() for _ in range(8)]
    assert seq_a == seq_b, "same seed must sleep identically (chaos repro)"
    assert all(d <= 1.0 * 1.2 + 1e-9 for d in seq_a), "cap (plus jitter) holds"
    assert 0.08 <= seq_a[0] <= 0.12
    a.reset()
    assert a.attempts == 0
    with pytest.raises(ValueError):
        Backoff(base_s=0.0)


def test_faulty_channel_drop_delay_kill(engine_factory):
    chan = FaultyChannel(FakeChannel(WorkerCore(engine_factory())))
    prompt = np.arange(6, dtype=np.int32)
    ok = chan.request(codec.AdmitRequest(device_id=0, prompt=prompt, now=0.0, seq=1))
    assert isinstance(ok, codec.AdmitReply) and ok.ok  # transparent until armed
    chan.arm_drop(2)
    for _ in range(2):
        with pytest.raises(ReplicaGone, match="chaos"):
            chan.request(codec.StepRequest(now=0.0, seq=chan.next_seq()))
    assert chan.dropped == 2 and chan.drop_n == 0
    chan.request(codec.StepRequest(now=0.0, seq=chan.next_seq()))  # healed
    chan.arm_delay(1, 0.01)
    chan.request(codec.StepRequest(now=0.0, seq=chan.next_seq()))
    assert chan.delayed == 1
    chan.kill()
    with pytest.raises(ReplicaGone):
        chan.request(codec.StepRequest(now=0.0, seq=chan.next_seq()))
    with pytest.raises(ReplicaGone):
        chan.reconnect()


def test_worker_replay_cache_dedups_resent_frames(engine_factory):
    """v4 replay protection: a resent (device, seq) side-effectful frame
    returns the ORIGINAL reply without re-applying — the worker absorbs a
    one-shot reconnect retry safely."""
    core = WorkerCore(engine_factory())
    prompt = np.arange(6, dtype=np.int32)
    first = core.handle(codec.AdmitRequest(device_id=0, prompt=prompt, now=0.0, seq=1))
    again = core.handle(codec.AdmitRequest(device_id=0, prompt=prompt, now=0.0, seq=1))
    assert core.replay_hits == 1
    assert again.slot == first.slot and len(core.engine.streams) == 1
    toks = np.asarray([1, 2, 3], np.int32)
    core.handle(codec.SubmitRequest(device_id=0, tokens=toks, now=0.1, seq=2))
    core.handle(codec.SubmitRequest(device_id=0, tokens=toks, now=0.1, seq=2))
    assert core.replay_hits == 2
    step = core.handle(codec.StepRequest(now=0.2, seq=3))
    assert len(step.verdicts) == 1, "the duplicate submit must not queue a round"
    # seq=0 frames (v3-style senders) are never cached
    assert core._replay_key(codec.StepRequest(now=0.0)) is None
    # Ping answers without touching the replay cache
    pong = core.handle(codec.Ping(seq=9, t=1.5))
    assert isinstance(pong, codec.Pong) and pong.seq == 9 and pong.t == 1.5


def test_retry_rpcs_absorbs_link_flap(engine_factory):
    """A flap (one severed RPC) is invisible to the Router when the replica
    retries over reconnect: the frame is resent with the same seq."""
    remote = _fake_remote(engine_factory())
    remote.channel = FaultyChannel(remote.channel)
    remote.retry_rpcs = True
    router = Router([remote])
    prompt = np.arange(6, dtype=np.int32)
    assert router.admit(0, prompt, 0.0) is not None
    remote.channel.flap()
    router.submit(0, np.asarray([1, 2, 3], np.int32), 0.1)  # survives the flap
    assert remote.retries == 1 and remote.channel.dropped == 1
    assert router.evictions == 0 and not remote.dead
    verdicts = router.step(0.2)
    assert verdicts is not None and verdicts[0].device_id == 0


def test_chaos_injector_fires_on_schedule(engine_factory):
    router = Router([_fake_remote(engine_factory()), _fake_remote(engine_factory())])
    spec = FaultSpec(events=(
        {"kind": "kill", "replica": 1, "round": 3},
        {"kind": "flap", "replica": 0, "round": 2},
    ))
    router.replicas[0].channel = FaultyChannel(router.replicas[0].channel)
    inj = ChaosInjector(spec, router)
    inj.on_step(1)
    assert not inj.fired and not inj.done
    inj.on_step(2)
    assert inj.fired == [(2, "flap", 0)]
    assert router.replicas[0].channel.drop_n == 1
    inj.on_step(5)  # past-due events still fire, once
    assert inj.fired[-1] == (5, "kill", 1) and inj.done
    assert router.replicas[1].channel.killed
    inj.on_step(9)
    assert len(inj.fired) == 2


def test_chaos_injector_refuses_unwrapped_channel(engine_factory):
    router = Router([_fake_remote(engine_factory())])
    inj = ChaosInjector(FaultSpec(events=({"kind": "drop", "replica": 0, "round": 1},)), router)
    with pytest.raises(RuntimeError, match="not a FaultyChannel"):
        inj.on_step(1)


def test_heartbeat_monitor_marks_suspect_then_router_evicts(engine_factory):
    class Silent:
        dead = False
        suspect = False

        def ping(self, *, timeout):
            return False

    class Fleet:
        replicas = [Silent()]

    policy = FaultPolicy(heartbeat_interval_s=0.05, heartbeat_misses=3)
    mon = _HeartbeatMonitor(Fleet(), policy)
    mon.sweep()
    mon.sweep()
    assert not Fleet.replicas[0].suspect
    mon.sweep()
    assert Fleet.replicas[0].suspect, "3 consecutive misses -> suspect"

    # a suspect replica is evicted at the next router step
    router = Router([_fake_remote(engine_factory()), _fake_remote(engine_factory())])
    router.replicas[1].suspect = True
    router.step(0.0)
    assert router.replicas[1].dead and router.evictions == 1
    assert not router.replicas[0].dead


# ---------------------------------------------------------------------------
# the acceptance pair: seeded kill mid-serve, with and without recovery
# ---------------------------------------------------------------------------


def _fake_fleet(spec, n=2):
    """Remote replicas over fake channels, engines built via PlaceReplica
    from the shipped spec (the worker path); revive() gets a fresh fake
    worker from channel_factory — an in-process respawn."""
    worker_spec = spec.with_backend(
        "engine",
        scheduler=dataclasses.replace(spec.scheduler, slots=spec.slots_per_replica),
    )
    remotes = []
    for _ in range(n):
        r = RemoteReplica(FakeChannel())
        r.place(worker_spec)
        r.channel_factory = lambda: FakeChannel()
        remotes.append(r)
    return remotes


def _kill_schedule(round_no=5):
    return FaultSpec(events=({"kind": "kill", "replica": 1, "round": round_no},))


def test_kill_with_recovery_is_token_identical(models):
    """Tentpole acceptance, fast tier: kill 1 of 2 workers mid-serve with
    respawn + device-replay recovery on -> every session completes with
    exactly the fault-free tokens, zero streams shed."""
    spec = _spec()
    inproc = System.build(spec, models=models)
    want = inproc.serve().outputs

    policy = FaultPolicy(
        respawn=True, recover_streams=True,
        backoff_base_s=0.01, backoff_max_s=0.05,
    )
    remotes = _fake_fleet(spec)
    router = Router(remotes, placement=spec.cluster.placement, faults=policy)
    router.chaos = ChaosInjector(_kill_schedule(), router)
    system = System(spec, models, router, inproc.kit)
    result = system.serve()

    assert router.chaos.done and router.evictions == 1
    assert router.respawns == 1, "the killed worker must have been respawned"
    assert router.shed_streams == 0 and result.lost_devices == []
    assert router.recovered_streams >= 1
    assert not any(s.shed for s in result.sessions)
    assert result.outputs == want, "recovery diverged from the fault-free run"


def test_kill_without_recovery_sheds_lost_streams(models):
    """Same seeded schedule, recovery off: today's behavior — the dead
    replica's streams are shed into lost_devices, their sessions end with
    an explicit rejection (committed prefix intact), survivors complete."""
    spec = _spec()
    inproc = System.build(spec, models=models)
    want = inproc.serve().outputs

    remotes = _fake_fleet(spec)
    router = Router(remotes, placement=spec.cluster.placement)  # default policy
    router.chaos = ChaosInjector(_kill_schedule(), router)
    system = System(spec, models, router, inproc.kit)
    result = system.serve()

    assert router.evictions == 1 and router.respawns == 0
    lost = sorted(result.lost_devices)
    assert lost, "killing a loaded replica with recovery off must lose streams"
    assert lost == sorted(router.lost_devices)
    by_dev = {s.device_id: s for s in result.sessions}
    for dev in lost:
        s = by_dev[dev]
        assert s.shed and len(s.tokens) < len(want[dev])
        assert want[dev][: len(s.tokens)] == s.tokens, "shed prefix must match"
    for dev, s in by_dev.items():
        if dev not in lost:
            assert not s.shed and s.tokens == want[dev]


def test_recovery_without_respawn_sheds_over_capacity(models):
    """recover_streams alone: orphans fit only the survivor's free slots —
    with both pools full at kill time, everything on the dead replica is
    shed (lost_devices shrinks exactly to the capacity overflow)."""
    spec = _spec()
    remotes = _fake_fleet(spec)
    policy = FaultPolicy(recover_streams=True)  # no respawn
    router = Router(remotes, placement=spec.cluster.placement, faults=policy)
    prompts = np.arange(4 * 6, dtype=np.int32).reshape(4, 6) % V
    for dev in range(4):
        assert router.admit(dev, prompts[dev], 0.0) is not None
    router.replicas[1].chaos_kill()
    for dev in range(4):
        if dev not in router._where:  # already shed by an earlier eviction
            continue
        try:
            router.submit(dev, np.asarray([1, 2, 3], np.int32), 0.1)
        except ConnectionError:
            pass
    router.step(0.2)
    assert router.evictions == 1
    # survivor had 0 free slots: both orphans shed, none recovered
    assert router.recovered_streams == 0 and router.shed_streams == 2
    assert sorted(router.lost_devices) == [1, 3]


# ---------------------------------------------------------------------------
# spec-driven chaos through System.build (local replicas)
# ---------------------------------------------------------------------------


def test_spec_driven_local_chaos_recovers_token_identical(models):
    spec = _spec(
        cluster=ClusterSpec(
            replicas=2,
            faults={
                "respawn": True, "recover_streams": True,
                "backoff_base_s": 0.01, "backoff_max_s": 0.05,
            },
        ),
        faults=FaultSpec(events=({"kind": "kill", "replica": 1, "round": 5},)),
    )
    want = System.build(
        dataclasses.replace(spec, faults=FaultSpec()), models=models
    ).serve().outputs

    system = System.build(spec, models=models)
    assert system.engine.chaos is not None, "FaultSpec must attach the injector"
    result = system.serve()
    assert system.engine.evictions == 1 and system.engine.respawns == 1
    assert result.lost_devices == [] and not any(s.shed for s in result.sessions)
    assert result.outputs == want


def test_spec_driven_chaos_without_recovery_surfaces_lost_devices(models):
    spec = _spec(
        faults=FaultSpec(events=({"kind": "kill", "replica": 1, "round": 5},)),
    )
    system = System.build(spec, models=models)
    result = system.serve()
    assert system.engine.evictions == 1
    assert result.lost_devices, "ServeResult must surface the shed devices"
    shed = {s.device_id for s in result.sessions if s.shed}
    assert shed == set(result.lost_devices)
    assert "lost_devices" in result.to_json()


def test_all_replicas_evicted_is_fatal_through_serve(models):
    spec = _spec(
        cluster=ClusterSpec(replicas=1),
        devices=2,
        faults=FaultSpec(events=({"kind": "kill", "replica": 0, "round": 3},)),
    )
    system = System.build(spec, models=models)
    with pytest.raises(RuntimeError, match="all 1 replicas evicted"):
        system.serve()


def test_fault_spec_json_round_trip():
    spec = _spec(
        cluster=ClusterSpec(replicas=2, faults={"respawn": True, "max_respawns": 5}),
        faults=FaultSpec(seed=3, events=(
            {"kind": "kill", "replica": 1, "round": 4},
            {"kind": "delay", "replica": 0, "round": 2, "count": 3, "delay_s": 0.5},
        )),
    )
    assert spec.cluster.faults.respawn and spec.cluster.faults.max_respawns == 5
    assert spec.faults.active and spec.faults.events[0].kind == "kill"
    assert ServeSpec.from_json(spec.to_json_str()) == spec


# ---------------------------------------------------------------------------
# real worker processes (slow tier): SIGKILL mid-serve
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_real_worker_sigkill_recovery_token_identical(models):
    """Acceptance bar on real processes: ``kill -9`` one of 2 spawned
    workers mid-serve; with respawn + recovery the run completes
    token-identical to the fault-free run, with recovery visible in
    telemetry counters and zero shed streams."""
    spec = _spec()
    want = System.build(spec, models=models).serve().outputs

    chaos_spec = dataclasses.replace(
        spec,
        cluster=ClusterSpec(
            replicas=[{"flavor": "remote"}] * 2,
            faults={
                "respawn": True, "recover_streams": True,
                "backoff_base_s": 0.05, "backoff_max_s": 0.5,
            },
        ),
        faults=FaultSpec(events=({"kind": "kill", "replica": 1, "round": 5},)),
    )
    with System.build(chaos_spec) as system:
        result = system.serve()
        router = system.engine
        assert router.evictions == 1 and router.respawns == 1
        assert router.shed_streams == 0 and result.lost_devices == []
    assert result.outputs == want, "post-SIGKILL recovery diverged"
