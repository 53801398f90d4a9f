"""Cross-process cluster: codec v3 control plane, worker dispatch, eviction.

Fast tier covers the protocol and supervision logic without subprocesses:
frame round-trips + truncation properties for every v3 control message,
bit-exact KV-row serialization (bf16 rides the wire as raw uint16 words),
per-replica ReplicaSpec validation, and — via a fake in-process channel
that routes every RPC through full encode -> WorkerCore.handle -> decode —
token identity between a Router of "remote" replicas and the in-process
cluster, worker-crash eviction, and the mixed-flavor migration guard.

Slow tier spawns REAL ``repro worker`` subprocesses on unix sockets and
holds the PR's acceptance bar: a Router dialing 2 worker processes commits
exactly the tokens the in-process cluster commits for the same ServeSpec
seed, through both the cluster and transport backends.
"""

import dataclasses
import json

import numpy as np
import pytest

import ml_dtypes

from repro.api import (
    ClusterSpec,
    ModelSpec,
    ReplicaSpec,
    SchedulerSpec,
    ServeSpec,
    SpecError,
    System,
    build_models,
)
from repro.cluster import (
    MigrationError,
    RemoteReplica,
    ReplicaGone,
    Router,
    WorkerError,
)
from repro.core.server_engine import ServerEngine
from repro.transport import codec
from repro.transport.links import parse_addr
from repro.transport.worker import WorkerCore, build_engine_from_spec

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


# ---------------------------------------------------------------------------
# codec v3: control-plane frames
# ---------------------------------------------------------------------------


def _roundtrip(msg):
    buf = codec.encode_frame(msg)
    out, used = codec.decode_frame(buf)
    assert used == len(buf)
    return out


def _eq(a, b) -> bool:
    """Structural equality that tolerates numpy fields inside dataclasses."""
    if type(a) is not type(b):
        return False
    if isinstance(a, np.ndarray):
        return a.dtype == b.dtype and a.shape == b.shape and bool(np.all(a == b))
    if dataclasses.is_dataclass(a):
        return all(
            _eq(getattr(a, f.name), getattr(b, f.name)) for f in dataclasses.fields(a)
        )
    if isinstance(a, (tuple, list)):
        return len(a) == len(b) and all(_eq(x, y) for x, y in zip(a, b))
    if isinstance(a, dict):
        return sorted(a) == sorted(b) and all(_eq(a[k], b[k]) for k in a)
    return a == b


def _sample_row():
    return {
        "layer0/k": np.arange(24, dtype=ml_dtypes.bfloat16).reshape(2, 3, 4),
        "layer0/v": np.linspace(-2, 2, 24, dtype=np.float32).reshape(2, 3, 4),
        "length": np.asarray([7], np.int32),
    }


def _sample_state():
    return codec.StreamState(
        device_id=3,
        slot=1,
        prev_token=42,
        committed=(5, 9, 1),
        admitted_at=1.25,
        rounds=4,
        drafted=12,
        accepted=9,
        row=_sample_row(),
    )


def _control_messages():
    toks = np.asarray([5, 0, V - 1, 3], np.int32)
    return [
        codec.PlaceReplica(spec_json='{"backend": "engine"}'),
        codec.PlaceAck(ok=True, n_slots=2, k_max=3, max_len=64, greedy=False),
        codec.PlaceAck(ok=False, error="no: bad spec"),
        codec.AdmitRequest(device_id=7, prompt=toks, now=0.5),
        codec.AdmitReply(device_id=7, ok=True, slot=1, prev_token=-3),
        codec.SubmitRequest(device_id=7, tokens=toks, now=1.5),
        codec.SubmitAck(device_id=7),
        codec.StepRequest(now=2.25),
        codec.StepReply(
            verdicts=(
                codec.VerdictRec(device_id=7, n_accepted=2, tokens=toks[:3],
                                 next_prev=9, accept_rate=0.5, queue_depth=1,
                                 queue_s=0.5, verify_s=0.25),
            ),
            queue_depth=1, n_free=1, hint=3.5,
        ),
        codec.StepReply(verdicts=(), queue_depth=0, n_free=2, hint=None),
        codec.RetireRequest(device_id=7),
        codec.RetireReply(stream=_sample_state()),
        codec.CancelRequest(device_id=7),
        codec.CancelReply(device_id=7, ok=False),
        codec.ForceExtendRequest(device_id=7, tokens=toks),
        codec.ForceExtendReply(device_id=7, next_prev=11),
        codec.ExportStream(device_id=7),
        codec.ExportReply(stream=_sample_state()),
        codec.ImportStream(stream=_sample_state()),
        codec.ImportAck(device_id=7, slot=0),
        codec.StatsRequest(now=9.0, has_now=True),
        codec.ReplicaStats(stats_json='{"rounds": 3}'),
        codec.ReplicaStats(stats_json='{"rounds": 3}',
                           telemetry_json='{"snapshot": {"counters": {}}}'),
        codec.WarmupRequest(),
        codec.WarmupReply(compile_json='{"4": 0.1}'),
        codec.Drain(),
        codec.DrainAck(streams_left=2),
        codec.ErrorReply(message="ValueError: boom"),
        # v4: per-RPC seq on side-effectful requests + heartbeat frames
        codec.AdmitRequest(device_id=7, prompt=toks, now=0.5, seq=12),
        codec.SubmitRequest(device_id=7, tokens=toks, now=1.5, seq=13),
        codec.StepRequest(now=2.25, seq=14),
        codec.RetireRequest(device_id=7, seq=15),
        codec.CancelRequest(device_id=7, seq=16),
        codec.ForceExtendRequest(device_id=7, tokens=toks, seq=17),
        codec.ExportStream(device_id=7, seq=18),
        codec.ImportStream(stream=_sample_state(), seq=19),
        codec.Ping(seq=20, t=1.25),
        codec.Pong(seq=20, t=1.25),
    ]


def test_codec_v3_control_roundtrip():
    for msg in _control_messages():
        out = _roundtrip(msg)
        assert _eq(out, msg), f"{type(msg).__name__} did not round-trip"


def test_codec_v3_stream_state_row_bit_exact():
    state = _roundtrip(codec.ImportStream(stream=_sample_state())).stream
    row, want = state.row, _sample_row()
    assert sorted(row) == sorted(want)
    for k in want:
        assert row[k].dtype == want[k].dtype and row[k].shape == want[k].shape
        # bit-level equality, not value closeness: bf16 must ride the wire
        # as raw words or cross-process KV rows stop being migration-safe
        np.testing.assert_array_equal(
            row[k].view(np.uint16) if row[k].dtype == ml_dtypes.bfloat16 else row[k],
            want[k].view(np.uint16) if want[k].dtype == ml_dtypes.bfloat16 else want[k],
        )


def test_codec_v3_truncation_never_yields_a_frame():
    """Every strict prefix of a valid frame reassembles to nothing (the
    decoder waits for more bytes) and never decodes to garbage."""
    for msg in _control_messages():
        buf = codec.encode_frame(msg)
        for cut in range(len(buf)):
            dec = codec.FrameDecoder()
            dec.feed(buf[:cut])
            assert dec.next_raw() is None, (type(msg).__name__, cut)
            with pytest.raises(codec.CodecError):
                codec.decode_frame(buf[:cut])


def test_codec_v3_corrupt_payload_raises_codec_error():
    """Truncating the payload while fixing up the length header must raise
    CodecError (not IndexError/struct.error) — the worker loop turns codec
    failures into protocol errors, anything else would kill the process."""
    for msg in (codec.ImportStream(stream=_sample_state()),
                codec.AdmitRequest(device_id=1, prompt=np.arange(4, dtype=np.int32))):
        buf = bytearray(codec.encode_frame(msg))
        body = buf[codec.HEADER_SIZE:][:-3]  # drop payload tail
        trimmed = bytearray(buf[: codec.HEADER_SIZE]) + body
        trimmed[4:8] = len(body).to_bytes(4, "big")
        with pytest.raises(codec.CodecError):
            codec.decode_frame(bytes(trimmed))


def test_codec_version_is_v4():
    """The runtime speaks v5 only (v4 until PlaceAck lost its paged-attention
    byte): frames carry 5, and a v4 frame is refused."""
    assert codec.VERSION == 5
    buf = bytearray(codec.encode_frame(codec.Drain()))
    assert buf[2] == 5
    buf[2] = 4
    with pytest.raises(codec.CodecError, match="version 4"):
        codec.decode_frame(bytes(buf))


# ---------------------------------------------------------------------------
# per-replica ServeSpec
# ---------------------------------------------------------------------------


def test_replica_spec_shorthand_expands():
    c = ClusterSpec(replicas=3)
    assert c.n_replicas == 3 and not c.has_remote
    assert c.replica_specs == (ReplicaSpec(), ReplicaSpec(), ReplicaSpec())


def test_replica_spec_list_round_trips():
    spec = _spec(
        cluster=ClusterSpec(
            replicas=[
                {"flavor": "remote"},
                {"flavor": "remote", "address": "uds:/tmp/w.sock", "slots": 3},
            ]
        )
    )
    assert spec.cluster.has_remote and spec.cluster.n_replicas == 2
    blob = spec.to_json_str()
    assert json.loads(blob) == spec.to_json()  # artifact-safe (lists, not tuples)
    assert ServeSpec.from_json(blob) == spec


@pytest.mark.parametrize(
    "kw",
    [
        dict(backend="engine", cluster=ClusterSpec(replicas=[{"flavor": "remote"}])),
        dict(backend="reference", cluster=ClusterSpec(replicas=[{"flavor": "remote"}])),
        dict(cluster=ClusterSpec(replicas=[{"flavor": "inproc", "address": "tcp:h:1"}])),
        dict(cluster=ClusterSpec(replicas=[])),
        dict(cluster=ClusterSpec(replicas=[{"flavor": "weird"}])),
        dict(cluster=ClusterSpec(replicas=[{"flavor": "remote", "address": "nope"}])),
        dict(cluster=ClusterSpec(replicas=[{"flavor": "remote", "slots": -1}])),
    ],
)
def test_replica_spec_invalid_combos_rejected(kw):
    with pytest.raises(SpecError):
        _spec(**kw)


def test_replica_spec_unknown_keys_rejected_at_normalization():
    with pytest.raises(SpecError, match="unknown replica keys"):
        ClusterSpec(replicas=[{"flavour": "remote"}])


def test_with_backend_resets_remote_fleet():
    spec = _spec(cluster=ClusterSpec(replicas=[{"flavor": "remote"}] * 2))
    eng = spec.with_backend("engine")
    assert eng.cluster.replicas == 1 and not eng.cluster.has_remote


def test_parse_addr_forms():
    assert parse_addr("tcp:127.0.0.1:0") == ("tcp", "127.0.0.1", 0)
    assert parse_addr("host:7001") == ("tcp", "host", 7001)
    assert parse_addr("uds:/tmp/x.sock") == ("uds", "/tmp/x.sock")
    for bad in ("uds:", "tcp:hostonly", "tcp:h:notaport", ":9"):
        with pytest.raises(ValueError):
            parse_addr(bad)


# ---------------------------------------------------------------------------
# WorkerCore over a fake wire (full dispatch, no sockets)
# ---------------------------------------------------------------------------


class FakeChannel:
    """In-process stand-in for ControlChannel: every request is ENCODED,
    decoded by the worker dispatch, and its reply encoded/decoded again —
    the whole wire path minus the socket.  ``killed`` simulates a worker
    crash (every RPC raises ReplicaGone, like a dead TCP peer)."""

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


def _fake_remote(engine=None) -> RemoteReplica:
    """RemoteReplica over a FakeChannel.  With a prebuilt ``engine`` the
    placement handshake is skipped (tests sharing one compiled VerifySteps
    bundle); fingerprint fields are adopted directly."""
    remote = RemoteReplica(FakeChannel(WorkerCore(engine)))
    if engine is not None:
        remote._placed = True
        remote._n_slots = engine.pool.n_slots
        remote.k_max = engine.k_max
        remote.max_len = engine.pool.max_len
        remote.greedy = engine.greedy
    return remote


@pytest.fixture(scope="module")
def models():
    return build_models(_spec().model)


@pytest.fixture(scope="module")
def engine_factory(models):
    """Build homogeneous engines sharing ONE compiled VerifySteps bundle."""
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


def test_remote_router_token_identical_to_inproc(models):
    """The PR's core invariant on the fast tier: a Router of remote
    replicas — every RPC through full codec v3 encode/decode and the real
    worker dispatch, engines built via PlaceReplica from a shipped spec —
    commits exactly the tokens the in-process cluster commits."""
    spec = _spec()
    inproc = System.build(spec, models=models)
    want = inproc.serve().outputs

    worker_spec = spec.with_backend(
        "engine",
        scheduler=dataclasses.replace(spec.scheduler, slots=spec.slots_per_replica),
    )
    remotes = []
    for _ in range(2):
        r = RemoteReplica(FakeChannel())
        r.place(worker_spec)  # builds the worker engine from the spec JSON
        remotes.append(r)
    router = Router(
        remotes,
        placement=spec.cluster.placement,
        migrate_on_retire=spec.cluster.migrate_on_retire,
    )
    system = System(spec, models, router, inproc.kit)
    got = system.serve().outputs
    assert got == want, "remote replicas diverged from the in-process cluster"
    assert router.migrations >= 0 and router.evictions == 0


def test_worker_crash_evicts_and_redistributes(engine_factory):
    router = Router([_fake_remote(engine_factory()), _fake_remote(engine_factory())])
    prompts = np.arange(4 * 6, dtype=np.int32).reshape(4, 6) % V
    for dev in range(4):  # least-loaded: 0,2 -> replica 0; 1,3 -> replica 1
        assert router.admit(dev, prompts[dev], 0.0) is not None
    assert router.loads() == [2, 2]
    for dev in range(4):
        router.submit(dev, np.asarray([1, 2, 3], np.int32), 0.1)

    router.replicas[1].channel.killed = True
    verdicts = router.step(0.2)  # replica 1 dies mid-step: evicted, not fatal

    assert router.evictions == 1
    assert router.replicas[1].dead and not router.replicas[0].dead
    assert sorted(router.lost_devices) == [1, 3]
    assert {v.device_id for v in verdicts} == {0, 2}  # survivors still served
    assert 1 not in router.streams and 3 not in router.streams

    # retire a survivor, then redistribution: new admissions land on the
    # live replica only
    router.retire(0)
    stream = router.admit(9, prompts[1], 1.0)
    assert stream is not None and router.replica_of(9) == 0
    # stats skip the dead replica instead of dialing a corpse
    st = router.stats(1.0)
    assert st.replicas == 1


def test_all_replicas_dead_is_fatal(engine_factory):
    router = Router([_fake_remote(engine_factory())])
    router.replicas[0].channel.killed = True
    with pytest.raises((RuntimeError, ConnectionError)):
        router.admit(0, np.zeros(6, np.int32), 0.0)


def test_mixed_flavor_migration_rejected(engine_factory):
    local = engine_factory()
    router = Router([local, _fake_remote(engine_factory())])
    assert router.replicas[0].flavor == "local"
    assert router.replicas[1].flavor == "remote"
    prompt = np.arange(6, dtype=np.int32)
    router.admit(0, prompt, 0.0)
    assert router.replica_of(0) == 0
    with pytest.raises(MigrationError, match="provenance"):
        router.migrate(0, 1)
    # the stream survived the refusal, untouched
    assert router.replica_of(0) == 0 and 0 in router.streams


def test_remote_to_remote_migration_over_frames(engine_factory):
    """Satellite 3: migration between remote replicas rides the
    ExportStream/ImportStream frames and preserves the stream record."""
    router = Router([_fake_remote(engine_factory()), _fake_remote(engine_factory())])
    prompt = np.arange(6, dtype=np.int32)
    stream = router.admit(0, prompt, 0.0)
    before = (stream.prev_token, list(stream.committed))
    router.migrate(0, 1)
    assert router.replica_of(0) == 1 and router.migrations == 1
    moved = router.streams[0]
    assert (moved.prev_token, list(moved.committed)) == before
    # the destination WORKER holds the stream now, not just the shadow
    assert 0 in router.replicas[1].channel.core.engine.streams
    assert 0 not in router.replicas[0].channel.core.engine.streams


def test_worker_error_is_not_eviction(engine_factory):
    """An engine-level rejection (ErrorReply) must surface as WorkerError
    and leave the replica alive — only transport failures evict."""
    remote = _fake_remote(engine_factory())
    router = Router([remote])
    with pytest.raises(WorkerError, match="KeyError"):
        remote.retire(99)  # no such stream: the worker says so, politely
    assert not remote.dead and router.evictions == 0


def test_worker_core_place_rejects_double_place(engine_factory):
    core = WorkerCore(engine_factory())
    ack = core.handle(codec.PlaceReplica('{"backend": "engine"}'))
    assert isinstance(ack, codec.PlaceAck) and not ack.ok
    assert "already" in ack.error


def test_worker_core_requires_engine():
    reply = WorkerCore().handle(codec.StepRequest(now=0.0))
    assert isinstance(reply, codec.ErrorReply)
    assert "PlaceReplica" in reply.message


def test_build_engine_from_spec_forces_engine_backend():
    spec = _spec()  # backend=cluster, replicas=2
    engine = build_engine_from_spec(spec)
    assert isinstance(engine, ServerEngine)
    assert engine.pool.n_slots == spec.slots_per_replica


def test_spawn_worker_refuses_when_parent_holds_accelerator(monkeypatch):
    """A chip belongs to one process: once this process holds a TPU, a
    spawned worker could never reach it, so spawn_worker refuses at once
    instead of waiting out its startup probe."""
    import subprocess

    import jax
    from jax._src import xla_bridge

    from repro.cluster import remote

    monkeypatch.setattr(xla_bridge, "backends_are_initialized", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def no_spawn(*a, **kw):
        raise AssertionError("spawn_worker started a process")

    monkeypatch.setattr(subprocess, "Popen", no_spawn)
    assert remote.held_accelerator() == "tpu"
    with pytest.raises(RuntimeError, match="holds the tpu backend"):
        remote.spawn_worker()
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert remote.held_accelerator() is None


# ---------------------------------------------------------------------------
# real worker processes (slow tier)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_spawned_workers_token_identical_across_backends(models):
    """Acceptance bar: 2 REAL ``repro worker`` processes on unix sockets,
    spawned + placed by System.build, commit token-identical streams to the
    in-process cluster for the same spec seed — via the cluster backend's
    in-process pump AND via the transport backend's wire runtime."""
    spec = _spec()
    want = System.build(spec, models=models).serve().outputs

    remote_cluster = dataclasses.replace(
        spec, cluster=ClusterSpec(replicas=[{"flavor": "remote"}] * 2)
    )
    with System.build(remote_cluster) as system:
        assert [r.flavor for r in system.engine.replicas] == ["remote", "remote"]
        got = system.serve().outputs
    assert got == want, "cross-process cluster diverged from in-process"

    remote_transport = remote_cluster.with_backend(
        "transport", cluster=remote_cluster.cluster
    )
    with System.build(remote_transport) as system:
        got = system.serve().outputs
    assert got == want, "transport over worker processes diverged"
