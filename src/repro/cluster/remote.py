"""RemoteReplica: the Router's proxy for a worker process on the far side
of a socket.

The Router (cluster/router.py) drives every replica through one synchronous
surface — admit / submit / step / retire / export / import / stats / warmup.
A :class:`RemoteReplica` implements that surface by proxying each call over
codec v3 control frames on a blocking :class:`ControlChannel` (plain socket
+ FrameDecoder; the Router stays synchronous, and concurrency across
workers comes from the Router stepping its remotes on a thread pool).

Client-side SHADOW state keeps the hot paths local: the replica mirrors
each stream's server-side record (slot, prev token, committed tokens,
lifetime counters) from admit/verdict/retire traffic, so placement
decisions (``n_free``, ``streams``, ``has_inflight``) never pay a round
trip — only actual engine work (admit's prefill, step's verification,
migration's row copy) crosses the wire.

Supervision is reconnect-or-evict: a transport failure on a SIDE-EFFECT-FREE
RPC (stats) is retried once over a fresh connection.  Side-effectful RPCs
(admit / submit / step / retire / migration) carry a codec-v4 per-channel
``seq``, so when the Router's :class:`~repro.api.spec.FaultPolicy` enables
``retry_rpcs`` they too get ONE reconnect-and-resend — the worker's replay
cache returns the original reply if the first copy landed, so the retry can
never double-apply a round.  A failure that survives the retry raises
:class:`ReplicaGone` and the Router evicts (and, policy permitting,
revives) the replica.  A worker-side handler error arrives as an ErrorReply
and raises :class:`WorkerError` (the worker is alive; the request was just
invalid).
"""

from __future__ import annotations

import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time
import uuid
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.core.admission import DeviceStream
from repro.core.engine import EngineStats, Verdict
from repro.transport import codec
from repro.transport.links import parse_addr

DEFAULT_TIMEOUT = 120.0  # control RPCs; crash shows up as EOF, not timeout
WARMUP_TIMEOUT = 900.0  # warmup compiles every verify bucket


class ReplicaGone(ConnectionError):
    """The worker is unreachable (crash, kill, network partition)."""


class WorkerError(ValueError):
    """The worker handled the request and rejected it (engine-level error)."""


class ControlChannel:
    """Blocking request/reply frame channel to one worker (TCP or UDS)."""

    def __init__(self, address: str, *, timeout: float = DEFAULT_TIMEOUT):
        self.address = address
        self.timeout = timeout
        self._sock: Optional[socket.socket] = None
        self._decoder = codec.FrameDecoder()
        self._seq = 0  # per-channel RPC seq (v4 replay keys); 0 = unused

    def next_seq(self) -> int:
        """Monotonic non-zero seq for side-effectful RPCs.  Survives
        reconnects of THIS channel (the worker's replay cache is keyed by
        it); a respawned worker gets a fresh channel and a fresh count."""
        self._seq += 1
        return self._seq

    def connect(self) -> None:
        parsed = parse_addr(self.address)
        try:
            if parsed[0] == "uds":
                sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                sock.settimeout(self.timeout)
                sock.connect(parsed[1])
            else:
                sock = socket.create_connection(
                    (parsed[1], parsed[2]), timeout=self.timeout
                )
        except OSError as e:
            raise ReplicaGone(f"cannot dial worker at {self.address}: {e}") from e
        self._sock = sock
        self._decoder = codec.FrameDecoder()

    @property
    def connected(self) -> bool:
        return self._sock is not None

    def close(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            finally:
                self._sock = None

    def reconnect(self) -> None:
        self.close()
        self.connect()

    def request(self, msg: codec.Message, *, timeout: Optional[float] = None):
        """Send one frame, block for its reply.  ErrorReply -> WorkerError;
        any transport failure -> ReplicaGone (this channel is closed)."""
        if self._sock is None:
            self.connect()
        sock = self._sock
        try:
            if timeout is not None:
                sock.settimeout(timeout)
            sock.sendall(codec.encode_frame(msg))
            while True:
                raw = self._decoder.next_raw()
                if raw is not None:
                    break
                data = sock.recv(65536)
                if not data:
                    raise ReplicaGone(
                        f"worker at {self.address} closed the control connection"
                    )
                self._decoder.feed(data)
        except ReplicaGone:
            self.close()
            raise
        except (OSError, codec.CodecError) as e:
            self.close()
            raise ReplicaGone(f"worker at {self.address} failed: {e}") from e
        finally:
            if timeout is not None and self._sock is not None:
                self._sock.settimeout(self.timeout)
        reply, _ = codec.decode_frame(raw)
        if isinstance(reply, codec.ErrorReply):
            raise WorkerError(reply.message)
        return reply


def repro_python_env() -> dict:
    """Env for a spawned worker: this interpreter's repro must be importable
    even when the parent runs from a source tree via PYTHONPATH=src."""
    import repro

    env = dict(os.environ)
    pkg_dir = (  # namespace packages have __file__=None; __path__ still points in
        os.path.dirname(repro.__file__) if getattr(repro, "__file__", None)
        else list(repro.__path__)[0]
    )
    src_root = os.path.dirname(os.path.abspath(pkg_dir))
    parts = [src_root] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    env["PYTHONPATH"] = os.pathsep.join(dict.fromkeys(parts))
    return env


def worker_sock_dir(address: str) -> Optional[str]:
    """The private ``repro-worker-*`` temp dir behind a spawned worker's UDS
    address, or None when the address is not one of ours."""
    if not address.startswith("uds:"):
        return None
    d = os.path.dirname(address[len("uds:"):])
    if os.path.basename(d).startswith("repro-worker-"):
        return d
    return None


def cleanup_worker_dir(address: str) -> None:
    """Remove the private socket dir a spawned worker was listening under."""
    d = worker_sock_dir(address)
    if d is not None:
        shutil.rmtree(d, ignore_errors=True)


def kill_worker_proc(proc: Optional[subprocess.Popen], *, wait_s: float = 5.0) -> None:
    """Reap a worker subprocess: terminate, bounded wait, then kill —
    a SIGTERM the worker ignores (hung in a compile, SIGSTOPped by the
    chaos harness) must not leave a zombie behind."""
    if proc is None or proc.poll() is not None:
        return
    proc.terminate()
    try:
        proc.wait(timeout=wait_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        try:
            proc.wait(timeout=wait_s)
        except subprocess.TimeoutExpired:
            pass


def held_accelerator() -> Optional[str]:
    """The accelerator platform this process has already initialised
    (``"tpu"``, ``"gpu"``), or None while it holds none.  Asking never
    initialises a backend."""
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return None
    import jax

    platform = jax.default_backend()
    return None if platform == "cpu" else platform


def spawn_worker(
    address: Optional[str] = None,
    *,
    spec_path: str = "",
    startup_timeout: float = 120.0,
):
    """Start a ``repro worker`` subprocess and wait until it accepts a dial.

    Returns ``(proc, address)``.  Without an explicit address the worker
    listens on a fresh UDS socket under a private temp dir (no port to
    guess, no parsing of the worker's stdout); the dir is removed by
    RemoteReplica.close()/drain(), or here if startup fails.

    Refuses at once when this process already holds an accelerator: a chip
    belongs to one process at a time, so the worker would fail or hang
    until the startup timeout."""
    held = held_accelerator()
    if held is not None:
        raise RuntimeError(
            f"cannot spawn a repro worker: this process already holds the "
            f"{held} backend, and a chip belongs to one process at a time, so "
            f"the worker could not reach it.  Serve with in-process replicas "
            f"(flavor 'inproc'), or start workers from a parent that has not "
            f"touched JAX and dial them by address."
        )
    made_dir = None
    if address is None:
        made_dir = tempfile.mkdtemp(prefix="repro-worker-")
        address = f"uds:{os.path.join(made_dir, uuid.uuid4().hex[:8] + '.sock')}"
    cmd = [sys.executable, "-m", "repro.cli", "worker", "--listen", address]
    if spec_path:
        cmd += ["--spec", spec_path]
    proc = subprocess.Popen(
        cmd, env=repro_python_env(), stdout=subprocess.DEVNULL
    )
    deadline = time.time() + startup_timeout
    probe = ControlChannel(address, timeout=5.0)
    while True:
        if proc.poll() is not None:
            if made_dir is not None:
                shutil.rmtree(made_dir, ignore_errors=True)
            raise RuntimeError(
                f"worker exited with code {proc.returncode} during startup "
                f"(cmd: {' '.join(cmd)})"
            )
        try:
            probe.connect()
            probe.close()
            return proc, address
        except ReplicaGone:
            if time.time() > deadline:
                kill_worker_proc(proc)
                if made_dir is not None:
                    shutil.rmtree(made_dir, ignore_errors=True)
                raise RuntimeError(
                    f"worker at {address} did not come up within {startup_timeout}s"
                ) from None
            time.sleep(0.05)


class RemoteReplica:
    """One worker process behind the replica driver surface.

    Mirrors the parts of :class:`~repro.core.server_engine.ServerEngine`
    the Router and the serving loops touch; see the module docstring for
    the shadow-state and supervision rules.
    """

    flavor = "remote"

    def __init__(
        self,
        channel: ControlChannel,
        *,
        address: str = "",
        proc: Optional[subprocess.Popen] = None,
    ):
        self.channel = channel
        self.address = address or channel.address
        self.proc = proc  # set when this replica spawned its worker
        self.spawned = proc is not None  # revive() respawns vs redials
        self.dead = False
        self.suspect = False  # heartbeat monitor: peer stopped answering
        self.retry_rpcs = False  # FaultPolicy: one-shot retry over reconnect
        self.retries = 0
        self.spec = None  # the placed ServeSpec subtree (revive re-places it)
        self._placed = False
        self._n_slots = 0
        self.k_max = 0
        self.max_len = 0
        self.greedy = True
        self._streams: Dict[int, DeviceStream] = {}
        self._pending: Dict[int, int] = {}  # device -> tokens in flight
        self._queue_depth = 0
        self._hint: Optional[float] = None
        self.last_telemetry: Optional[dict] = None  # worker payload from stats()
        self._hb_channel: Optional[ControlChannel] = None  # heartbeat probes
        # tests/chaos override: how revive() obtains a fresh channel; the
        # default respawns the worker process or redials the address
        self.channel_factory: Optional[Callable[[], ControlChannel]] = None

    @classmethod
    def dial(cls, address: str, *, timeout: float = DEFAULT_TIMEOUT) -> "RemoteReplica":
        channel = ControlChannel(address, timeout=timeout)
        channel.connect()
        return cls(channel, address=address)

    # -- placement -----------------------------------------------------------

    def place(self, spec) -> None:
        """Ship the ServeSpec subtree; the worker builds its engine from it.
        The spec is kept so a supervised revive() can re-place it."""
        ack = self.channel.request(
            codec.PlaceReplica(spec.to_json_str()), timeout=WARMUP_TIMEOUT
        )
        if not isinstance(ack, codec.PlaceAck):
            raise WorkerError(f"expected PlaceAck, got {type(ack).__name__}")
        if not ack.ok:
            raise WorkerError(f"worker at {self.address} refused placement: {ack.error}")
        self.spec = spec
        self._placed = True
        self._n_slots = ack.n_slots
        self.k_max = ack.k_max
        self.max_len = ack.max_len
        self.greedy = ack.greedy

    # -- supervision: retryable RPCs, chaos hooks, revive ---------------------

    def _request(self, msg: codec.Message, *, timeout: Optional[float] = None):
        """Side-effectful RPC with v4 replay protection.  The frame already
        carries a fresh non-zero seq; when ``retry_rpcs`` is on, one
        ReplicaGone is absorbed by reconnecting and RESENDING the same frame
        — the worker's replay cache dedups it if the first copy landed."""
        try:
            return self.channel.request(msg, timeout=timeout)
        except ReplicaGone:
            if not self.retry_rpcs or getattr(msg, "seq", 0) == 0:
                raise
            self.retries += 1
            self.channel.reconnect()
            return self.channel.request(msg, timeout=timeout)

    def ping(self, *, timeout: float = 2.0) -> bool:
        """Heartbeat probe on a DEDICATED channel — the main channel is
        driven by the router thread and is not shareable.  False on any
        failure (dial refused, timeout, bad reply); the failed channel is
        torn down so the next probe redials from scratch."""
        try:
            if self._hb_channel is None:
                self._hb_channel = ControlChannel(self.address, timeout=timeout)
                self._hb_channel.connect()
            reply = self._hb_channel.request(
                codec.Ping(seq=self._hb_channel.next_seq(), t=time.monotonic()),
                timeout=timeout,
            )
            return isinstance(reply, codec.Pong)
        except Exception:
            ch, self._hb_channel = self._hb_channel, None
            if ch is not None:
                ch.close()
            return False

    def chaos_kill(self) -> None:
        """Deterministic fault injection: make this worker unreachable the
        way a real crash would — SIGKILL a spawned process, or sever the
        control link of a dialed/faked one.  The Router discovers it on the
        next RPC exactly as it would a genuine failure."""
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            try:
                self.proc.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                pass
        kill = getattr(self.channel, "kill", None)
        if kill is not None:
            kill()  # test channels: flip their killed flag
        else:
            self.channel.close()

    def chaos_hang(self) -> None:
        """SIGSTOP a spawned worker: connected but silent (partition-like);
        only the heartbeat monitor or an RPC timeout can notice."""
        import signal

        if self.proc is not None and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGSTOP)
        else:
            hang = getattr(self.channel, "hang", None)
            if hang is not None:
                hang()

    def can_revive(self) -> bool:
        return self.channel_factory is not None or self.spawned or bool(self.address)

    def revive(self) -> None:
        """Bring a dead replica back: respawn the worker (or redial the
        address), re-place the stored spec, re-warmup.  The new engine is
        rebuilt deterministically from the spec's model seed, so recovered
        streams stay token-identical.  Raises ReplicaGone/RuntimeError on
        failure; the caller owns backoff and retry budgets."""
        if self.spec is None and self.channel_factory is None:
            raise ReplicaGone(f"replica at {self.address} was never placed")
        old_addr = self.address
        self.channel.close()
        if self._hb_channel is not None:
            self._hb_channel.close()
            self._hb_channel = None
        if self.channel_factory is not None:
            self.channel = self.channel_factory()
        elif self.spawned:
            kill_worker_proc(self.proc)
            cleanup_worker_dir(old_addr)
            self.proc, self.address = spawn_worker()
            self.channel = ControlChannel(self.address, timeout=self.channel.timeout)
            self.channel.connect()
        else:
            self.channel = ControlChannel(self.address, timeout=self.channel.timeout)
            self.channel.connect()
        self._streams.clear()
        self._pending.clear()
        self._queue_depth = 0
        self._hint = None
        self._placed = False
        if self.spec is not None:
            try:
                self.place(self.spec)
                self.warmup()
            except WorkerError as e:
                raise ReplicaGone(f"revived worker refused placement: {e}") from e
        self.dead = False
        self.suspect = False

    @property
    def fingerprint(self) -> tuple:
        # kv_dtype comes from the placed spec (the worker builds its pool
        # from it), mirroring LocalReplica's engine-derived fingerprint
        kv_dtype = getattr(self.spec, "kv_dtype", "bf16") if self.spec is not None else "bf16"
        return (self.k_max, self.max_len, self.greedy, kv_dtype)

    # -- shadowed introspection (no round trips) -----------------------------

    @property
    def streams(self) -> Dict[int, DeviceStream]:
        return self._streams

    @property
    def n_free(self) -> int:
        return self._n_slots - len(self._streams)

    @property
    def queue_depth(self) -> int:
        return len(self._pending)

    @property
    def steps(self):
        """Compiled executables cannot cross processes; never shareable."""
        return None

    def has_inflight(self, device_id: int) -> bool:
        return device_id in self._pending

    def next_event_hint(self, now: float) -> Optional[float]:
        return self._hint

    # -- driver surface (proxied) --------------------------------------------

    def admit(self, device_id: int, prompt, now: float = 0.0) -> Optional[DeviceStream]:
        reply = self._request(
            codec.AdmitRequest(
                device_id, np.asarray(prompt, np.int32), now,
                seq=self.channel.next_seq(),
            )
        )
        if not reply.ok:
            return None
        stream = DeviceStream(
            device_id=device_id,
            slot=reply.slot,
            prev_token=int(reply.prev_token),
            admitted_at=now,
        )
        self._streams[device_id] = stream
        return stream

    def submit(self, device_id: int, draft_tokens, now: float, draft_q=None) -> None:
        toks = np.asarray(draft_tokens, np.int32).reshape(-1)
        self._request(
            codec.SubmitRequest(
                device_id, toks, now,
                draft_q=None if draft_q is None else np.asarray(draft_q, np.float32),
                qmode="none" if draft_q is None else "f32",
                seq=self.channel.next_seq(),
            )
        )
        self._pending[device_id] = int(toks.shape[0])

    def step(self, now: float) -> Optional[List[Verdict]]:
        if not self._pending:
            return None  # nothing queued on this worker: skip the round trip
        reply = self._request(codec.StepRequest(now, seq=self.channel.next_seq()))
        self._queue_depth = reply.queue_depth
        self._hint = reply.hint
        verdicts: List[Verdict] = []
        for rec in reply.verdicts:
            stream = self._streams.get(rec.device_id)
            drafted = self._pending.pop(rec.device_id, 0)
            if stream is not None:
                stream.committed.extend(int(t) for t in rec.tokens)
                stream.prev_token = int(rec.next_prev)
                stream.rounds += 1
                stream.drafted += drafted
                stream.accepted += int(rec.n_accepted)
            verdicts.append(
                Verdict(
                    device_id=rec.device_id,
                    n_accepted=int(rec.n_accepted),
                    tokens=np.asarray(rec.tokens, np.int32),
                    next_prev=int(rec.next_prev),
                    accept_rate=float(rec.accept_rate),
                    queue_depth=int(rec.queue_depth),
                    queue_s=float(rec.queue_s),
                    verify_s=float(rec.verify_s),
                )
            )
        return verdicts or None

    def retire(self, device_id: int) -> DeviceStream:
        reply = self._request(
            codec.RetireRequest(device_id, seq=self.channel.next_seq())
        )
        self._pending.pop(device_id, None)
        self._streams.pop(device_id, None)
        from repro.transport.worker import state_to_stream

        return state_to_stream(reply.stream)

    def cancel_request(self, device_id: int) -> bool:
        reply = self._request(
            codec.CancelRequest(device_id, seq=self.channel.next_seq())
        )
        if reply.ok:
            self._pending.pop(device_id, None)
        return reply.ok

    def force_extend(self, device_id: int, tokens) -> int:
        reply = self._request(
            codec.ForceExtendRequest(
                device_id, np.asarray(tokens, np.int32), seq=self.channel.next_seq()
            )
        )
        stream = self._streams.get(device_id)
        if stream is not None:
            stream.committed.extend(int(t) for t in np.asarray(tokens).reshape(-1))
            stream.prev_token = int(reply.next_prev)
        return int(reply.next_prev)

    # -- migration (streams cross the wire bit-exactly) ----------------------

    def export_stream(self, device_id: int):
        reply = self._request(
            codec.ExportStream(device_id, seq=self.channel.next_seq())
        )
        self._pending.pop(device_id, None)
        self._streams.pop(device_id, None)
        from repro.transport.worker import state_to_stream

        return state_to_stream(reply.stream), dict(reply.stream.row)

    def import_stream(self, stream: DeviceStream, row_cache) -> DeviceStream:
        from repro.transport.worker import stream_to_state

        reply = self._request(
            codec.ImportStream(
                stream_to_state(stream, row_cache), seq=self.channel.next_seq()
            )
        )
        stream.slot = reply.slot
        self._streams[stream.device_id] = stream
        return stream

    # -- stats / warmup / lifecycle ------------------------------------------

    def stats(self, now: Optional[float] = None) -> EngineStats:
        req = codec.StatsRequest(
            now=0.0 if now is None else float(now), has_now=now is not None
        )
        try:
            reply = self.channel.request(req)
        except ReplicaGone:
            # side-effect-free: one reconnect-and-retry before giving up
            self.channel.reconnect()
            reply = self.channel.request(req)
        if reply.telemetry_json:
            self.last_telemetry = json.loads(reply.telemetry_json)
        return EngineStats(**json.loads(reply.stats_json))

    def warmup(self, buckets=None) -> Dict[int, float]:
        reply = self.channel.request(codec.WarmupRequest(), timeout=WARMUP_TIMEOUT)
        return {int(k): v for k, v in json.loads(reply.compile_json).items()}

    def drain(self) -> None:
        """Best-effort: ask the worker to exit; reap a spawned process and
        remove its private socket dir."""
        try:
            if self.channel.connected or not self.dead:
                self.channel.request(codec.Drain(), timeout=10.0)
        except (ReplicaGone, WorkerError):
            pass
        self.close()
        if self.proc is not None:
            try:
                self.proc.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                kill_worker_proc(self.proc)
            self.proc = None

    def close(self) -> None:
        self.channel.close()
        if self._hb_channel is not None:
            self._hb_channel.close()
            self._hb_channel = None
        if self.spawned:
            cleanup_worker_dir(self.address)
