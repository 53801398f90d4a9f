"""Remote replica worker: one engine replica per OS process, behind a socket.

``repro worker --listen ADDR [--spec FILE]`` runs ONE verification replica
in its own process, listening on a TCP or UDS :class:`StreamEndpoint` for
codec v3 control frames from a cluster Router (cluster/remote.py's
``RemoteReplica`` is the dialing side).  The worker is the cross-process
half of the ROADMAP's "placement RPC is just a ServeSpec shipped to another
host" slice:

  * ``PlaceReplica`` carries a serialized ServeSpec subtree; the worker
    builds its engine from it through the same ``System.build`` front door
    as every in-process backend, so worker params are rebuilt
    deterministically from the spec's model seed — two processes placing the
    same spec hold bit-identical weights, which is what keeps cross-process
    serving token-identical to the in-process cluster;
  * every driver RPC (admit / submit / step / retire / cancel /
    force-extend / export / import / stats / warmup) mirrors the
    ServerEngine surface 1:1, and every ``now`` comes from the ROUTER's
    clock — the worker never consults its own, so cross-process batch
    scheduling is deterministic and clock skew cannot reorder rounds;
  * ``ExportStream``/``ImportStream`` move a stream's full server-side
    state plus a bit-exact KV row serialization, so the Router migrates
    streams across processes exactly as it does between in-process replicas;
  * ``Drain`` acks and exits the process.

The engine is wrapped in a :class:`~repro.transport.server.TransportServer`:
control connections drive the engine through :class:`WorkerCore` dispatch,
while a connection that opens with a data-plane frame (``Hello``) is handed
to the transport server instead — a worker can also serve edge devices
directly, one replica per port (do not mix router-driven stepping and
direct device service on one worker; the two step clocks are independent).

Dispatch is transport-free in :class:`WorkerCore` (message in, reply out),
so tests drive the full wire dispatch without sockets or subprocesses.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
from collections import OrderedDict
from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.transport import codec
from repro.transport.links import Endpoint, listen_addr

log = logging.getLogger(__name__)


def stream_to_state(stream, row: Optional[dict] = None) -> codec.StreamState:
    """Serialize a server-side DeviceStream (core/admission.py) for the wire."""
    return codec.StreamState(
        device_id=stream.device_id,
        slot=stream.slot,
        prev_token=int(stream.prev_token),
        committed=tuple(int(t) for t in stream.committed),
        admitted_at=float(stream.admitted_at),
        rounds=int(stream.rounds),
        drafted=int(stream.drafted),
        accepted=int(stream.accepted),
        row={} if row is None else {k: np.asarray(v) for k, v in row.items()},
    )


def state_to_stream(state: codec.StreamState):
    """Inverse of :func:`stream_to_state` (row travels separately)."""
    from repro.core.admission import DeviceStream

    return DeviceStream(
        device_id=state.device_id,
        slot=state.slot,
        prev_token=state.prev_token,
        committed=[int(t) for t in state.committed],
        admitted_at=state.admitted_at,
        rounds=state.rounds,
        drafted=state.drafted,
        accepted=state.accepted,
    )


def build_engine_from_spec(spec):
    """One engine replica from a ServeSpec subtree, through the same front
    door as every in-process backend (System.build), so construction
    semantics — paging fallback warnings included — cannot drift."""
    from repro.api.system import System

    if spec.backend != "engine":
        spec = spec.with_backend("engine")
    return System.build(spec).engine


class WorkerCore:
    """Control-frame dispatch against one engine: message in, reply out.

    Any handler exception becomes an :class:`~repro.transport.codec.ErrorReply`
    (the dialing side re-raises it); the connection survives, because a
    rejected RPC (say, an export refused while a request is in flight) is a
    protocol answer, not a worker crash.

    v4 replay protection: side-effectful requests carrying a non-zero
    ``seq`` are deduped through a bounded replay cache keyed by
    (frame type, device, seq).  A router that lost the reply to a link flap
    can reconnect and RESEND the same frame — the worker returns the
    original reply instead of double-applying the admit/submit/step/retire,
    which is what makes the dialing side's one-shot retry safe.
    """

    REPLAY_CAP = 512  # cached replies; enough to cover any in-flight window

    _REPLAYABLE = ()  # filled below (codec classes defined at module scope)

    def __init__(self, engine=None):
        self.engine = engine
        self.draining = False
        self._replay: "OrderedDict[tuple, codec.Message]" = OrderedDict()
        self.replay_hits = 0

    def _replay_key(self, msg: codec.Message) -> Optional[tuple]:
        if not isinstance(msg, WorkerCore._REPLAYABLE) or msg.seq == 0:
            return None
        if isinstance(msg, codec.ImportStream):
            dev = msg.stream.device_id
        else:
            dev = getattr(msg, "device_id", -1)
        return (type(msg).__name__, dev, msg.seq)

    def handle(self, msg: codec.Message) -> codec.Message:
        if isinstance(msg, codec.Ping):  # heartbeat: no engine, no side effects
            return codec.Pong(seq=msg.seq, t=msg.t)
        key = self._replay_key(msg)
        if key is not None and key in self._replay:
            self.replay_hits += 1
            return self._replay[key]
        try:
            reply = self._dispatch(msg)
        except Exception as e:  # surfaced to the router, not crashed here
            reply = codec.ErrorReply(f"{type(e).__name__}: {e}")
        if key is not None:
            self._replay[key] = reply
            while len(self._replay) > self.REPLAY_CAP:
                self._replay.popitem(last=False)
        return reply

    def _dispatch(self, msg: codec.Message) -> codec.Message:
        if isinstance(msg, codec.PlaceReplica):
            return self._place(msg)
        if isinstance(msg, codec.Drain):
            self.draining = True
            return codec.DrainAck(
                streams_left=0 if self.engine is None else len(self.engine.streams)
            )
        if self.engine is None:
            raise RuntimeError("worker has no engine yet (send PlaceReplica first)")
        engine = self.engine
        if isinstance(msg, codec.AdmitRequest):
            stream = engine.admit(
                msg.device_id, jnp.asarray(msg.prompt, jnp.int32), msg.now
            )
            if stream is None:
                return codec.AdmitReply(msg.device_id, ok=False)
            return codec.AdmitReply(
                msg.device_id, ok=True, slot=stream.slot,
                prev_token=int(stream.prev_token),
            )
        if isinstance(msg, codec.SubmitRequest):
            engine.submit(msg.device_id, msg.tokens, msg.now, draft_q=msg.draft_q)
            return codec.SubmitAck(msg.device_id)
        if isinstance(msg, codec.StepRequest):
            verdicts = engine.step(msg.now) or []
            recs = tuple(
                codec.VerdictRec(
                    device_id=v.device_id,
                    n_accepted=int(v.n_accepted),
                    tokens=np.asarray(v.tokens, np.int32),
                    next_prev=int(v.next_prev),
                    accept_rate=float(v.accept_rate),
                    queue_depth=int(v.queue_depth),
                    queue_s=float(v.queue_s),
                    verify_s=float(v.verify_s),
                )
                for v in verdicts
            )
            return codec.StepReply(
                verdicts=recs,
                queue_depth=engine.queue_depth,
                n_free=engine.pool.n_free,
                hint=engine.next_event_hint(msg.now),
            )
        if isinstance(msg, codec.RetireRequest):
            stream = engine.retire(msg.device_id)
            return codec.RetireReply(stream=stream_to_state(stream))
        if isinstance(msg, codec.CancelRequest):
            return codec.CancelReply(msg.device_id, ok=engine.cancel_request(msg.device_id))
        if isinstance(msg, codec.ForceExtendRequest):
            nxt = engine.force_extend(msg.device_id, msg.tokens)
            return codec.ForceExtendReply(msg.device_id, next_prev=int(nxt))
        if isinstance(msg, codec.ExportStream):
            stream, row = engine.export_stream(msg.device_id)
            return codec.ExportReply(stream=stream_to_state(stream, row))
        if isinstance(msg, codec.ImportStream):
            stream = state_to_stream(msg.stream)
            engine.import_stream(stream, dict(msg.stream.row))
            return codec.ImportAck(msg.stream.device_id, slot=stream.slot)
        if isinstance(msg, codec.StatsRequest):
            st = engine.stats(msg.now if msg.has_now else None)
            payload = engine.telemetry_payload() if hasattr(engine, "telemetry_payload") else {}
            return codec.ReplicaStats(
                stats_json=json.dumps(st.to_json()),
                telemetry_json=json.dumps(payload) if payload else "",
            )
        if isinstance(msg, codec.WarmupRequest):
            secs = engine.warmup()
            return codec.WarmupReply(
                compile_json=json.dumps({str(k): v for k, v in secs.items()})
            )
        raise codec.CodecError(f"worker cannot handle {type(msg).__name__}")

    def _place(self, msg: codec.PlaceReplica) -> codec.Message:
        from repro.api.spec import ServeSpec

        if self.engine is not None:
            return codec.PlaceAck(ok=False, error="worker already has an engine placed")
        try:
            spec = ServeSpec.from_json(msg.spec_json)
            self.engine = build_engine_from_spec(spec)
        except Exception as e:
            return codec.PlaceAck(ok=False, error=f"{type(e).__name__}: {e}")
        return codec.PlaceAck(
            ok=True,
            n_slots=self.engine.pool.n_slots,
            k_max=self.engine.k_max,
            max_len=self.engine.pool.max_len,
            greedy=self.engine.greedy,
        )


WorkerCore._REPLAYABLE = (
    codec.AdmitRequest,
    codec.SubmitRequest,
    codec.StepRequest,
    codec.RetireRequest,
    codec.CancelRequest,
    codec.ForceExtendRequest,
    codec.ExportStream,
    codec.ImportStream,
)


class ReplicaWorker:
    """The worker process' accept loop: control sessions drive WorkerCore;
    a connection that opens with a data-plane ``Hello`` is attached to the
    TransportServer wrapping the engine instead (direct device service)."""

    def __init__(self, listen: str, *, engine=None):
        self.listen = listen
        self.core = WorkerCore(engine)
        self.resolved: Optional[str] = None
        self._drained = None  # asyncio.Event, created on the serve loop
        self._transport = None  # TransportServer, on first data-plane conn

    async def serve(self) -> None:
        self._drained = asyncio.Event()
        server, self.resolved = await listen_addr(self._serve_conn, self.listen)
        print(f"repro-worker listening on {self.resolved}", flush=True)
        log.info("worker listening on %s", self.resolved)
        try:
            await self._drained.wait()
        finally:
            if self._transport is not None:
                await self._transport.stop()
            server.close()
            await server.wait_closed()

    async def _serve_conn(self, ep: Endpoint) -> None:
        while True:
            frame = await ep.recv()
            if frame is None:
                return
            msg, _ = codec.decode_frame(frame)
            if isinstance(msg, (codec.Hello, codec.DraftPacket, codec.Fallback, codec.Close)):
                await self._serve_device(ep, msg)
                return
            reply = self.core.handle(msg)
            await ep.send(codec.encode_frame(reply))
            if isinstance(msg, codec.Drain):
                self._drained.set()
                return

    async def _serve_device(self, ep: Endpoint, first: codec.Message) -> None:
        """Hand a data-plane connection to the TransportServer wrapper."""
        from repro.transport.server import TransportServer

        if self.core.engine is None:
            raise RuntimeError("worker has no engine yet (send PlaceReplica or --spec)")
        if self._transport is None:
            self._transport = TransportServer(self.core.engine)
        srv = self._transport
        srv._endpoints.append(ep)  # wire stats: this conn counts in stats()
        await srv._send_all(srv._dispatch(first, ep))
        if srv._stepper is None:
            srv._stepper = asyncio.get_running_loop().create_task(srv._step_loop())
        device_id = getattr(first, "device_id", None)
        while True:
            frame = await ep.recv()
            if frame is None:
                break
            msg, _ = codec.decode_frame(frame)
            device_id = msg.device_id
            await srv._send_all(srv._dispatch(msg, ep))
        if device_id is not None and device_id in srv.engine.streams:
            await srv._send_all(srv._retire(device_id))


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(
        prog="repro worker",
        description="Run one SLED engine replica behind a TCP/UDS control socket.",
    )
    ap.add_argument(
        "--listen", type=str, default="tcp:127.0.0.1:0",
        help="listen address: tcp:HOST:PORT (port 0 = free port) or uds:/path.sock",
    )
    ap.add_argument(
        "--spec", type=str, default="",
        help="optional ServeSpec JSON artifact: build the engine up front "
             "instead of waiting for a PlaceReplica frame",
    )
    ap.add_argument(
        "--log-level", type=str, default=None,
        help="repro.* logger level (debug/info/warning/error); "
             "falls back to REPRO_LOG_LEVEL, default warning",
    )
    args = ap.parse_args(argv)
    if args.log_level or not logging.getLogger("repro").handlers:
        # don't clobber a level the repro CLI's global --log-level already set
        telemetry.setup_logging(args.log_level)
    engine = None
    if args.spec:
        from repro.api.spec import ServeSpec

        with open(args.spec) as f:
            engine = build_engine_from_spec(ServeSpec.from_json(f.read()))
    asyncio.run(ReplicaWorker(args.listen, engine=engine).serve())


if __name__ == "__main__":
    main()
