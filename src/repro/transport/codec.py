"""SLED wire protocol: versioned, length-prefixed binary frames.

Every frame is ``header || payload`` with an 8-byte header::

    magic "SL" (2) | version u8 | msg_type u8 | payload_len u32 (big-endian)

so frames survive byte-stream transports (TCP-style reassembly via
``FrameDecoder``) as well as message-oriented links.  All multi-byte integers
are big-endian; token vectors are little-endian int32 arrays (numpy
``tobytes`` of the natural serving dtype) behind a u16 count.

The draft-probability payload of a ``DraftPacket`` (the q(token) row needed
for lossless sampling-mode verification) dominates frame size at fp32, so it
can ride the wire quantized — ``qmode``:

    "none"  no q payload (greedy verification)
    "f32"   4 bytes/token, exact
    "f16"   2 bytes/token
    "int8"  1 byte/token + one fp32 scale (reuses quant/quantize.py's
            symmetric per-row scheme)

Quantization is an honest wire cost/fidelity trade the benchmarks measure;
decode returns fp32 either way.

v3 adds the cluster CONTROL PLANE: the frames a Router speaks to a remote
replica worker (transport/worker.py) over one TCP/UDS control connection —
``PlaceReplica`` ships a serialized ServeSpec subtree and the worker builds
its engine from it; ``AdmitRequest``/``SubmitRequest``/``StepRequest``
proxy the in-process replica driver surface (every ``now`` is the Router's
clock, so cross-process scheduling is deterministic); ``ExportStream``/
``ImportStream`` carry a stream's full server-side state plus a bit-exact
serialization of its KV pool row (bfloat16 rides the wire as raw uint16
words — no float round-trip); ``ReplicaStats`` returns the uniform
EngineStats record; ``Drain`` retires the worker.  Control payloads can
carry whole KV rows, so the payload cap is far above the v2 data-plane one.

v4 HARDENS the control plane for fault tolerance: every side-effectful
request (admit/submit/step/retire/cancel/force-extend/export/import) now
carries a per-channel ``seq`` id, and the worker keeps a bounded replay
cache keyed by (msg type, device, seq) — a retried frame after a reconnect
returns the ORIGINAL reply instead of double-applying the side effect, so a
one-shot retry over a flapped link is safe.  ``Ping``/``Pong`` add a
lightweight heartbeat (echoed seq + sender timestamp) so a partitioned or
hung peer is detected in seconds rather than at the 120 s RPC timeout.
``seq=0`` means "no replay protection" (v3-style fire-once semantics).

v5 drops ``PlaceAck``'s paged-attention byte: the engine's verify path
follows from the model's cache kind, so it is no longer part of a replica's
shape.
"""
from __future__ import annotations

import dataclasses
import struct
from typing import Iterator, List, Optional, Union

import numpy as np

from repro.quant.quantize import QTensor, dequantize, quantize

MAGIC = b"SL"
VERSION = 5  # v5: PlaceAck without the paged-attention byte
_HEADER = struct.Struct(">2sBBI")
HEADER_SIZE = _HEADER.size
# v3 control frames carry serialized KV rows (ExportStream/ImportStream), so
# the cap must hold a full pool row, not just a draft packet
MAX_PAYLOAD = 1 << 26

# message type ids (wire-stable: append only)
T_HELLO = 1
T_ADMIT = 2
T_DRAFT = 3
T_VERDICT = 4
T_FALLBACK = 5
T_FALLBACK_ACK = 6
T_CLOSE = 7
# v3 control plane (Router <-> remote replica worker)
T_PLACE = 8
T_PLACE_ACK = 9
T_ADMIT_REQ = 10
T_ADMIT_REPLY = 11
T_SUBMIT = 12
T_SUBMIT_ACK = 13
T_STEP = 14
T_STEP_REPLY = 15
T_RETIRE = 16
T_RETIRE_REPLY = 17
T_CANCEL = 18
T_CANCEL_REPLY = 19
T_FORCE_EXTEND = 20
T_FORCE_EXTEND_REPLY = 21
T_EXPORT = 22
T_EXPORT_REPLY = 23
T_IMPORT = 24
T_IMPORT_ACK = 25
T_STATS = 26
T_REPLICA_STATS = 27
T_WARMUP = 28
T_WARMUP_REPLY = 29
T_DRAIN = 30
T_DRAIN_ACK = 31
T_ERROR = 32
# v4 heartbeat
T_PING = 33
T_PONG = 34

QMODES = ("none", "f32", "f16", "int8")


class CodecError(ValueError):
    """Malformed, truncated, or version-incompatible frame."""


@dataclasses.dataclass(frozen=True)
class Hello:
    """Device -> server admission request; prompt is prefilled server-side."""

    device_id: int
    prompt: np.ndarray  # (P,) int32


@dataclasses.dataclass(frozen=True)
class Admit:
    """Server -> device admission verdict (ok=False: pool full, wait)."""

    device_id: int
    ok: bool
    slot: int = 0


@dataclasses.dataclass(frozen=True)
class DraftPacket:
    """Device -> server: one drafting round's proposal."""

    device_id: int
    seq: int
    tokens: np.ndarray  # (k,) int32
    draft_q: Optional[np.ndarray] = None  # (k,) fp32 (decoded), or None
    qmode: str = "none"


@dataclasses.dataclass(frozen=True)
class Verdict:
    """Server -> device: verification outcome for DraftPacket ``seq``.

    ``accept_rate`` (this round's draft-acceptance ratio — per-round so the
    control loop reacts to regime shifts; smoothing is the receiver's job)
    and ``queue_depth`` (the serving replica's planner queue after dispatch)
    are the v2 closed-loop feedback fields: devices feed them to an AIMD
    spec-length controller (serving/speclen.py) to tune ``k`` online.

    ``queue_s``/``verify_s`` are the server-timing breakdown (how long the
    round sat in the admission queue and how long its verify step took), so
    an edge client can attribute round latency to queue vs verify vs wire:
    wire time = measured RTT minus the two server spans.
    """

    device_id: int
    seq: int
    n_accepted: int
    tokens: np.ndarray  # committed this round (accepted + correction/bonus)
    next_prev: int
    flags: int = 0  # reserved for future protocol bits (always 0 in v2)
    accept_rate: float = 0.0  # this round's accepted/drafted, in [0, 1]
    queue_depth: int = 0  # replica queue depth after this round's dispatch
    queue_s: float = 0.0  # admission-queue wait for this round (server clock)
    verify_s: float = 0.0  # verify-step wall time for this round's batch


@dataclasses.dataclass(frozen=True)
class Fallback:
    """Device -> server: round ``seq`` timed out device-side; the device
    released ``tokens`` locally (§III-A) and asks the server to resync."""

    device_id: int
    seq: int
    tokens: np.ndarray  # (k,) int32 locally-released draft tokens


@dataclasses.dataclass(frozen=True)
class FallbackAck:
    """Server -> device: resync applied; draft from ``next_prev``."""

    device_id: int
    seq: int
    next_prev: int


@dataclasses.dataclass(frozen=True)
class Close:
    """Either side: stream ends; server frees the slot."""

    device_id: int


# -- v3 control plane (Router <-> remote replica worker) ---------------------


@dataclasses.dataclass(frozen=True)
class PlaceReplica:
    """Router -> worker: build your engine from this ServeSpec subtree
    (JSON; backend forced to "engine" with the per-replica slot count)."""

    spec_json: str


@dataclasses.dataclass(frozen=True)
class PlaceAck:
    """Worker -> router: engine built (or not); the fields echo the engine
    shape so the router can fingerprint replicas for migration safety."""

    ok: bool
    n_slots: int = 0
    k_max: int = 0
    max_len: int = 0
    greedy: bool = True
    error: str = ""


@dataclasses.dataclass(frozen=True)
class AdmitRequest:
    """Router -> worker: place a stream (prompt prefilled worker-side).
    ``now`` is the ROUTER's clock — the worker never consults its own.
    ``seq`` (v4, all side-effectful requests) keys the worker's replay
    cache: a retried frame with the same seq returns the original reply."""

    device_id: int
    prompt: np.ndarray  # (P,) int32
    now: float = 0.0
    seq: int = 0


@dataclasses.dataclass(frozen=True)
class AdmitReply:
    device_id: int
    ok: bool
    slot: int = 0
    prev_token: int = 0


@dataclasses.dataclass(frozen=True)
class SubmitRequest:
    """Router -> worker: one drafting round's proposal for verification."""

    device_id: int
    tokens: np.ndarray  # (k,) int32
    now: float = 0.0
    draft_q: Optional[np.ndarray] = None
    qmode: str = "none"
    seq: int = 0


@dataclasses.dataclass(frozen=True)
class SubmitAck:
    device_id: int


@dataclasses.dataclass(frozen=True)
class StepRequest:
    """Router -> worker: run one engine.step at the router's clock."""

    now: float
    seq: int = 0


@dataclasses.dataclass(frozen=True)
class VerdictRec:
    """One verdict inside a StepReply (mirrors core.engine.Verdict)."""

    device_id: int
    n_accepted: int
    tokens: np.ndarray  # (n,) int32 committed this round
    next_prev: int
    accept_rate: float = 0.0
    queue_depth: int = 0
    queue_s: float = 0.0  # server-timing breakdown (see Verdict)
    verify_s: float = 0.0


@dataclasses.dataclass(frozen=True)
class StepReply:
    """Worker -> router: the round's verdicts plus the replica's load
    signals (queue depth, free slots, next planner event hint)."""

    verdicts: tuple  # tuple[VerdictRec, ...]
    queue_depth: int = 0
    n_free: int = 0
    hint: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class RetireRequest:
    device_id: int
    seq: int = 0


@dataclasses.dataclass(frozen=True)
class CancelRequest:
    device_id: int
    seq: int = 0


@dataclasses.dataclass(frozen=True)
class CancelReply:
    device_id: int
    ok: bool


@dataclasses.dataclass(frozen=True)
class ForceExtendRequest:
    """Router -> worker: append unverified fallback tokens (§III-A)."""

    device_id: int
    tokens: np.ndarray  # (n,) int32
    seq: int = 0


@dataclasses.dataclass(frozen=True)
class ForceExtendReply:
    device_id: int
    next_prev: int


@dataclasses.dataclass(frozen=True)
class StreamState:
    """Full server-side state of one stream (RetireReply / migration).

    ``committed`` is the stream's lifetime committed-token list; ``row`` is
    the bit-exact serialized KV pool row (flat name->array dict; empty for
    replies that do not move the cache, e.g. retirement)."""

    device_id: int
    slot: int
    prev_token: int
    committed: tuple  # tuple[int, ...]
    admitted_at: float = 0.0
    rounds: int = 0
    drafted: int = 0
    accepted: int = 0
    row: dict = dataclasses.field(default_factory=dict)  # name -> np.ndarray

    # np arrays in a frozen dataclass: compare fields, not array truthiness
    def __eq__(self, other):
        if not isinstance(other, StreamState):
            return NotImplemented
        if (
            self.device_id, self.slot, self.prev_token, self.committed,
            self.admitted_at, self.rounds, self.drafted, self.accepted,
        ) != (
            other.device_id, other.slot, other.prev_token, other.committed,
            other.admitted_at, other.rounds, other.drafted, other.accepted,
        ):
            return False
        if sorted(self.row) != sorted(other.row):
            return False
        return all(
            self.row[k].dtype == other.row[k].dtype
            and self.row[k].shape == other.row[k].shape
            and bool(np.all(self.row[k] == other.row[k]))
            for k in self.row
        )


@dataclasses.dataclass(frozen=True)
class RetireReply:
    stream: StreamState


@dataclasses.dataclass(frozen=True)
class ExportStream:
    """Router -> worker: detach a quiescent stream for migration."""

    device_id: int
    seq: int = 0


@dataclasses.dataclass(frozen=True)
class ExportReply:
    stream: StreamState  # row populated


@dataclasses.dataclass(frozen=True)
class ImportStream:
    """Router -> worker: adopt a stream exported elsewhere (row populated)."""

    stream: StreamState
    seq: int = 0


@dataclasses.dataclass(frozen=True)
class ImportAck:
    device_id: int
    slot: int


@dataclasses.dataclass(frozen=True)
class StatsRequest:
    now: float = 0.0
    has_now: bool = False


@dataclasses.dataclass(frozen=True)
class ReplicaStats:
    """Worker -> router: the uniform EngineStats record as JSON, plus an
    optional telemetry payload (metrics snapshot + flight-recorder dump —
    see repro.telemetry) when the placed spec enabled telemetry."""

    stats_json: str
    telemetry_json: str = ""


@dataclasses.dataclass(frozen=True)
class WarmupRequest:
    pass


@dataclasses.dataclass(frozen=True)
class WarmupReply:
    compile_json: str = "{}"  # bucket -> seconds


@dataclasses.dataclass(frozen=True)
class Drain:
    """Router -> worker: retire everything and exit after the ack."""


@dataclasses.dataclass(frozen=True)
class DrainAck:
    streams_left: int = 0


@dataclasses.dataclass(frozen=True)
class ErrorReply:
    """Worker -> router: the request raised; message carries the detail."""

    message: str


@dataclasses.dataclass(frozen=True)
class Ping:
    """Heartbeat probe (v4).  ``t`` is the SENDER's monotonic timestamp,
    echoed back in the Pong so the sender computes RTT without clock sync.
    Side-effect free: never enters the replay cache, safe on any channel."""

    seq: int
    t: float = 0.0


@dataclasses.dataclass(frozen=True)
class Pong:
    """Heartbeat reply: echoes the Ping's seq and timestamp."""

    seq: int
    t: float = 0.0


Message = Union[
    Hello, Admit, DraftPacket, Verdict, Fallback, FallbackAck, Close,
    PlaceReplica, PlaceAck, AdmitRequest, AdmitReply, SubmitRequest,
    SubmitAck, StepRequest, StepReply, RetireRequest, RetireReply,
    CancelRequest, CancelReply, ForceExtendRequest, ForceExtendReply,
    ExportStream, ExportReply, ImportStream, ImportAck, StatsRequest,
    ReplicaStats, WarmupRequest, WarmupReply, Drain, DrainAck, ErrorReply,
    Ping, Pong,
]


# -- primitive encoders ------------------------------------------------------


def _put_tokens(out: List[bytes], toks: np.ndarray) -> None:
    toks = np.ascontiguousarray(np.asarray(toks, dtype="<i4"))
    if toks.ndim != 1:
        raise CodecError(f"token vector must be 1-D, got shape {toks.shape}")
    if toks.shape[0] > 0xFFFF:
        raise CodecError(f"token vector too long: {toks.shape[0]}")
    out.append(struct.pack(">H", toks.shape[0]))
    out.append(toks.tobytes())


def _put_str(out: List[bytes], s: str) -> None:
    b = s.encode("utf-8")
    out.append(struct.pack(">I", len(b)))
    out.append(b)


def _put_tokens32(out: List[bytes], toks) -> None:
    """Token vector behind a u32 count (lifetime committed lists can exceed
    the data-plane u16 cap)."""
    arr = np.ascontiguousarray(np.asarray(toks, dtype="<i4").reshape(-1))
    out.append(struct.pack(">I", arr.shape[0]))
    out.append(arr.tobytes())


def _np_dtype(name: str) -> np.dtype:
    if name == "bfloat16":
        import ml_dtypes  # jax dependency; the KV pool's default dtype

        return np.dtype(ml_dtypes.bfloat16)
    try:
        return np.dtype(name)
    except TypeError as e:
        raise CodecError(f"unknown array dtype {name!r}") from e


def _put_array(out: List[bytes], arr) -> None:
    """Bit-exact array serialization: dtype name, shape, little-endian raw
    bytes.  bfloat16 (the KV pool's serving dtype) has no numpy byte-order
    variants, so it rides as its raw uint16 words — no float conversion can
    perturb a migrated cache row."""
    a = np.ascontiguousarray(np.asarray(arr))
    name = a.dtype.name
    nb = name.encode("ascii")
    if len(nb) > 0xFF:
        raise CodecError(f"dtype name too long: {name!r}")
    if a.ndim > 0xFF:
        raise CodecError(f"array rank {a.ndim} too large")
    out.append(struct.pack(">B", len(nb)))
    out.append(nb)
    out.append(struct.pack(">B", a.ndim))
    if a.ndim:
        out.append(struct.pack(f">{a.ndim}I", *a.shape))
    if name == "bfloat16":
        raw = a.view(np.uint16).astype("<u2").tobytes()
    else:
        raw = a.astype(a.dtype.newbyteorder("<")).tobytes()
    out.append(struct.pack(">I", len(raw)))
    out.append(raw)


def _put_row(out: List[bytes], row: dict) -> None:
    """Flat name->array dict (a KV pool row from EngineCore.export_row)."""
    if len(row) > 0xFFFF:
        raise CodecError(f"row has too many leaves: {len(row)}")
    out.append(struct.pack(">H", len(row)))
    for name in sorted(row):
        nb = name.encode("utf-8")
        if len(nb) > 0xFFFF:
            raise CodecError(f"row leaf name too long: {name!r}")
        out.append(struct.pack(">H", len(nb)))
        out.append(nb)
        _put_array(out, row[name])


def _put_stream_state(out: List[bytes], s: StreamState) -> None:
    out.append(
        struct.pack(
            ">IIidIII",
            s.device_id,
            s.slot,
            s.prev_token,
            s.admitted_at,
            s.rounds,
            s.drafted,
            s.accepted,
        )
    )
    _put_tokens32(out, list(s.committed))
    _put_row(out, s.row)


class _Reader:
    """Bounds-checked cursor over a payload; raises CodecError on overrun."""

    def __init__(self, buf: bytes):
        self.buf = buf
        self.pos = 0

    def take(self, n: int) -> bytes:
        if self.pos + n > len(self.buf):
            raise CodecError(
                f"truncated payload: need {n} bytes at offset {self.pos}, "
                f"have {len(self.buf) - self.pos}"
            )
        b = self.buf[self.pos : self.pos + n]
        self.pos += n
        return b

    def u8(self) -> int:
        return self.take(1)[0]

    def u16(self) -> int:
        return struct.unpack(">H", self.take(2))[0]

    def u32(self) -> int:
        return struct.unpack(">I", self.take(4))[0]

    def i32(self) -> int:
        return struct.unpack(">i", self.take(4))[0]

    def f32(self) -> float:
        return struct.unpack(">f", self.take(4))[0]

    def f64(self) -> float:
        return struct.unpack(">d", self.take(8))[0]

    def tokens(self) -> np.ndarray:
        n = self.u16()
        return np.frombuffer(self.take(4 * n), dtype="<i4").astype(np.int32)

    def tokens32(self) -> np.ndarray:
        n = self.u32()
        if 4 * n > len(self.buf) - self.pos:
            raise CodecError(f"token32 vector of {n} overruns the payload")
        return np.frombuffer(self.take(4 * n), dtype="<i4").astype(np.int32)

    def string(self) -> str:
        n = self.u32()
        try:
            return self.take(n).decode("utf-8")
        except UnicodeDecodeError as e:
            raise CodecError(f"malformed utf-8 string payload: {e}") from e

    def array(self) -> np.ndarray:
        name = self.take(self.u8()).decode("ascii")
        ndim = self.u8()
        shape = tuple(self.u32() for _ in range(ndim))
        nbytes = self.u32()
        raw = self.take(nbytes)
        dt = _np_dtype(name)
        n_items = 1
        for d in shape:
            n_items *= d
        if nbytes != n_items * dt.itemsize:
            raise CodecError(
                f"array payload of {nbytes} bytes does not match "
                f"{name}{list(shape)} ({n_items * dt.itemsize} expected)"
            )
        if name == "bfloat16":
            arr = np.frombuffer(raw, dtype="<u2").astype(np.uint16).view(dt)
        else:
            arr = np.frombuffer(raw, dtype=dt.newbyteorder("<")).astype(dt)
        return arr.reshape(shape)

    def row(self) -> dict:
        n = self.u16()
        row = {}
        for _ in range(n):
            name = self.take(self.u16()).decode("utf-8")
            row[name] = self.array()
        return row

    def stream_state(self) -> StreamState:
        dev, slot, prev = self.u32(), self.u32(), self.i32()
        admitted_at = self.f64()
        rounds, drafted, accepted = self.u32(), self.u32(), self.u32()
        committed = tuple(int(t) for t in self.tokens32())
        return StreamState(
            device_id=dev,
            slot=slot,
            prev_token=prev,
            committed=committed,
            admitted_at=admitted_at,
            rounds=rounds,
            drafted=drafted,
            accepted=accepted,
            row=self.row(),
        )

    def done(self) -> None:
        if self.pos != len(self.buf):
            raise CodecError(f"{len(self.buf) - self.pos} trailing bytes in payload")


# -- q payload (quantized probability row) -----------------------------------


def _encode_q(out: List[bytes], q: Optional[np.ndarray], qmode: str) -> None:
    if qmode not in QMODES:
        raise CodecError(f"unknown qmode {qmode!r}")
    out.append(bytes([QMODES.index(qmode)]))
    if qmode == "none":
        return
    if q is None:
        raise CodecError(f"qmode {qmode!r} requires a draft_q payload")
    q = np.asarray(q, np.float32).reshape(-1)
    out.append(struct.pack(">H", q.shape[0]))
    if qmode == "f32":
        out.append(q.astype("<f4").tobytes())
    elif qmode == "f16":
        out.append(q.astype("<f2").tobytes())
    elif not q.size:  # int8 of an empty draft: no row to scale
        out.append(struct.pack(">f", 0.0))
    else:  # int8: symmetric per-row scheme from quant/quantize.py
        qt = quantize(q[None, :], bits=8)
        out.append(struct.pack(">f", float(qt.scale[0, 0])))
        out.append(np.ascontiguousarray(qt.q[0]).astype("|i1").tobytes())


def _decode_q(r: _Reader):
    mode_id = r.u8()
    if mode_id >= len(QMODES):
        raise CodecError(f"unknown qmode id {mode_id}")
    qmode = QMODES[mode_id]
    if qmode == "none":
        return None, qmode
    n = r.u16()
    if qmode == "f32":
        q = np.frombuffer(r.take(4 * n), dtype="<f4").astype(np.float32)
    elif qmode == "f16":
        q = np.frombuffer(r.take(2 * n), dtype="<f2").astype(np.float32)
    else:
        scale = r.f32()
        raw = np.frombuffer(r.take(n), dtype="|i1")
        qt = QTensor(
            q=raw[None, :], scale=np.asarray([[scale]], np.float32), bits=8, shape=(1, n)
        )
        q = np.asarray(dequantize(qt, np.float32))[0]
    return q, qmode


# -- frame encode/decode -----------------------------------------------------


def encode_frame(msg: Message) -> bytes:
    out: List[bytes] = []
    if isinstance(msg, Hello):
        mtype = T_HELLO
        out.append(struct.pack(">I", msg.device_id))
        _put_tokens(out, msg.prompt)
    elif isinstance(msg, Admit):
        mtype = T_ADMIT
        out.append(struct.pack(">IBI", msg.device_id, int(msg.ok), msg.slot))
    elif isinstance(msg, DraftPacket):
        mtype = T_DRAFT
        out.append(struct.pack(">II", msg.device_id, msg.seq))
        _put_tokens(out, msg.tokens)
        _encode_q(out, msg.draft_q, msg.qmode)
    elif isinstance(msg, Verdict):
        mtype = T_VERDICT
        out.append(
            struct.pack(
                ">IIHiBfHff",
                msg.device_id,
                msg.seq,
                msg.n_accepted,
                msg.next_prev,
                msg.flags,
                float(msg.accept_rate),
                min(int(msg.queue_depth), 0xFFFF),
                float(msg.queue_s),
                float(msg.verify_s),
            )
        )
        _put_tokens(out, msg.tokens)
    elif isinstance(msg, Fallback):
        mtype = T_FALLBACK
        out.append(struct.pack(">II", msg.device_id, msg.seq))
        _put_tokens(out, msg.tokens)
    elif isinstance(msg, FallbackAck):
        mtype = T_FALLBACK_ACK
        out.append(struct.pack(">IIi", msg.device_id, msg.seq, msg.next_prev))
    elif isinstance(msg, Close):
        mtype = T_CLOSE
        out.append(struct.pack(">I", msg.device_id))
    elif isinstance(msg, PlaceReplica):
        mtype = T_PLACE
        _put_str(out, msg.spec_json)
    elif isinstance(msg, PlaceAck):
        mtype = T_PLACE_ACK
        out.append(
            struct.pack(
                ">BIIIB",
                int(msg.ok),
                msg.n_slots,
                msg.k_max,
                msg.max_len,
                int(msg.greedy),
            )
        )
        _put_str(out, msg.error)
    elif isinstance(msg, AdmitRequest):
        mtype = T_ADMIT_REQ
        out.append(struct.pack(">IId", msg.seq, msg.device_id, float(msg.now)))
        _put_tokens(out, msg.prompt)
    elif isinstance(msg, AdmitReply):
        mtype = T_ADMIT_REPLY
        out.append(
            struct.pack(">IBIi", msg.device_id, int(msg.ok), msg.slot, msg.prev_token)
        )
    elif isinstance(msg, SubmitRequest):
        mtype = T_SUBMIT
        out.append(struct.pack(">IId", msg.seq, msg.device_id, float(msg.now)))
        _put_tokens(out, msg.tokens)
        _encode_q(out, msg.draft_q, msg.qmode)
    elif isinstance(msg, SubmitAck):
        mtype = T_SUBMIT_ACK
        out.append(struct.pack(">I", msg.device_id))
    elif isinstance(msg, StepRequest):
        mtype = T_STEP
        out.append(struct.pack(">Id", msg.seq, float(msg.now)))
    elif isinstance(msg, StepReply):
        mtype = T_STEP_REPLY
        if len(msg.verdicts) > 0xFFFF:
            raise CodecError(f"too many verdicts in one step: {len(msg.verdicts)}")
        out.append(
            struct.pack(
                ">IIBd",
                msg.queue_depth,
                msg.n_free,
                int(msg.hint is not None),
                0.0 if msg.hint is None else float(msg.hint),
            )
        )
        out.append(struct.pack(">H", len(msg.verdicts)))
        for v in msg.verdicts:
            out.append(
                struct.pack(
                    ">IHifIff",
                    v.device_id,
                    v.n_accepted,
                    v.next_prev,
                    float(v.accept_rate),
                    v.queue_depth,
                    float(v.queue_s),
                    float(v.verify_s),
                )
            )
            _put_tokens(out, v.tokens)
    elif isinstance(msg, RetireRequest):
        mtype = T_RETIRE
        out.append(struct.pack(">II", msg.seq, msg.device_id))
    elif isinstance(msg, RetireReply):
        mtype = T_RETIRE_REPLY
        _put_stream_state(out, msg.stream)
    elif isinstance(msg, CancelRequest):
        mtype = T_CANCEL
        out.append(struct.pack(">II", msg.seq, msg.device_id))
    elif isinstance(msg, CancelReply):
        mtype = T_CANCEL_REPLY
        out.append(struct.pack(">IB", msg.device_id, int(msg.ok)))
    elif isinstance(msg, ForceExtendRequest):
        mtype = T_FORCE_EXTEND
        out.append(struct.pack(">II", msg.seq, msg.device_id))
        _put_tokens(out, msg.tokens)
    elif isinstance(msg, ForceExtendReply):
        mtype = T_FORCE_EXTEND_REPLY
        out.append(struct.pack(">Ii", msg.device_id, msg.next_prev))
    elif isinstance(msg, ExportStream):
        mtype = T_EXPORT
        out.append(struct.pack(">II", msg.seq, msg.device_id))
    elif isinstance(msg, ExportReply):
        mtype = T_EXPORT_REPLY
        _put_stream_state(out, msg.stream)
    elif isinstance(msg, ImportStream):
        mtype = T_IMPORT
        out.append(struct.pack(">I", msg.seq))
        _put_stream_state(out, msg.stream)
    elif isinstance(msg, ImportAck):
        mtype = T_IMPORT_ACK
        out.append(struct.pack(">II", msg.device_id, msg.slot))
    elif isinstance(msg, StatsRequest):
        mtype = T_STATS
        out.append(struct.pack(">dB", float(msg.now), int(msg.has_now)))
    elif isinstance(msg, ReplicaStats):
        mtype = T_REPLICA_STATS
        _put_str(out, msg.stats_json)
        _put_str(out, msg.telemetry_json)
    elif isinstance(msg, WarmupRequest):
        mtype = T_WARMUP
    elif isinstance(msg, WarmupReply):
        mtype = T_WARMUP_REPLY
        _put_str(out, msg.compile_json)
    elif isinstance(msg, Drain):
        mtype = T_DRAIN
    elif isinstance(msg, DrainAck):
        mtype = T_DRAIN_ACK
        out.append(struct.pack(">I", msg.streams_left))
    elif isinstance(msg, ErrorReply):
        mtype = T_ERROR
        _put_str(out, msg.message)
    elif isinstance(msg, Ping):
        mtype = T_PING
        out.append(struct.pack(">Id", msg.seq, float(msg.t)))
    elif isinstance(msg, Pong):
        mtype = T_PONG
        out.append(struct.pack(">Id", msg.seq, float(msg.t)))
    else:
        raise CodecError(f"cannot encode {type(msg).__name__}")
    payload = b"".join(out)
    return _HEADER.pack(MAGIC, VERSION, mtype, len(payload)) + payload


def decode_frame(buf: bytes) -> tuple:
    """Decode one frame from the head of ``buf``; returns (message, consumed).

    Raises CodecError on a malformed header or payload; an *incomplete* frame
    (fewer bytes than the header announces) also raises — stream transports
    should use FrameDecoder, which buffers instead.
    """
    if len(buf) < HEADER_SIZE:
        raise CodecError(f"truncated header: {len(buf)} < {HEADER_SIZE} bytes")
    magic, version, mtype, plen = _HEADER.unpack_from(buf)
    if magic != MAGIC:
        raise CodecError(f"bad magic {magic!r}")
    if version != VERSION:
        raise CodecError(f"unsupported protocol version {version} (speak {VERSION})")
    if plen > MAX_PAYLOAD:
        raise CodecError(f"payload length {plen} exceeds cap {MAX_PAYLOAD}")
    if len(buf) < HEADER_SIZE + plen:
        raise CodecError(
            f"truncated frame: payload needs {plen} bytes, have {len(buf) - HEADER_SIZE}"
        )
    r = _Reader(bytes(buf[HEADER_SIZE : HEADER_SIZE + plen]))
    if mtype == T_HELLO:
        msg: Message = Hello(device_id=r.u32(), prompt=r.tokens())
    elif mtype == T_ADMIT:
        msg = Admit(device_id=r.u32(), ok=bool(r.u8()), slot=r.u32())
    elif mtype == T_DRAFT:
        dev, seq = r.u32(), r.u32()
        toks = r.tokens()
        q, qmode = _decode_q(r)
        if q is not None and q.shape[0] != toks.shape[0]:
            raise CodecError(f"draft_q length {q.shape[0]} != token count {toks.shape[0]}")
        msg = DraftPacket(device_id=dev, seq=seq, tokens=toks, draft_q=q, qmode=qmode)
    elif mtype == T_VERDICT:
        dev, seq, n_acc, nxt, flags = r.u32(), r.u32(), r.u16(), r.i32(), r.u8()
        accept_rate, queue_depth = r.f32(), r.u16()
        queue_s, verify_s = r.f32(), r.f32()
        msg = Verdict(
            device_id=dev,
            seq=seq,
            n_accepted=n_acc,
            tokens=r.tokens(),
            next_prev=nxt,
            flags=flags,
            accept_rate=accept_rate,
            queue_depth=queue_depth,
            queue_s=queue_s,
            verify_s=verify_s,
        )
    elif mtype == T_FALLBACK:
        msg = Fallback(device_id=r.u32(), seq=r.u32(), tokens=r.tokens())
    elif mtype == T_FALLBACK_ACK:
        msg = FallbackAck(device_id=r.u32(), seq=r.u32(), next_prev=r.i32())
    elif mtype == T_CLOSE:
        msg = Close(device_id=r.u32())
    elif mtype == T_PLACE:
        msg = PlaceReplica(spec_json=r.string())
    elif mtype == T_PLACE_ACK:
        ok, n_slots, k_max, max_len = bool(r.u8()), r.u32(), r.u32(), r.u32()
        msg = PlaceAck(
            ok=ok, n_slots=n_slots, k_max=k_max, max_len=max_len,
            greedy=bool(r.u8()), error=r.string(),
        )
    elif mtype == T_ADMIT_REQ:
        seq, dev, now = r.u32(), r.u32(), r.f64()
        msg = AdmitRequest(device_id=dev, prompt=r.tokens(), now=now, seq=seq)
    elif mtype == T_ADMIT_REPLY:
        msg = AdmitReply(
            device_id=r.u32(), ok=bool(r.u8()), slot=r.u32(), prev_token=r.i32()
        )
    elif mtype == T_SUBMIT:
        seq, dev, now = r.u32(), r.u32(), r.f64()
        toks = r.tokens()
        q, qmode = _decode_q(r)
        if q is not None and q.shape[0] != toks.shape[0]:
            raise CodecError(f"draft_q length {q.shape[0]} != token count {toks.shape[0]}")
        msg = SubmitRequest(
            device_id=dev, tokens=toks, now=now, draft_q=q, qmode=qmode, seq=seq
        )
    elif mtype == T_SUBMIT_ACK:
        msg = SubmitAck(device_id=r.u32())
    elif mtype == T_STEP:
        seq = r.u32()
        msg = StepRequest(now=r.f64(), seq=seq)
    elif mtype == T_STEP_REPLY:
        depth, n_free, has_hint, hint = r.u32(), r.u32(), r.u8(), r.f64()
        verdicts = []
        for _ in range(r.u16()):
            dev, n_acc, nxt, rate, vdepth = r.u32(), r.u16(), r.i32(), r.f32(), r.u32()
            vqueue_s, vverify_s = r.f32(), r.f32()
            verdicts.append(
                VerdictRec(
                    device_id=dev, n_accepted=n_acc, tokens=r.tokens(),
                    next_prev=nxt, accept_rate=rate, queue_depth=vdepth,
                    queue_s=vqueue_s, verify_s=vverify_s,
                )
            )
        msg = StepReply(
            verdicts=tuple(verdicts), queue_depth=depth, n_free=n_free,
            hint=hint if has_hint else None,
        )
    elif mtype == T_RETIRE:
        seq = r.u32()
        msg = RetireRequest(device_id=r.u32(), seq=seq)
    elif mtype == T_RETIRE_REPLY:
        msg = RetireReply(stream=r.stream_state())
    elif mtype == T_CANCEL:
        seq = r.u32()
        msg = CancelRequest(device_id=r.u32(), seq=seq)
    elif mtype == T_CANCEL_REPLY:
        msg = CancelReply(device_id=r.u32(), ok=bool(r.u8()))
    elif mtype == T_FORCE_EXTEND:
        seq = r.u32()
        msg = ForceExtendRequest(device_id=r.u32(), tokens=r.tokens(), seq=seq)
    elif mtype == T_FORCE_EXTEND_REPLY:
        msg = ForceExtendReply(device_id=r.u32(), next_prev=r.i32())
    elif mtype == T_EXPORT:
        seq = r.u32()
        msg = ExportStream(device_id=r.u32(), seq=seq)
    elif mtype == T_EXPORT_REPLY:
        msg = ExportReply(stream=r.stream_state())
    elif mtype == T_IMPORT:
        seq = r.u32()
        msg = ImportStream(stream=r.stream_state(), seq=seq)
    elif mtype == T_IMPORT_ACK:
        msg = ImportAck(device_id=r.u32(), slot=r.u32())
    elif mtype == T_STATS:
        msg = StatsRequest(now=r.f64(), has_now=bool(r.u8()))
    elif mtype == T_REPLICA_STATS:
        msg = ReplicaStats(stats_json=r.string(), telemetry_json=r.string())
    elif mtype == T_WARMUP:
        msg = WarmupRequest()
    elif mtype == T_WARMUP_REPLY:
        msg = WarmupReply(compile_json=r.string())
    elif mtype == T_DRAIN:
        msg = Drain()
    elif mtype == T_DRAIN_ACK:
        msg = DrainAck(streams_left=r.u32())
    elif mtype == T_ERROR:
        msg = ErrorReply(message=r.string())
    elif mtype == T_PING:
        msg = Ping(seq=r.u32(), t=r.f64())
    elif mtype == T_PONG:
        msg = Pong(seq=r.u32(), t=r.f64())
    else:
        raise CodecError(f"unknown message type {mtype}")
    r.done()
    return msg, HEADER_SIZE + plen


class FrameDecoder:
    """Incremental decoder for byte-stream transports: feed arbitrary chunks,
    iterate complete messages (partial frames wait for more bytes)."""

    def __init__(self):
        self._buf = bytearray()

    def feed(self, data: bytes) -> None:
        self._buf.extend(data)

    def next_raw(self) -> Optional[bytes]:
        """Pop the next COMPLETE frame as raw bytes (header+payload), or None
        when more bytes are needed.  Used by byte-stream endpoints
        (transport/links.py StreamEndpoint) that forward whole frames without
        decoding them; corrupt headers raise the precise CodecError."""
        if len(self._buf) < HEADER_SIZE:
            return None
        magic, version, _, plen = _HEADER.unpack_from(self._buf)
        if magic != MAGIC or version != VERSION or plen > MAX_PAYLOAD:
            decode_frame(bytes(self._buf))  # raises the precise error
        if len(self._buf) < HEADER_SIZE + plen:
            return None
        raw = bytes(self._buf[: HEADER_SIZE + plen])
        del self._buf[: HEADER_SIZE + plen]
        return raw

    def __iter__(self) -> Iterator[Message]:
        while True:
            if len(self._buf) < HEADER_SIZE:
                return
            magic, version, _, plen = _HEADER.unpack_from(self._buf)
            if magic != MAGIC or version != VERSION or plen > MAX_PAYLOAD:
                # corrupt stream: decode_frame raises the precise error
                decode_frame(bytes(self._buf))
            if len(self._buf) < HEADER_SIZE + plen:
                return
            msg, used = decode_frame(bytes(self._buf[: HEADER_SIZE + plen]))
            del self._buf[:used]
            yield msg
