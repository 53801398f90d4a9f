"""Transport runtime: codec round-trips, link invariants, end-to-end serving.

The load-bearing test mirrors PR 1's engine equivalence one level up the
stack: N async EdgeClients talking to a TransportServer over zero-latency
loopback links — the full wire protocol, admission, pipelined draft-ahead —
must commit exactly the tokens the lock-step reference loop commits.  The
network may change *when* things happen, never *what* is generated; only the
§III-A fallback (exercised with a deliberately lossy link) is allowed to
release unverified tokens, and even then client and server streams must
agree token-for-token with each other.
"""

import asyncio
import dataclasses
import select
import socket
import threading
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.engine_loop import sled_generate
from repro.core.server_engine import EdgeDeviceKit, ServerEngine
from repro.models.model_zoo import build_model, perturb_params
from repro.serving.devices import NETS, NetProfile
from repro.transport import codec
from repro.transport.client import EdgeClient
from repro.transport.links import LoopbackLink, SimulatedLink, make_link, tcp_connect, tcp_listen
from repro.transport.server import _PASS_TURNS, TransportServer

V = 128


# ---------------------------------------------------------------------------
# codec
# ---------------------------------------------------------------------------


def _roundtrip(msg):
    buf = codec.encode_frame(msg)
    out, used = codec.decode_frame(buf)
    assert used == len(buf)
    return out


def test_codec_roundtrip_all_messages():
    toks = np.asarray([5, 0, 127, 3], np.int32)
    hello = _roundtrip(codec.Hello(device_id=7, prompt=toks))
    assert hello.device_id == 7
    np.testing.assert_array_equal(hello.prompt, toks)

    admit = _roundtrip(codec.Admit(device_id=7, ok=True, slot=3))
    assert admit.ok and admit.slot == 3

    d = _roundtrip(codec.DraftPacket(device_id=1, seq=42, tokens=toks))
    assert (d.seq, d.qmode) == (42, "none") and d.draft_q is None
    np.testing.assert_array_equal(d.tokens, toks)

    v = _roundtrip(
        codec.Verdict(device_id=1, seq=42, n_accepted=2, tokens=toks[:3], next_prev=-1)
    )
    assert v.n_accepted == 2 and v.next_prev == -1 and v.flags == 0
    np.testing.assert_array_equal(v.tokens, toks[:3])

    f = _roundtrip(codec.Fallback(device_id=2, seq=9, tokens=toks))
    np.testing.assert_array_equal(f.tokens, toks)
    a = _roundtrip(codec.FallbackAck(device_id=2, seq=9, next_prev=77))
    assert a.next_prev == 77
    assert _roundtrip(codec.Close(device_id=3)).device_id == 3


def test_codec_empty_token_vector():
    d = _roundtrip(codec.DraftPacket(device_id=0, seq=0, tokens=np.zeros((0,), np.int32)))
    assert d.tokens.shape == (0,)


def test_codec_rejects_bad_frames():
    good = codec.encode_frame(codec.Close(device_id=1))
    with pytest.raises(codec.CodecError, match="magic"):
        codec.decode_frame(b"XX" + good[2:])
    with pytest.raises(codec.CodecError, match="version"):
        codec.decode_frame(good[:2] + bytes([99]) + good[3:])
    with pytest.raises(codec.CodecError, match="unknown message type"):
        codec.decode_frame(good[:3] + bytes([200]) + good[4:])
    # payload longer than the message needs -> trailing bytes
    padded = good[:4] + (len(good) - 8 + 2).to_bytes(4, "big") + good[8:] + b"\x00\x00"
    with pytest.raises(codec.CodecError, match="trailing"):
        codec.decode_frame(padded)


def test_codec_rejects_every_truncation():
    frame = codec.encode_frame(
        codec.DraftPacket(
            device_id=3, seq=1, tokens=np.asarray([1, 2, 3], np.int32),
            draft_q=np.asarray([0.5, 0.25, 0.125], np.float32), qmode="int8",
        )
    )
    for cut in range(len(frame)):
        with pytest.raises(codec.CodecError):
            codec.decode_frame(frame[:cut])


@pytest.mark.parametrize("qmode,atol", [("f32", 0.0), ("f16", 1e-3), ("int8", 1e-2)])
def test_codec_quantized_q_payload(qmode, atol):
    rngq = np.random.default_rng(0)
    q = rngq.uniform(0.0, 1.0, size=11).astype(np.float32)
    msg = codec.DraftPacket(
        device_id=0, seq=0, tokens=np.arange(11, dtype=np.int32), draft_q=q, qmode=qmode
    )
    out = _roundtrip(msg)
    assert out.qmode == qmode
    np.testing.assert_allclose(out.draft_q, q, atol=max(atol, 1e-7))
    # the whole point: quantized payloads are smaller on the wire
    size = {
        m: len(codec.encode_frame(dataclasses.replace(msg, qmode=m)))
        for m in ("f32", "f16", "int8")
    }
    assert size["int8"] < size["f16"] < size["f32"]


def test_codec_property_roundtrip():
    pytest.importorskip("hypothesis", reason="dev extra: pip install -e .[dev]")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=60, deadline=None)
    @given(
        dev=st.integers(0, 2**32 - 1),
        seq=st.integers(0, 2**32 - 1),
        toks=st.lists(st.integers(-(2**31), 2**31 - 1), max_size=40),
        qmode=st.sampled_from(codec.QMODES),
        qseed=st.integers(0, 2**16),
    )
    def check(dev, seq, toks, qmode, qseed):
        toks = np.asarray(toks, np.int32)
        q = None
        if qmode != "none":
            q = np.random.default_rng(qseed).uniform(0, 1, size=len(toks)).astype(np.float32)
        out = _roundtrip(codec.DraftPacket(dev, seq, toks, draft_q=q, qmode=qmode))
        assert (out.device_id, out.seq, out.qmode) == (dev, seq, qmode)
        np.testing.assert_array_equal(out.tokens, toks)
        if qmode == "none":
            assert out.draft_q is None
        else:
            np.testing.assert_allclose(out.draft_q, q, atol=2e-2)

    check()


def test_frame_decoder_reassembles_byte_stream():
    frames = [
        codec.encode_frame(codec.Hello(1, np.asarray([1, 2], np.int32))),
        codec.encode_frame(codec.DraftPacket(1, 0, np.asarray([3], np.int32))),
        codec.encode_frame(codec.Close(1)),
    ]
    stream = b"".join(frames)
    dec = codec.FrameDecoder()
    got = []
    for i in range(0, len(stream), 3):  # arbitrary chunking
        dec.feed(stream[i : i + 3])
        got.extend(dec)
    assert [type(m).__name__ for m in got] == ["Hello", "DraftPacket", "Close"]


# ---------------------------------------------------------------------------
# links
# ---------------------------------------------------------------------------


def test_loopback_link_immediate_fifo():
    async def inner():
        link = LoopbackLink()
        for i in range(5):
            await link.device.send(bytes([i]))
        got = [await link.server.recv() for _ in range(5)]
        assert got == [bytes([i]) for i in range(5)]
        assert link.device.stats.frames_tx == 5 and link.server.stats.frames_rx == 5
        link.device.close()
        assert await link.server.recv() is None

    asyncio.run(inner())


def test_simulated_link_latency_and_order():
    net = NetProfile("t", rtt_mean=0.02, rtt_jitter=0.01, bandwidth_bps=1e6)

    async def inner():
        link = SimulatedLink(net, seed=3)
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        payloads = [bytes([i]) * 100 for i in range(10)]
        for p in payloads:
            await link.device.send(p)
        got, times = [], []
        for _ in payloads:
            got.append(await link.server.recv())
            times.append(loop.time() - t0)
        # jitter must never reorder (FIFO invariant) ...
        assert got == payloads
        assert times == sorted(times)
        # ... and every frame pays at least serialization + some propagation
        assert times[0] >= 100 * 8 / 1e6
        # 10 x 100B back-to-back on a 1 Mb/s line: serialization alone is 8ms
        assert times[-1] >= 10 * 100 * 8 / 1e6

    asyncio.run(inner())


def test_simulated_link_drop_accounting():
    net = NetProfile("lossy", rtt_mean=0.001, rtt_jitter=0.0, bandwidth_bps=1e9, drop_prob=1.0)

    async def inner():
        link = SimulatedLink(net, seed=0)
        for i in range(4):
            await link.device.send(bytes([i]))
        assert link.device.stats.frames_dropped == 4
        link.device.close()  # close still rides through
        assert await link.server.recv() is None

    asyncio.run(inner())


def test_make_link_factory():
    assert isinstance(make_link("loopback"), LoopbackLink)
    assert isinstance(make_link("sim", NETS["wlan"]), SimulatedLink)
    with pytest.raises(ValueError):
        make_link("sim")
    with pytest.raises(ValueError):
        make_link("tcp")


# ---------------------------------------------------------------------------
# end-to-end over the wire
# ---------------------------------------------------------------------------


def _models():
    tcfg = dataclasses.replace(
        get_config("qwen2-1.5b").reduced(), name="tgt", vocab_size=V, num_layers=3
    )
    dcfg = dataclasses.replace(get_config("qwen2-1.5b").reduced(), vocab_size=V)
    dm, tm = build_model(dcfg), build_model(tcfg)
    dp = perturb_params(dm.init_params(jax.random.key(1)), 0.03)  # partial acceptance
    return dm, dp, tm, tm.init_params(jax.random.key(2))


def _run_fleet(dm, dp, tm, tp, prompts, *, policy, max_new, k_max=4, link_factory=None,
               verify_timeout=30.0, pipeline=True):
    n_dev = prompts.shape[0]
    engine = ServerEngine(
        tm, tp, n_slots=n_dev, max_len=128, k_max=k_max, policy=policy,
        max_wait=0.01, attn_chunk=32,
    )
    kit = EdgeDeviceKit(dm, dp, k_max=k_max, c_th=0.3, greedy=True, attn_chunk=32)
    retired = {}
    orig_retire = engine.retire
    engine.retire = lambda dev: retired.setdefault(dev, orig_retire(dev))

    async def inner():
        server = TransportServer(engine)
        clients = []
        for i in range(n_dev):
            link = link_factory(i) if link_factory else LoopbackLink()
            server.attach(link.server)
            clients.append(
                EdgeClient(
                    kit, i, np.asarray(prompts[i]), link.device,
                    max_new=max_new, max_len=128, pipeline=pipeline,
                    verify_timeout=verify_timeout, admit_timeout=verify_timeout,
                    seed=100 + i,
                )
            )
        outs = await asyncio.gather(*(c.run() for c in clients))
        for _ in range(500):
            if not engine.streams:
                break
            await asyncio.sleep(0.01)
        stats = server.stats()
        await server.stop()
        return outs, clients, stats

    outs, clients, stats = asyncio.run(inner())
    return outs, clients, stats, retired


def test_transport_loopback_matches_lockstep_reference():
    """Zero-latency loopback, continuous policy, pipelining on: the full wire
    path must be output-identical to sled_generate."""
    dm, dp, tm, tp = _models()
    B, max_new = 3, 10
    prompts = jax.random.randint(jax.random.key(3), (B, 12), 0, V)
    outs, clients, stats, _ = _run_fleet(
        dm, dp, tm, tp, prompts, policy="continuous", max_new=max_new
    )
    ref, _, _ = sled_generate(
        dm, dp, tm, tp, prompts, max_new=max_new, k_max=4, c_th=0.3, greedy=True
    )
    np.testing.assert_array_equal(np.array(outs), np.asarray(ref))
    assert stats.streams_served == B
    assert stats.bytes_rx > 0 and stats.bytes_tx > 0  # wire stats populated
    assert stats.fallback_tokens == 0
    # rejections happened, so the pipelined speculation must have missed too
    assert stats.acceptance_rate < 1.0
    assert sum(c.stats.pipeline_misses for c in clients) > 0


@pytest.mark.slow
@pytest.mark.parametrize("policy", ["static", "deadline"])
def test_transport_loopback_all_policies(policy):
    dm, dp, tm, tp = _models()
    B, max_new = 2, 8
    prompts = jax.random.randint(jax.random.key(4), (B, 12), 0, V)
    outs, _, _, _ = _run_fleet(dm, dp, tm, tp, prompts, policy=policy, max_new=max_new)
    ref, _, _ = sled_generate(
        dm, dp, tm, tp, prompts, max_new=max_new, k_max=4, c_th=0.3, greedy=True
    )
    np.testing.assert_array_equal(np.array(outs), np.asarray(ref))


@pytest.mark.slow
def test_transport_sim_link_matches_reference():
    """Latency and jitter (lossless) reorder nothing and change no tokens."""
    dm, dp, tm, tp = _models()
    B, max_new = 2, 8
    prompts = jax.random.randint(jax.random.key(5), (B, 12), 0, V)
    fast = NetProfile("fast", rtt_mean=0.004, rtt_jitter=0.002, bandwidth_bps=1e8)
    outs, _, _, _ = _run_fleet(
        dm, dp, tm, tp, prompts, policy="continuous", max_new=max_new,
        link_factory=lambda i: SimulatedLink(fast, seed=i),
    )
    ref, _, _ = sled_generate(
        dm, dp, tm, tp, prompts, max_new=max_new, k_max=4, c_th=0.3, greedy=True
    )
    np.testing.assert_array_equal(np.array(outs), np.asarray(ref))


class _DropNthDraft(LoopbackLink):
    """Loopback that eats exactly the n-th DraftPacket on the uplink."""

    def __init__(self, n: int):
        super().__init__()
        self._n = n
        self._count = 0
        inner_put = self.device._out.put

        async def put(frame):
            msg, _ = codec.decode_frame(frame)
            if isinstance(msg, codec.DraftPacket):
                self._count += 1
                if self._count == self._n:
                    self.device.stats.frames_dropped += 1
                    return
            await inner_put(frame)

        self.device._out.put = put


@pytest.mark.slow
def test_transport_fallback_resync_on_lost_request():
    """A lost DraftPacket times out device-side: the device releases its
    drafts locally (§III-A) and the server force-extends the stream, so both
    sides stay token-identical even though the round was never verified."""
    dm, dp, tm, tp = _models()
    max_new = 10
    prompts = jax.random.randint(jax.random.key(6), (1, 12), 0, V)
    link = _DropNthDraft(2)
    outs, clients, stats, retired = _run_fleet(
        dm, dp, tm, tp, prompts, policy="continuous", max_new=max_new,
        link_factory=lambda i: link, verify_timeout=1.5,
    )
    c = clients[0]
    assert c.stats.fallback_rounds == 1
    assert c.stats.fallback_tokens > 0
    assert stats.fallback_tokens == c.stats.fallback_tokens
    assert stats.fallback_rounds == 1
    assert len(outs[0]) == max_new
    # client and server committed streams agree exactly, including the
    # unverified fallback run
    assert retired[0].committed == c.device.committed


def test_transport_client_reconnects_after_midround_link_death():
    """Regression: a link severed mid-round (server's sending half closed,
    verdict lost with it) used to escape as a ConnectionError and kill the
    session coroutine.  With a reconnect hook the client redials, re-Hellos
    (the server resends Admit for the admitted stream), resyncs the open
    round through Fallback arbitration — and the committed stream stays
    token-identical to the lock-step reference."""
    dm, dp, tm, tp = _models()
    max_new = 10
    prompts = jax.random.randint(jax.random.key(8), (1, 12), 0, V)
    engine = ServerEngine(
        tm, tp, n_slots=1, max_len=128, k_max=4, policy="continuous",
        max_wait=0.01, attn_chunk=32,
    )
    kit = EdgeDeviceKit(dm, dp, k_max=4, c_th=0.3, greedy=True, attn_chunk=32)

    async def inner():
        server = TransportServer(engine)
        link = LoopbackLink()
        server.attach(link.server)

        async def redial():
            fresh = LoopbackLink()
            server.attach(fresh.server)
            return fresh.device

        client = EdgeClient(
            kit, 0, np.asarray(prompts[0]), link.device,
            max_new=max_new, max_len=128,
            verify_timeout=0.5, admit_timeout=0.5, seed=100,
            reconnect=redial,
        )

        # sever the ORIGINAL link as the 2nd verdict goes out: the verdict
        # is lost with the link, so the client sees a dead socket mid-round
        orig_send = server._send
        sent = {"verdicts": 0}

        async def chaotic_send(dev, frame):
            msg, _ = codec.decode_frame(frame)
            if isinstance(msg, codec.Verdict):
                sent["verdicts"] += 1
                if sent["verdicts"] == 2:
                    link.server.close()
                    return  # frame dies with the link
            await orig_send(dev, frame)

        server._send = chaotic_send
        out = await client.run()
        for _ in range(500):
            if not engine.streams:
                break
            await asyncio.sleep(0.01)
        await server.stop()
        return out, client, server

    out, client, server = asyncio.run(inner())
    assert client.stats.reconnects == 1, "exactly one redial should heal it"
    assert client.stats.late_verdicts >= 1  # round resolved by resent verdict
    assert server.late_verdicts_resent >= 1
    ref, _, _ = sled_generate(
        dm, dp, tm, tp, prompts, max_new=max_new, k_max=4, c_th=0.3, greedy=True
    )
    np.testing.assert_array_equal(np.array([out]), np.asarray(ref))


def test_transport_client_without_hook_still_raises():
    """No reconnect hook installed -> legacy behavior: the ConnectionError
    escapes (callers that want the old semantics keep them)."""
    dm, dp, _, _ = _models()
    kit = EdgeDeviceKit(dm, dp, k_max=4, c_th=0.3, greedy=True, attn_chunk=32)

    async def inner():
        link = LoopbackLink()
        client = EdgeClient(
            kit, 0, np.arange(8, dtype=np.int32), link.device,
            max_new=4, max_len=64, admit_timeout=0.2, seed=1,
        )
        link.server.close()  # server side gone before admission
        with pytest.raises(ConnectionError):
            await client._recv(1.0)
        with pytest.raises(ConnectionError):
            await client._redial(ConnectionError("boom"))

    asyncio.run(inner())


# ---------------------------------------------------------------------------
# the stepper's read order, over localhost TCP
# ---------------------------------------------------------------------------


class _FakeEngine:
    """The surface TransportServer drives, with no model.  ``step`` verifies
    every queued request and records which devices each call carried;
    ``on_step(devices)`` and ``on_admit(device)``, when set, run inside the
    call and block the event loop the way a device call and a prefill do."""

    def __init__(self):
        self.streams = {}
        self.queue = {}
        self.calls = []
        self.forced = []
        self.on_step = self.on_admit = None

    @property
    def queue_depth(self):
        return len(self.queue)

    def admit(self, dev, prompt, now=0.0):
        if self.on_admit is not None:
            self.on_admit(dev)
        self.streams[dev] = SimpleNamespace(slot=len(self.streams))
        return self.streams[dev]

    def has_inflight(self, dev):
        return dev in self.queue

    def submit(self, dev, tokens, now, draft_q=None):
        self.queue[dev] = np.asarray(tokens, np.int32)

    def next_event_hint(self, now):
        return None

    def step(self, now):
        batch, self.queue = self.queue, {}
        self.calls.append(set(batch))
        if batch and self.on_step is not None:
            self.on_step(set(batch))
        return [
            SimpleNamespace(device_id=d, n_accepted=len(t), tokens=t, next_prev=int(t[-1]),
                            accept_rate=1.0, queue_depth=0, queue_s=0.0, verify_s=0.0)
            for d, t in batch.items()
        ]

    def cancel_request(self, dev):
        return self.queue.pop(dev, None) is not None

    def force_extend(self, dev, tokens):
        self.forced.append(dev)
        return int(tokens[-1])

    def retire(self, dev):
        self.streams.pop(dev, None)
        self.queue.pop(dev, None)


class _Device:
    """A device's blocking TCP socket, driven from the test's thread."""

    def __init__(self, port):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=10.0)
        self.decoder = codec.FrameDecoder()

    def send(self, msg):
        self.sock.sendall(codec.encode_frame(msg))

    def recv(self):
        while (raw := self.decoder.next_raw()) is None:
            data = self.sock.recv(65536)
            assert data, "the server closed the connection"
            self.decoder.feed(data)
        return codec.decode_frame(raw)[0]


_TOKS = np.asarray([3, 1, 4], np.int32)


def _hello(dial, dev):
    d = dial()
    d.send(codec.Hello(dev, np.arange(4, dtype=np.int32)))
    assert d.recv().ok
    return d


def _draft(dev, seq=1):
    return codec.DraftPacket(dev, seq, _TOKS)


def _wait_readable(*eps):
    """Block (the event loop, when called from the engine) until bytes wait
    unread in each server endpoint's socket."""
    pending = {ep._writer.get_extra_info("socket") for ep in eps}
    while pending:
        ready, _, _ = select.select(list(pending), [], [], 10.0)
        assert ready, "the frames never reached the server's sockets"
        pending -= set(ready)


def _serve_tcp(engine, client):
    """Serve ``engine`` over localhost TCP while ``client(dial, server)`` runs
    in a thread (``dial()`` opens a _Device); returns its result and the
    server."""
    devices = []

    async def inner():
        server = TransportServer(engine)
        listener, port = await tcp_listen(server.attach)

        def dial():
            devices.append(_Device(port))
            return devices[-1]

        try:
            return await asyncio.to_thread(client, dial, server), server
        finally:
            for d in devices:
                d.sock.close()
            await server.stop()
            for ep in server._endpoints:
                ep.close()
            listener.close()

    return asyncio.run(inner())


def test_frames_that_land_during_a_call_make_the_next_call():
    """Frames for A and B land while the call carrying C blocks the loop:
    the very next call carries them; the one after is not the first."""
    engine, in_call = _FakeEngine(), threading.Event()
    A, B, C = 0, 1, 2

    def client(dial, server):
        devs = {d: _hello(dial, d) for d in (A, B, C)}

        def gate(batch):
            if batch == {C}:
                in_call.set()
                _wait_readable(server._conns[A], server._conns[B])

        engine.on_step = gate
        devs[C].send(_draft(C))
        assert in_call.wait(10.0)
        devs[A].send(_draft(A))
        devs[B].send(_draft(B))
        return {d: devs[d].recv() for d in (C, A, B)}

    verdicts, _ = _serve_tcp(engine, client)
    assert all(isinstance(v, codec.Verdict) and v.seq == 1 for v in verdicts.values())
    i = engine.calls.index({C})
    assert engine.calls[i + 1] == {A, B}, engine.calls[i:]


def test_a_frame_that_lands_during_an_admission_makes_the_next_call():
    """E's Hello lands during the call carrying C; its admission blocks like
    a prefill while A's frame lands.  The drain reads A before the next call."""
    engine = _FakeEngine()
    in_call, admitting = threading.Event(), threading.Event()
    A, C, E = 0, 2, 3

    def client(dial, server):
        devs = {d: _hello(dial, d) for d in (A, C)}
        e = dial()
        deadline = time.monotonic() + 10.0
        while len(server._endpoints) < 3 and time.monotonic() < deadline:
            time.sleep(0.001)
        (e_ep,) = [ep for ep in server._endpoints if ep not in server._conns.values()]

        def step_gate(batch):
            if batch == {C}:
                in_call.set()
                _wait_readable(e_ep)

        def admit_gate(dev):
            if dev == E:
                admitting.set()
                _wait_readable(server._conns[A])

        engine.on_step, engine.on_admit = step_gate, admit_gate
        devs[C].send(_draft(C))
        assert in_call.wait(10.0)
        e.send(codec.Hello(E, np.arange(4, dtype=np.int32)))
        assert admitting.wait(10.0)
        devs[A].send(_draft(A))
        return e.recv(), devs[C].recv(), devs[A].recv()

    (admit, vc, va), _ = _serve_tcp(engine, client)
    assert admit.ok and (vc.device_id, va.device_id) == (C, A)
    i = engine.calls.index({C})
    assert engine.calls[i + 1] == {A}, engine.calls[i:]


def test_a_flooding_peer_cannot_hold_the_stepper_past_the_bound():
    """A device that writes a fresh round on every loop turn keeps every
    drain pass busy; the stepper still plans a call at least once per
    bound (one pass per connected device: here one) and a pass."""
    engine, writes, at_call = _FakeEngine(), [0], []
    n_writes = 1000
    engine.on_step = lambda batch: at_call.append(writes[0])

    async def inner():
        server = TransportServer(engine)
        listener, port = await tcp_listen(server.attach)
        ep = await tcp_connect("127.0.0.1", port)
        await ep.send(codec.encode_frame(codec.Hello(0, np.arange(4, dtype=np.int32))))
        assert codec.decode_frame(await ep.recv())[0].ok
        for seq in range(1, n_writes + 1):
            await ep.send(codec.encode_frame(_draft(0, seq)))
            writes[0] = seq
            await asyncio.sleep(0)
        await server.stop()
        for e in (ep, *server._endpoints):
            e.close()
        listener.close()
        return server

    server = asyncio.run(inner())
    during = [w for w in at_call if w < n_writes]
    assert len(during) >= 100, len(during)
    assert max(np.diff(during)) <= 2 * _PASS_TURNS
    assert server._dispatched > len(at_call)


def test_a_fallback_that_lands_during_its_verify_call_gets_the_verdict():
    """Race discipline: the call verifying A's round records its verdict
    before any await, so a Fallback for that round that landed during the
    call, and is read by the drain, gets the stored Verdict resent."""
    engine, in_call = _FakeEngine(), threading.Event()

    def client(dial, server):
        dev = _hello(dial, 0)

        def gate(batch):
            if batch == {0}:
                in_call.set()
                _wait_readable(server._conns[0])

        engine.on_step = gate
        dev.send(_draft(0))
        assert in_call.wait(10.0)
        dev.send(codec.Fallback(0, 1, _TOKS))
        return dev.recv(), dev.recv()

    (first, second), server = _serve_tcp(engine, client)
    assert isinstance(first, codec.Verdict) and isinstance(second, codec.Verdict)
    assert first.seq == second.seq == 1
    np.testing.assert_array_equal(first.tokens, second.tokens)
    assert (server.late_verdicts_resent, server.fallback_acks, engine.forced) == (1, 0, [])


# ---------------------------------------------------------------------------
# engine hooks behind the transport
# ---------------------------------------------------------------------------


def test_engine_cancel_and_force_extend():
    _, _, tm, tp = _models()
    engine = ServerEngine(tm, tp, n_slots=1, max_len=64, k_max=4, attn_chunk=32)
    prompt = jax.random.randint(jax.random.key(7), (8,), 0, V)
    engine.admit(0, prompt, 0.0)
    assert not engine.cancel_request(0)  # nothing queued
    engine.submit(0, np.asarray([1, 2, 3], np.int32), 0.0)
    assert engine.cancel_request(0)
    assert engine.queue_depth == 0

    before_len = int(engine.pool.lengths()[0])
    stream = engine.streams[0]
    prev = engine.force_extend(0, np.asarray([9, 8, 7], np.int32))
    assert prev == 7 and stream.prev_token == 7
    assert stream.committed[-3:] == [9, 8, 7]
    assert int(engine.pool.lengths()[0]) == before_len + 3
    assert engine.stats(1.0).fallback_tokens == 3
    # the stream still verifies fine from the resynced tail
    engine.submit(0, np.asarray([1], np.int32), 1.0)
    verdicts = engine.step(1.1)
    assert verdicts and verdicts[0].device_id == 0
