"""The serving loop's ``sled.*`` profiler spans (``telemetry.span``).

A tiny ServerEngine behind the TransportServer, over in-process links,
served under ``jax.profiler`` on the CPU: every phase of the loop shows up as a host
event on the profiler's clock, the verify call's parts nest inside it, a
frame's read carries its device and round, the drain after each call's
verdicts holds the reads it made, and the profiler changes no served
token; all with telemetry off, as the benchmark serves.  A second serve
with telemetry on checks that the drain counts the frames it read.  And
``verify_s`` times the verify step through its results on the host, not
the dispatch alone.
"""

import asyncio
import dataclasses
import glob
import time

import jax
import numpy as np
import pytest

from repro import telemetry
from repro.configs.base import get_config
from repro.core.server_engine import EdgeDeviceKit, ServerEngine
from repro.models.model_zoo import build_model, perturb_params
from repro.serving.devices import NetProfile
from repro.transport.client import EdgeClient
from repro.transport.links import LoopbackLink, SimulatedLink
from repro.transport.server import TransportServer

V = 128
LAG = NetProfile("lag", rtt_mean=0.02, rtt_jitter=0.0, bandwidth_bps=1e8)
PHASES = (
    "recv", "await_work", "hold", "send", "drain", "plan", "verify", "pack", "launch",
    "sync", "commit", "prefill", "pool_write",
)


@pytest.fixture(scope="module")
def models():
    tcfg = dataclasses.replace(
        get_config("qwen2-1.5b").reduced(), name="tgt", vocab_size=V, num_layers=2
    )
    dcfg = dataclasses.replace(get_config("qwen2-1.5b").reduced(), vocab_size=V)
    dm, tm = build_model(dcfg), build_model(tcfg)
    dp = perturb_params(dm.init_params(jax.random.key(1)), 0.03)
    return dm, dp, tm, tm.init_params(jax.random.key(2))


def _serve(models, profile_dir=None):
    """Two devices, the second behind a link with a 20 ms round trip; the
    deadline policy holds the first one's request until the second's lands
    (or for 10 ms), so the loop both waits for work and holds it."""
    dm, dp, tm, tp = models
    engine = ServerEngine(tm, tp, n_slots=2, max_len=128, k_max=4, policy="deadline",
                          max_wait=0.01, attn_chunk=32)
    kit = EdgeDeviceKit(dm, dp, k_max=4, c_th=0.3, greedy=True, attn_chunk=32)
    prompts = np.asarray(jax.random.randint(jax.random.key(3), (2, 12), 0, V))

    async def inner():
        server = TransportServer(engine)
        clients = []
        for i in range(2):
            link = LoopbackLink() if i == 0 else SimulatedLink(LAG, seed=i)
            server.attach(link.server)
            clients.append(EdgeClient(kit, i, prompts[i], link.device, max_new=8, max_len=128,
                                      verify_timeout=30.0, admit_timeout=30.0, seed=100 + i))
        outs = await asyncio.gather(*(c.run() for c in clients))
        await server.stop()
        return outs

    if profile_dir is None:
        return asyncio.run(inner())
    with jax.profiler.trace(str(profile_dir)):
        return asyncio.run(inner())


def _host_spans(profile_dir):
    """(name, start_ns, end_ns, stats) of every sled.* event on the host."""
    path = sorted(glob.glob(f"{profile_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                    for ev in line.events if ev.name.startswith("sled.")
                )
    return out


@pytest.fixture(scope="module")
def profiled(models, tmp_path_factory):
    """The served tokens and the host spans, with telemetry off: the spans
    come from the profiler alone, as the benchmark records them."""
    d = tmp_path_factory.mktemp("profile")
    return _serve(models, d), _host_spans(d)


@pytest.fixture(scope="module")
def counted(models, tmp_path_factory):
    """The host spans and the drain's frame counter of a second profiled
    serve, with telemetry on so that counters count."""
    d = tmp_path_factory.mktemp("counted")
    telemetry.registry().reset()
    telemetry.enable(True)
    try:
        _serve(models, d)
        drained = telemetry.registry().counter("transport_frames_drained_total").value
    finally:
        telemetry.enable(False)
        telemetry.registry().reset()
    return _host_spans(d), drained


def test_every_phase_is_a_profiler_span(profiled):
    _, spans = profiled
    names = {s[0] for s in spans}
    assert {f"sled.{p}" for p in PHASES} <= names, sorted(names)


def test_verify_parts_nest_inside_the_verify_span(profiled):
    _, spans = profiled
    verify = [(a, b) for name, a, b, _ in spans if name == "sled.verify"]
    for part in ("sled.pack", "sled.launch", "sled.sync"):
        runs = [(a, b) for name, a, b, _ in spans if name == part]
        assert len(runs) == len(verify)
        assert all(any(va <= a and b <= vb for va, vb in verify) for a, b in runs), part


def test_recv_carries_device_and_round(profiled):
    _, spans = profiled
    recv = [st for name, _, _, st in spans if name == "sled.recv"]
    assert recv and all(st["device_id"] in (0, 1) and "seq" in st for st in recv)
    rounds = {(st["device_id"], st["seq"]) for st in recv if st["seq"] >= 0}
    assert {0, 1} == {d for d, _ in rounds} and len(rounds) > 2


def _drained_recvs(spans):
    """The ``sled.recv`` spans inside a ``sled.drain``, after checking that
    each drain follows a ``sled.send`` and that no read straddles a drain."""
    drains = sorted((a, b) for name, a, b, _ in spans if name == "sled.drain")
    sends = sorted(b for name, _, b, _ in spans if name == "sled.send")
    assert drains and len(drains) == len(sends)
    assert all(s <= a for s, (a, _) in zip(sends, drains))
    recv = [(a, b) for name, a, b, _ in spans if name == "sled.recv"]
    inside = [r for r in recv if any(a <= r[0] and r[1] <= b for a, b in drains)]
    assert not any(
        a < r[1] and r[0] < b for r in recv if r not in inside for a, b in drains
    )
    return inside


def test_drain_holds_its_reads(profiled):
    """A ``sled.recv`` lies wholly inside a drain or wholly outside every
    drain, with telemetry off."""
    _, spans = profiled
    assert _drained_recvs(spans)


def test_drain_holds_its_reads_and_counts_them(counted):
    """With telemetry on, the counter counts the frames read inside drains."""
    spans, drained = counted
    inside = _drained_recvs(spans)
    assert inside and drained == len(inside)


def test_profiler_changes_no_served_token(models, profiled):
    outs, _ = profiled
    np.testing.assert_array_equal(np.array(_serve(models)), np.array(outs))


class _SlowRead:
    """A device result whose read to the host takes 50 ms."""

    def __init__(self, a):
        self.a = a

    def __array__(self, dtype=None, copy=None):
        time.sleep(0.05)
        return np.asarray(self.a, dtype)


def test_verify_s_includes_the_result_read(models):
    _, _, tm, tp = models
    engine = ServerEngine(tm, tp, n_slots=2, max_len=64, k_max=4, attn_chunk=32)
    verify = engine.core.verify

    def slow(*a):
        res, bucket, fill = verify(*a)
        return dataclasses.replace(res, out_tokens=_SlowRead(res.out_tokens)), bucket, fill

    engine.core.verify = slow
    rng = np.random.default_rng(0)
    for d in range(2):
        engine.admit(d, rng.integers(0, V, 8).astype(np.int32))
        engine.submit(d, rng.integers(0, V, 3).astype(np.int32), 0.0)
    telemetry.registry().reset()
    telemetry.enable(True)
    try:
        verdicts = engine.step(0.0)
        hist = telemetry.registry().histogram("engine_verify_seconds")
    finally:
        telemetry.enable(False)
        telemetry.registry().reset()
    step_seconds = engine.round_log[-1].step_seconds
    assert len(verdicts) == 2
    assert step_seconds >= 0.05
    assert all(v.verify_s == step_seconds for v in verdicts)
    assert hist.count == 1 and hist.sum == step_seconds
