"""Cluster router: placement, migration, replica equivalence, adaptive k.

The load-bearing tests extend the PR-1/PR-2 equivalence ladder one more
level: a replica-sharded Router — including one that migrates a live stream
between replicas mid-run — must commit exactly the tokens the lock-step
reference loop commits.  Placement and migration may change which replica's
batches a stream rides in, never what it generates.
"""

import asyncio
import dataclasses

import jax
import numpy as np
import pytest

from repro.cluster import Router, make_placement
from repro.configs.base import get_config
from repro.core.engine import EngineStats
from repro.core.engine_loop import sled_generate
from repro.core.server_engine import EdgeDeviceKit, ServerEngine
from repro.models.model_zoo import build_model, perturb_params
from repro.serving.speclen import SpecLenController, make_controller
from repro.transport import codec
from repro.transport.links import LoopbackLink, tcp_connect, tcp_listen

V = 128


def _models():
    tcfg = dataclasses.replace(
        get_config("qwen2-1.5b").reduced(), name="tgt", vocab_size=V, num_layers=3
    )
    dcfg = dataclasses.replace(get_config("qwen2-1.5b").reduced(), vocab_size=V)
    dm, tm = build_model(dcfg), build_model(tcfg)
    dp = perturb_params(dm.init_params(jax.random.key(1)), 0.03)
    return dm, dp, tm, tm.init_params(jax.random.key(2))


def _drive(router, kit, prompts, *, max_new, seed_base=100):
    """In-process fleet loop over a router (mirrors launch/serve.py inproc);
    ``max_new`` may be per-device (list) to force staggered retirement."""
    n = prompts.shape[0]
    budgets = max_new if isinstance(max_new, (list, tuple)) else [max_new] * n
    devices, outputs = {}, {}
    now = 0.0
    while len(outputs) < n:
        now += 1.0
        for i in range(n):
            if i not in devices and i not in outputs:
                if router.admit(i, prompts[i], now) is not None:
                    devices[i] = kit.spawn(i, prompts[i], max_len=128, seed=seed_base + i)
        for i, dev in devices.items():
            if not dev.awaiting:
                router.submit(i, dev.draft(), now)
        for v in router.step(now) or []:
            dev = devices[v.device_id]
            dev.on_verdict(v)
            if len(dev.committed) >= budgets[v.device_id]:
                outputs[v.device_id] = dev.committed[: budgets[v.device_id]]
                router.retire(v.device_id)
                del devices[v.device_id]
        assert now < 500, "fleet failed to drain"
    return outputs


# ---------------------------------------------------------------------------
# EngineStats.merge
# ---------------------------------------------------------------------------


def test_engine_stats_merge():
    a = EngineStats(
        wstgr=10.0, per_device_rate=5.0, server_busy_frac=0.4, rounds=8,
        timeouts=1, fallback_tokens=3, mean_batch_fill=2.0,
        mean_round_latency=0.1, server_rounds_per_s=4.0, partial_rounds=2,
        streams_served=2, acceptance_rate=0.8, mean_queue_depth=1.0,
        bytes_tx=100, frames_tx=10,
    )
    b = EngineStats(
        wstgr=30.0, per_device_rate=5.0, server_busy_frac=0.2, rounds=24,
        timeouts=0, fallback_tokens=1, mean_batch_fill=4.0,
        mean_round_latency=0.3, server_rounds_per_s=12.0, partial_rounds=1,
        streams_served=6, acceptance_rate=0.4, mean_queue_depth=3.0,
        bytes_tx=300, frames_tx=30,
    )
    m = EngineStats.merge([a, b])
    assert m.replicas == 2
    assert m.wstgr == 40.0 and m.server_rounds_per_s == 16.0
    assert m.rounds == 32 and m.timeouts == 1 and m.fallback_tokens == 4
    assert m.streams_served == 8 and m.partial_rounds == 3
    assert m.bytes_tx == 400 and m.frames_tx == 40
    # round-weighted means: (2*8 + 4*24) / 32 = 3.5
    assert m.mean_batch_fill == pytest.approx(3.5)
    assert m.mean_round_latency == pytest.approx(0.25)
    assert m.acceptance_rate == pytest.approx((0.8 * 8 + 0.4 * 24) / 32)
    # n_streams reconstructed as wstgr/per_device_rate: 2 + 6 devices
    assert m.per_device_rate == pytest.approx(40.0 / 8)
    # merge of one is a copy, not an alias
    one = EngineStats.merge([a])
    assert one == a and one is not a
    with pytest.raises(ValueError):
        EngineStats.merge([])


# ---------------------------------------------------------------------------
# adaptive spec-length controller
# ---------------------------------------------------------------------------


def test_speclen_aimd_increase_and_decrease():
    c = SpecLenController(k_max=8, k_min=1, k_init=4, ewma=1.0)
    # high acceptance, idle queue: additive increase up to the bound
    assert c.update(1.0, 0) == 5
    assert c.update(1.0, 0) == 6
    for _ in range(8):
        c.update(1.0, 0)
    assert c.k == 8  # bounded above
    # low acceptance: multiplicative back-off
    assert c.update(0.1, 0) == 4
    assert c.update(0.1, 0) == 2
    assert c.update(0.1, 0) == 1
    assert c.update(0.1, 0) == 1  # bounded below
    assert c.decreases >= 3 and c.increases >= 2


def test_speclen_congestion_backs_off_despite_acceptance():
    c = SpecLenController(k_max=8, k_init=8, queue_hi=2, ewma=1.0)
    # perfect acceptance but a deep replica queue still reads as congestion
    assert c.update(1.0, 10) == 4
    assert c.update(1.0, 10) == 2
    # queue drains -> probe back up
    assert c.update(1.0, 0) == 3


def test_speclen_middle_band_holds_k():
    c = SpecLenController(k_max=8, k_init=4, accept_lo=0.3, accept_hi=0.8, ewma=1.0)
    assert c.update(0.5, 0) == 4  # between thresholds: hold


def test_make_controller():
    assert make_controller("fixed", k_max=4) is None
    c = make_controller("adaptive", k_max=4)
    assert isinstance(c, SpecLenController) and c.k == 4
    with pytest.raises(ValueError):
        make_controller("warp", k_max=4)
    with pytest.raises(ValueError):
        SpecLenController(k_max=2, k_min=3)


# ---------------------------------------------------------------------------
# codec feedback fields
# ---------------------------------------------------------------------------


def test_codec_verdict_feedback_roundtrip():
    v = codec.Verdict(
        device_id=3, seq=9, n_accepted=2,
        tokens=np.asarray([1, 2, 3], np.int32), next_prev=7,
        accept_rate=0.625, queue_depth=5,
    )
    out, used = codec.decode_frame(codec.encode_frame(v))
    assert used == len(codec.encode_frame(v))
    assert out.accept_rate == pytest.approx(0.625)
    assert out.queue_depth == 5
    np.testing.assert_array_equal(out.tokens, v.tokens)
    # defaults stay wire-compatible within v2
    out2, _ = codec.decode_frame(
        codec.encode_frame(codec.Verdict(1, 2, 1, np.asarray([4], np.int32), 4))
    )
    assert out2.accept_rate == 0.0 and out2.queue_depth == 0


# ---------------------------------------------------------------------------
# placement policies
# ---------------------------------------------------------------------------


def test_idle_replica_does_not_skew_merged_means():
    busy = EngineStats(
        wstgr=10.0, per_device_rate=5.0, server_busy_frac=0.5, rounds=100,
        timeouts=0, fallback_tokens=0, mean_batch_fill=4.0,
        mean_round_latency=0.2, server_rounds_per_s=2.0, streams_served=2,
        acceptance_rate=0.9,
    )
    idle = EngineStats(
        wstgr=0.0, per_device_rate=0.0, server_busy_frac=0.0, rounds=0,
        timeouts=0, fallback_tokens=0, mean_batch_fill=0.0,
        mean_round_latency=0.0, server_rounds_per_s=0.0,
    )
    m = EngineStats.merge([busy, idle])
    assert m.mean_batch_fill == pytest.approx(4.0)  # idle carries no weight
    assert m.acceptance_rate == pytest.approx(0.9)
    assert m.per_device_rate == pytest.approx(5.0)  # no phantom stream


def test_shared_steps_bundle_mismatch_raises():
    _, _, tm, tp = _models()
    a = ServerEngine(tm, tp, n_slots=2, max_len=64, k_max=4, attn_chunk=32)
    with pytest.raises(ValueError, match="greedy"):
        ServerEngine(tm, tp, n_slots=2, max_len=64, k_max=4, attn_chunk=32,
                     greedy=False, steps=a.steps)
    with pytest.raises(ValueError, match="scratch_slot"):
        ServerEngine(tm, tp, n_slots=3, max_len=64, k_max=4, attn_chunk=32,
                     steps=a.steps)


def test_router_requires_replicas_and_homogeneity():
    with pytest.raises(ValueError):
        Router([])
    with pytest.raises(ValueError, match="unknown placement"):
        make_placement("warp")
    _, _, tm, tp = _models()
    a = ServerEngine(tm, tp, n_slots=1, max_len=64, k_max=4, attn_chunk=32)
    b = ServerEngine(tm, tp, n_slots=1, max_len=64, k_max=2, attn_chunk=32)
    with pytest.raises(ValueError, match="homogeneous"):
        Router([a, b])


def test_least_loaded_placement_invariant():
    """Under staggered arrivals with no retirements, least-loaded keeps the
    per-replica load spread within 1 stream after every admission."""
    _, _, tm, tp = _models()
    router = Router.build(tm, tp, replicas=3, n_slots=2, max_len=64, k_max=4,
                          attn_chunk=32)
    prompts = jax.random.randint(jax.random.key(0), (6, 8), 0, V)
    for i in range(6):
        assert router.admit(i, prompts[i], float(i)) is not None
        loads = router.loads()
        assert max(loads) - min(loads) <= 1, f"unbalanced after admit {i}: {loads}"
    assert router.loads() == [2, 2, 2]
    # full cluster refuses further admissions (caller queues + retries)
    assert router.admit(99, prompts[0], 9.0) is None


def test_affinity_and_round_robin_placement():
    _, _, tm, tp = _models()
    prompts = jax.random.randint(jax.random.key(0), (5, 8), 0, V)

    router = Router.build(tm, tp, replicas=2, n_slots=2, max_len=64, k_max=4,
                          attn_chunk=32, placement="affinity",
                          migrate_on_retire=False)
    for i in (0, 2, 1):  # home replica = device_id % 2
        router.admit(i, prompts[i], 0.0)
    assert router.replica_of(0) == 0 and router.replica_of(2) == 0
    assert router.replica_of(1) == 1
    router.admit(4, prompts[4], 1.0)  # home r0 is full -> least-loaded spill
    assert router.replica_of(4) == 1

    rr = Router.build(tm, tp, replicas=2, n_slots=2, max_len=64, k_max=4,
                      attn_chunk=32, placement="round-robin")
    for i in range(4):
        rr.admit(i, prompts[i], 0.0)
    assert [rr.replica_of(i) for i in range(4)] == [0, 1, 0, 1]


# ---------------------------------------------------------------------------
# equivalence: replicas, migration
# ---------------------------------------------------------------------------


def test_router_single_replica_matches_lockstep_reference():
    """replicas=1 is the old single-engine serving loop: token-identical to
    sled_generate under the continuous policy with staggered arrivals."""
    dm, dp, tm, tp = _models()
    B, max_new = 3, 10
    prompts = jax.random.randint(jax.random.key(3), (B, 12), 0, V)
    router = Router.build(tm, tp, replicas=1, n_slots=B, max_len=128, k_max=4,
                          policy="continuous", attn_chunk=32)
    kit = EdgeDeviceKit(dm, dp, k_max=4, c_th=0.3, greedy=True, attn_chunk=32)
    outputs = _drive(router, kit, prompts, max_new=max_new)
    ref, _, _ = sled_generate(
        dm, dp, tm, tp, prompts, max_new=max_new, k_max=4, c_th=0.3, greedy=True
    )
    np.testing.assert_array_equal(
        np.array([outputs[i] for i in range(B)]), np.asarray(ref)
    )
    st = router.stats(50.0)
    assert st.streams_served == B and st.replicas == 1


@pytest.mark.slow
@pytest.mark.parametrize("policy", ["deadline", "static"])
def test_router_single_replica_all_policies(policy):
    dm, dp, tm, tp = _models()
    B, max_new = 2, 8
    prompts = jax.random.randint(jax.random.key(4), (B, 12), 0, V)
    router = Router.build(tm, tp, replicas=1, n_slots=B, max_len=128, k_max=4,
                          policy=policy, max_wait=0.0, attn_chunk=32)
    kit = EdgeDeviceKit(dm, dp, k_max=4, c_th=0.3, greedy=True, attn_chunk=32)
    outputs = _drive(router, kit, prompts, max_new=max_new)
    ref, _, _ = sled_generate(
        dm, dp, tm, tp, prompts, max_new=max_new, k_max=4, c_th=0.3, greedy=True
    )
    np.testing.assert_array_equal(
        np.array([outputs[i] for i in range(B)]), np.asarray(ref)
    )


def test_migration_on_retire_is_bit_identical():
    """Pile streams onto replica 0 via affinity, retire replica 1's only
    stream early: the router migrates a live stream over (its KV row copied
    bit-exactly), and every stream's output still equals the reference."""
    dm, dp, tm, tp = _models()
    prompts = jax.random.randint(jax.random.key(5), (5, 12), 0, V)
    router = Router.build(tm, tp, replicas=2, n_slots=3, max_len=128, k_max=4,
                          policy="continuous", attn_chunk=32,
                          placement="affinity", migrate_on_retire=True)
    kit = EdgeDeviceKit(dm, dp, k_max=4, c_th=0.3, greedy=True, attn_chunk=32)
    # ids 0/2/4 home onto replica 0 (full), id 1 onto replica 1; stream 1's
    # small budget retires it early -> imbalance [3, 0] -> migration fires
    ids = [0, 2, 4, 1]
    budgets = [12, 4, 12, 12, 12]  # indexed by device id: stream 1 quits early
    n = prompts.shape[0]  # only ids in `ids` are driven
    devices, outputs = {}, {}
    now = 0.0
    for i in ids:
        assert router.admit(i, prompts[i], now) is not None
        devices[i] = kit.spawn(i, prompts[i], max_len=128, seed=100 + i)
    assert router.loads() == [3, 1]
    assert all(router.replica_of(i) == 0 for i in (0, 2, 4))
    migrated_live = set()
    while len(outputs) < len(ids):
        now += 1.0
        for i, dev in devices.items():
            if not dev.awaiting:
                router.submit(i, dev.draft(), now)
        for v in router.step(now) or []:
            dev = devices[v.device_id]
            dev.on_verdict(v)
            if len(dev.committed) >= budgets[v.device_id]:
                outputs[v.device_id] = dev.committed[: budgets[v.device_id]]
                router.retire(v.device_id)
                del devices[v.device_id]
        # catch a stream that now lives on replica 1 while still generating
        migrated_live |= {i for i in (0, 2, 4)
                          if i in devices and router.replica_of(i) == 1}
        assert now < 500, "fleet failed to drain"
    assert router.migrations >= 1, "retirement imbalance must trigger migration"
    assert migrated_live, "a replica-0 stream should keep generating on replica 1"
    ref, _, _ = sled_generate(
        dm, dp, tm, tp, prompts, max_new=12, k_max=4, c_th=0.3, greedy=True
    )
    for i in ids:
        np.testing.assert_array_equal(
            np.asarray(outputs[i]), np.asarray(ref)[i, : budgets[i]],
            err_msg=f"stream {i} diverged (n={n})",
        )


def test_export_import_stream_moves_row_bit_exactly():
    _, _, tm, tp = _models()
    a = ServerEngine(tm, tp, n_slots=2, max_len=64, k_max=4, attn_chunk=32)
    b = ServerEngine(tm, tp, n_slots=2, max_len=64, k_max=4, attn_chunk=32,
                     steps=a.steps)
    prompt = jax.random.randint(jax.random.key(6), (9,), 0, V)
    a.admit(7, prompt, 0.0)
    stream, row = a.export_stream(7)
    assert 7 not in a.streams and a.pool.n_free == 2
    b.import_stream(stream, row)
    assert b.streams[7].prev_token == stream.prev_token
    got = b.core.export_row(b.streams[7].slot)
    for leaf_name in row:
        np.testing.assert_array_equal(np.asarray(row[leaf_name]),
                                      np.asarray(got[leaf_name]))
    # in-flight requests block migration (the row would change under copy)
    b.submit(7, np.asarray([1, 2], np.int32), 1.0)
    with pytest.raises(ValueError, match="in flight"):
        b.export_stream(7)


# ---------------------------------------------------------------------------
# adaptive k end-to-end (loopback transport, real feedback)
# ---------------------------------------------------------------------------


def test_adaptive_k_fleet_converges_down_on_rejections():
    """With a noisy draft model the AIMD controller must actually move k:
    verdict feedback drives it below k_max, and the proposal lengths on the
    wire respect the adapted cap."""
    from repro.transport.client import EdgeClient
    from repro.transport.server import TransportServer

    dm, dp0, tm, tp = _models()
    dp = perturb_params(dp0, 0.15)  # heavy noise: low acceptance
    k_max, max_new = 4, 12
    prompts = jax.random.randint(jax.random.key(7), (2, 12), 0, V)
    engine = ServerEngine(tm, tp, n_slots=2, max_len=128, k_max=k_max,
                          attn_chunk=32)
    kit = EdgeDeviceKit(dm, dp, k_max=k_max, c_th=0.0, greedy=True, attn_chunk=32)

    async def inner():
        server = TransportServer(engine)
        clients = []
        for i in range(2):
            link = LoopbackLink()
            server.attach(link.server)
            clients.append(
                EdgeClient(kit, i, np.asarray(prompts[i]), link.device,
                           max_new=max_new, max_len=128, pipeline=False,
                           verify_timeout=30.0, kctl="adaptive", seed=i)
            )
        outs = await asyncio.gather(*(c.run() for c in clients))
        await server.stop()
        return outs, clients

    outs, clients = asyncio.run(inner())
    assert all(len(o) == max_new for o in outs)
    assert all(c.kctl is not None and c.kctl.updates > 0 for c in clients)
    assert any(c.stats.k_final < k_max for c in clients), (
        f"low acceptance must shrink k: finals "
        f"{[c.stats.k_final for c in clients]}"
    )
    assert all(1 <= c.stats.k_final <= k_max for c in clients)


def test_edge_device_draft_k_clamp_is_prefix():
    """draft(k=) must return exactly the first k tokens of the unclamped
    greedy round (deterministic prefix property the truncation relies on)."""
    dm, dp, _, _ = _models()
    kit = EdgeDeviceKit(dm, dp, k_max=4, c_th=0.0, greedy=True, attn_chunk=32)
    prompt = jax.random.randint(jax.random.key(8), (10,), 0, V)
    full = kit.spawn(0, prompt, max_len=64, seed=1).draft()
    clamped = kit.spawn(0, prompt, max_len=64, seed=1).draft(k=2)
    assert clamped.shape[0] == 2
    np.testing.assert_array_equal(clamped, full[:2])


# ---------------------------------------------------------------------------
# TCP endpoint (real sockets, same codec)
# ---------------------------------------------------------------------------


def test_tcp_endpoint_codec_roundtrip_matches_loopback():
    """Frames over a real TCP socket decode identically to loopback — the
    FrameDecoder reassembles whatever segmentation the kernel produces."""
    msgs = [
        codec.Hello(device_id=1, prompt=np.asarray([5, 6, 7], np.int32)),
        codec.DraftPacket(device_id=1, seq=0, tokens=np.asarray([9, 8], np.int32)),
        codec.Verdict(device_id=1, seq=0, n_accepted=1,
                      tokens=np.asarray([9, 3], np.int32), next_prev=3,
                      accept_rate=0.5, queue_depth=2),
        codec.Fallback(device_id=1, seq=1, tokens=np.asarray([2], np.int32)),
        codec.FallbackAck(device_id=1, seq=1, next_prev=2),
        codec.Close(device_id=1),
    ]

    async def over_tcp():
        accepted = asyncio.Queue()
        server, port = await tcp_listen(lambda ep: accepted.put_nowait(ep))
        client = await tcp_connect("127.0.0.1", port)
        server_ep = await accepted.get()
        got = []
        # client -> server, one frame per send (kernel may merge them)
        for m in msgs:
            await client.send(codec.encode_frame(m))
        for _ in msgs:
            frame = await asyncio.wait_for(server_ep.recv(), 5.0)
            got.append(codec.decode_frame(frame)[0])
        # server -> client in one write burst (split across reads)
        for m in msgs:
            await server_ep.send(codec.encode_frame(m))
        back = []
        for _ in msgs:
            frame = await asyncio.wait_for(client.recv(), 5.0)
            back.append(codec.decode_frame(frame)[0])
        client.close()
        server_ep.close()  # Python 3.12's wait_closed waits for every connection
        server.close()
        await server.wait_closed()
        assert client.stats.frames_tx == len(msgs)
        assert server_ep.stats.frames_rx == len(msgs)
        return got, back

    async def over_loopback():
        link = LoopbackLink()
        got = []
        for m in msgs:
            await link.device.send(codec.encode_frame(m))
            got.append(codec.decode_frame(await link.server.recv())[0])
        return got

    tcp_got, tcp_back = asyncio.run(over_tcp())
    loop_got = asyncio.run(over_loopback())
    for a, b in zip(tcp_got, loop_got):
        assert type(a) is type(b)
        assert codec.encode_frame(a) == codec.encode_frame(b)
    for a, m in zip(tcp_back, msgs):
        assert codec.encode_frame(a) == codec.encode_frame(m)


def test_tcp_endpoint_recv_none_on_close():
    async def inner():
        accepted = asyncio.Queue()
        server, port = await tcp_listen(accepted.put_nowait)
        client = await tcp_connect("127.0.0.1", port)
        server_ep = await accepted.get()
        await client.send(codec.encode_frame(codec.Close(device_id=4)))
        frame = await asyncio.wait_for(server_ep.recv(), 5.0)
        assert isinstance(codec.decode_frame(frame)[0], codec.Close)
        client.close()
        assert await asyncio.wait_for(server_ep.recv(), 5.0) is None
        server_ep.close()  # Python 3.12's wait_closed waits for every connection
        server.close()
        await server.wait_closed()

    asyncio.run(inner())


# ---------------------------------------------------------------------------
# SSM/hybrid paged routing fails clean
# ---------------------------------------------------------------------------


def test_ssm_decode_forward_with_slots_raises_cleanly():
    """Routing an SSM model down the slot-indexed path must fail with a
    clear NotImplementedError at the API boundary, not a shape error deep
    in the step (the gather fallback is the supported route)."""
    mcfg = dataclasses.replace(
        get_config("mamba2-370m").reduced(), vocab_size=V, num_layers=2
    )
    mm = build_model(mcfg)
    mp = mm.init_params(jax.random.key(0))
    cache = mm.make_cache(2, 32)
    toks = jax.numpy.zeros((2, 3), jax.numpy.int32)
    with pytest.raises(NotImplementedError, match="gather/scatter fallback"):
        mm.decode_forward(mp, cache, toks, slots=jax.numpy.asarray([0, 1]))


def test_router_places_replicas_on_distinct_devices():
    """On a host with several devices each in-process replica commits its
    params and pool to its own device, verifies there, and accepts a KV row
    exported from the other device bit-exactly.  Runs in a child process
    with two virtual CPU devices (this process has one)."""
    import os
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import dataclasses, jax, numpy as np
        from repro.cluster import Router
        from repro.configs.base import get_config
        from repro.models.model_zoo import build_model
        cfg = dataclasses.replace(get_config("qwen2-1.5b").reduced(), vocab_size=64)
        model = build_model(cfg)
        params = jax.jit(model.init_params)(jax.random.key(0))
        router = Router.build(model, params, replicas=2, n_slots=1, max_len=32, k_max=2)
        engines = [r.engine for r in router.replicas]
        assert [e.device for e in engines] == jax.devices()[:2]
        router.warmup()
        prompt = np.arange(6, dtype=np.int32)
        for dev in range(2):
            assert router.admit(dev, prompt, 0.0) is not None
            router.submit(dev, np.asarray([1, 2], np.int32), 0.0)
        verdicts = router.step(1.0)
        assert sorted(v.device_id for v in verdicts) == [0, 1]
        for e in engines:
            assert len(e.round_log) == 1
            for leaf in jax.tree.leaves((e.params, e.pool.cache)):
                assert leaf.devices() == {e.device}
        row = engines[1].core.export_row(0)
        engines[0].core.import_row(0, row)
        back = engines[0].core.export_row(0)
        for a, b in zip(jax.tree.leaves(row), jax.tree.leaves(back)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        print("ok")
    """)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"), JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.split()[-1] == "ok"
