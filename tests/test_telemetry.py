"""Telemetry: metrics registry, traces, flight recorder, stats merging, and
the codec v3 server-timing / telemetry-payload wire fields."""

import dataclasses
import json
import logging

import numpy as np
import pytest

from repro import telemetry
from repro.core.engine import EngineStats
from repro.transport import codec
from repro.transport.client import ClientStats


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Telemetry state is process-global: every test starts and ends off+empty."""
    telemetry.enable(False)
    telemetry.registry().reset()
    yield
    telemetry.enable(False)
    telemetry.registry().reset()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = telemetry.MetricsRegistry()
    c = reg.counter("rounds_total")
    c.inc()
    c.inc(2.0)
    assert c.value == 3.0
    g = reg.gauge("queue_depth")
    g.set(5)
    g.inc(-2)
    assert g.value == 3.0
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 10.0):
        h.observe(v)
    assert h.count == 3
    assert h.counts == [1, 1, 1]  # one per bucket incl. +Inf
    assert h.sum == pytest.approx(10.55)


def test_registry_get_or_create_and_kind_conflict():
    reg = telemetry.MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    assert reg.counter("x", labels={"a": 1}) is not reg.counter("x")
    with pytest.raises(ValueError):
        reg.gauge("x")
    reg.reset()
    assert len(reg) == 0
    reg.gauge("x")  # after reset the name is free for another kind


def test_histogram_rejects_unsorted_buckets():
    with pytest.raises(ValueError):
        telemetry.Histogram("bad", buckets=(1.0, 0.5))


def test_histogram_quantiles():
    h = telemetry.Histogram("lat", buckets=(1.0, 2.0, 4.0))
    for v in (0.5,) * 50 + (1.5,) * 50:
        h.observe(v)
    assert 0.0 < h.quantile(0.25) <= 1.0
    assert 1.0 < h.quantile(0.95) <= 2.0
    # +Inf overflow clamps to the last finite bound
    h2 = telemetry.Histogram("lat2", buckets=(1.0,))
    h2.observe(100.0)
    assert h2.quantile(0.99) == 1.0
    assert telemetry.Histogram("lat3", buckets=(1.0,)).quantile(0.5) == 0.0


def test_snapshot_shape_and_json_safety():
    reg = telemetry.MetricsRegistry()
    reg.counter("c").inc()
    reg.gauge("g", labels={"replica": 0}).set(2)
    reg.histogram("h", buckets=(1.0, 2.0)).observe(1.5)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["counters"]["c"] == 1.0
    assert snap["gauges"]['g{replica="0"}'] == 2.0
    h = snap["histograms"]["h"]
    assert h["count"] == 1 and h["sum"] == 1.5
    assert h["buckets"] == [[1.0, 0], [2.0, 1], ["+Inf", 1]]


def test_exposition_text_format():
    reg = telemetry.MetricsRegistry()
    reg.counter("rounds_total", help="total rounds").inc(4)
    reg.histogram("verify_seconds", buckets=(0.5, 1.0)).observe(0.7)
    text = reg.exposition()
    assert "# HELP repro_rounds_total total rounds" in text
    assert "# TYPE repro_rounds_total counter" in text
    assert "repro_rounds_total 4.0" in text
    assert 'repro_verify_seconds_bucket{le="0.5"} 0' in text
    assert 'repro_verify_seconds_bucket{le="+Inf"} 1' in text
    assert "repro_verify_seconds_count 1" in text


# ---------------------------------------------------------------------------
# enable gating: spans, observe, count
# ---------------------------------------------------------------------------


def test_span_is_noop_when_disabled():
    s1, s2 = telemetry.span("a"), telemetry.span("b")
    assert s1 is s2  # the shared null span: zero allocation when off
    with s1:
        pass
    assert len(telemetry.registry()) == 0


def test_span_records_when_enabled():
    telemetry.enable(True)
    with telemetry.span("verify", "engine_verify_seconds"):
        pass
    h = telemetry.registry().histogram("engine_verify_seconds")
    assert h.count == 1
    assert h.sum >= 0.0


def test_observe_and_count_gated():
    telemetry.observe("lat", 0.5)
    telemetry.count("c")
    assert len(telemetry.registry()) == 0
    telemetry.enable(True)
    telemetry.observe("lat", 0.5)
    telemetry.count("c", 2)
    assert telemetry.registry().counter("c").value == 2.0
    assert telemetry.registry().histogram("lat").count == 1


# ---------------------------------------------------------------------------
# trace events + flight recorder
# ---------------------------------------------------------------------------


def test_trace_event_round_trip():
    ev = telemetry.TraceEvent(
        device_id=3, round=7, t=1.25, k=4, n_accepted=2, n_commit=3,
        queue_s=0.5, verify_s=0.25, wire_s=0.125, draft_s=0.0625,
        replica=1, fallback=True,
    )
    d = ev.to_json()
    assert telemetry.TraceEvent.from_json(d) == ev
    # unknown keys (a newer producer) are ignored, not fatal
    d["future_field"] = 42
    assert telemetry.TraceEvent.from_json(d) == ev


def test_flight_recorder_is_bounded():
    fr = telemetry.FlightRecorder(capacity=4)
    fr.extend(
        telemetry.TraceEvent(device_id=0, round=i, t=float(i), k=1,
                             n_accepted=1, n_commit=2)
        for i in range(10)
    )
    assert len(fr) == 4
    rounds = [ev.round for ev in fr.events()]
    assert rounds == [6, 7, 8, 9]  # oldest evicted, dump oldest-first
    assert [d["round"] for d in fr.dump()] == rounds
    fr.clear()
    assert len(fr) == 0
    with pytest.raises(ValueError):
        telemetry.FlightRecorder(capacity=0)


# ---------------------------------------------------------------------------
# stats merge edge cases
# ---------------------------------------------------------------------------


def _engine_stats(**kw) -> EngineStats:
    base = dict(
        wstgr=0.0, per_device_rate=0.0, server_busy_frac=0.0, rounds=0,
        timeouts=0, fallback_tokens=0, mean_batch_fill=0.0,
        mean_round_latency=0.0, server_rounds_per_s=0.0,
    )
    base.update(kw)
    return EngineStats(**base)


def test_engine_stats_merge_empty_raises():
    with pytest.raises(ValueError):
        EngineStats.merge([])


def test_engine_stats_merge_single_is_identity_copy():
    st = _engine_stats(wstgr=10.0, per_device_rate=5.0, rounds=3,
                      mean_batch_fill=2.0, acceptance_rate=0.5)
    merged = EngineStats.merge([st])
    assert merged == st
    assert merged is not st  # a copy: mutating it can't corrupt the source


def test_engine_stats_merge_idle_replicas():
    """All-idle replicas (0 rounds) must not divide by zero; means fall back
    to the plain average."""
    a = _engine_stats(mean_batch_fill=2.0)
    b = _engine_stats(mean_batch_fill=4.0)
    merged = EngineStats.merge([a, b])
    assert merged.rounds == 0
    assert merged.mean_batch_fill == pytest.approx(3.0)
    assert merged.wstgr == 0.0


def test_engine_stats_merge_weighted_by_rounds():
    a = _engine_stats(wstgr=10.0, per_device_rate=5.0, rounds=30,
                      mean_batch_fill=3.0, acceptance_rate=0.9)
    idle = _engine_stats()  # an empty-field replica rides along harmlessly
    merged = EngineStats.merge([a, idle])
    assert merged.wstgr == 10.0
    assert merged.mean_batch_fill == pytest.approx(3.0)
    assert merged.acceptance_rate == pytest.approx(0.9)
    assert merged.rounds == 30


def test_client_stats_merge_empty_and_single():
    empty = ClientStats.merge([])
    assert empty.device_id == -1 and empty.rounds == 0
    one = ClientStats(device_id=4, rounds=7, committed=24, k_final=3,
                      k_mean=2.5, wall_seconds=1.5)
    merged = ClientStats.merge([one])
    assert merged.rounds == 7 and merged.committed == 24
    assert merged.k_final == 3 and merged.k_mean == 2.5
    assert merged.wall_seconds == 1.5
    assert merged.device_id == -1  # merged records are fleet-level


def test_client_stats_merge_zero_token_streams():
    """Streams that never committed anything merge without division errors."""
    zeros = [ClientStats(device_id=i) for i in range(3)]
    merged = ClientStats.merge(zeros)
    assert merged.committed == 0 and merged.rounds == 0
    assert merged.k_mean == 0.0 and merged.wall_seconds == 0.0


# ---------------------------------------------------------------------------
# codec v3: server-timing fields + telemetry payload, bit-exact round trips
# ---------------------------------------------------------------------------


def _round_trip(msg):
    decoded, consumed = codec.decode_frame(codec.encode_frame(msg))
    assert consumed == len(codec.encode_frame(msg))
    return decoded


def test_verdict_carries_server_timing_bit_exact():
    # f32-representable values survive the wire without rounding
    v = codec.Verdict(
        device_id=2, seq=5, n_accepted=3,
        tokens=np.asarray([7, 8, 9, 10], np.int32), next_prev=10,
        accept_rate=0.75, queue_depth=2, queue_s=0.5, verify_s=0.25,
    )
    out = _round_trip(v)
    assert out.queue_s == 0.5 and out.verify_s == 0.25
    assert out.n_accepted == 3 and list(out.tokens) == [7, 8, 9, 10]


def test_verdict_timing_defaults_to_zero():
    out = _round_trip(codec.Verdict(
        device_id=0, seq=0, n_accepted=1, tokens=np.asarray([1], np.int32),
        next_prev=1, accept_rate=1.0, queue_depth=0,
    ))
    assert out.queue_s == 0.0 and out.verify_s == 0.0


def test_step_reply_verdict_rec_timing():
    rec = codec.VerdictRec(
        device_id=1, n_accepted=2, tokens=np.asarray([3, 4, 5], np.int32),
        next_prev=5, accept_rate=0.5, queue_depth=1,
        queue_s=0.125, verify_s=0.0625,
    )
    out = _round_trip(codec.StepReply(verdicts=(rec,), queue_depth=1,
                                      n_free=2, hint=None))
    got = out.verdicts[0]
    assert got.queue_s == 0.125 and got.verify_s == 0.0625
    assert list(got.tokens) == [3, 4, 5]


def test_replica_stats_telemetry_payload_round_trip():
    payload = {
        "snapshot": {
            "counters": {"engine_fallback_rounds_total": 2.0},
            "gauges": {},
            "histograms": {
                "engine_verify_seconds": {
                    "sum": 0.75, "count": 3, "mean": 0.25,
                    "p50": 0.25, "p95": 0.5,
                    "buckets": [[0.5, 2], ["+Inf", 3]],
                },
            },
        },
        "flight": [telemetry.TraceEvent(device_id=0, round=1, t=0.5, k=4,
                                        n_accepted=3, n_commit=4).to_json()],
    }
    msg = codec.ReplicaStats(
        stats_json=json.dumps({"rounds": 3}),
        telemetry_json=json.dumps(payload),
    )
    out = _round_trip(msg)
    assert out.stats_json == msg.stats_json  # bit-exact: strings, not floats
    assert out.telemetry_json == msg.telemetry_json
    assert json.loads(out.telemetry_json) == payload


def test_replica_stats_empty_telemetry_default():
    out = _round_trip(codec.ReplicaStats(stats_json='{"rounds": 1}'))
    assert out.telemetry_json == ""


# ---------------------------------------------------------------------------
# logging setup
# ---------------------------------------------------------------------------


def test_setup_logging_idempotent_and_leveled():
    root = telemetry.setup_logging("debug")
    assert root.name == "repro"
    assert root.level == logging.DEBUG
    n = len(root.handlers)
    telemetry.setup_logging("info")
    assert len(root.handlers) == n  # no handler stacking
    assert root.level == logging.INFO
    assert not root.propagate
    with pytest.raises(ValueError):
        telemetry.setup_logging("chatty")


# ---------------------------------------------------------------------------
# end-to-end: tokens are identical with telemetry on, and the payload parses
# ---------------------------------------------------------------------------


def _tiny_spec(**kw):
    from repro.api import ModelSpec, ServeSpec

    return ServeSpec(
        backend="engine",
        model=ModelSpec(vocab_size=64, draft_layers=1, seed=0),
        devices=2, prompt_len=6, max_new=6, k_max=3, max_len=32,
        **kw,
    )


def test_serve_token_identical_with_telemetry_on():
    from repro.api import System, build_models

    models = build_models(_tiny_spec().model)
    telemetry.enable(False)
    off = System.build(_tiny_spec(), models=models).serve()
    on_sys = System.build(_tiny_spec(telemetry=True), models=models,
                          steps=None, kit=None)
    assert telemetry.enabled()  # the spec flipped collection on
    on = on_sys.serve()
    assert on.outputs == off.outputs  # observation-only: streams identical
    # the payload is a parseable snapshot with the engine spans populated
    snap = json.loads(json.dumps(on.telemetry))["snapshot"]
    assert snap["histograms"]["engine_verify_seconds"]["count"] > 0
    assert snap["histograms"]["engine_round_latency_seconds"]["count"] > 0
    # per-session traces attribute every round
    for s in on.sessions:
        assert len(s.trace) == s.rounds
        assert all(ev.verify_s > 0.0 for ev in s.trace)
    assert all(not s.trace for s in off.sessions)
    # registry text exposition renders and is prefixed
    text = telemetry.registry().exposition()
    assert "repro_engine_verify_seconds_count" in text
