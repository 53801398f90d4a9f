"""The benchmark's readers of the program's ``sled.*`` spans
(``benchmarks/sled_bench/metrics``), on a hand-built trace whose every
interval is known, and on a recorded trace of a program without the spans.

Times below are in units of 0.1 ms on the profiler's clock; the traced
window is 1000 units (0.1 s).  Device 0 runs the verify program twice and
the prefill program once.  Its idle time, 560 units, falls:

* inside the first verify run (10 units);
* before the first run, under ``sled.await_work`` (100), and between the
  second verify run and the prefill, under ``sled.await_work`` (150);
* between the verify runs, under ``sled.recv`` (200), and after the
  prefill, under no span at all (100): the host's share, 300 units.
"""

import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "sled_bench"
sys.path.insert(0, str(BENCH))

import run  # noqa: E402  (the harness: finds a reader by its metric's name)

trace = run.load_file("trace.py")
U = 1e5  # ns in one unit
T_OPEN = 50.0  # the window's start on the host's monotonic clock, seconds
VERIFY = "jit_paged_verify_step"
NEW = ("idle_host_share.rtt", "verify_call_host_ms.rtt", "loop_host_ms_per_verify.rtt",
       "rtt_unaccounted_mean_ms.rtt")

SPANS = [  # (name, start, end) in units
    ("sled.window", 0, 1000),
    ("sled.await_work", 0, 80),
    ("sled.recv", 60, 70),
    ("sled.step", 80, 345),  # the harness's own span around the engine's step
    ("sled.plan", 80, 90),
    ("sled.verify", 90, 320),
    ("sled.pack", 90, 95),
    ("sled.launch", 95, 100),
    ("sled.sync", 300, 320),
    ("sled.commit", 320, 340),
    ("sled.send", 340, 360),
    ("sled.recv", 380, 420),
    ("sled.retire", 400, 410),  # harness span inside the program's recv
    ("sled.plan", 480, 490),
    ("sled.verify", 490, 720),
    ("sled.commit", 720, 730),
    ("sled.send", 730, 740),
    ("sled.await_work", 745, 840),
    ("sled.prefill", 840, 905),
]


def _trace(spans=SPANS):
    u = lambda *xs: tuple(x * U for x in xs)  # noqa: E731
    runs = [(*u(100, 300), VERIFY), (*u(500, 700), VERIFY), (*u(850, 900), "jit_prefill_step")]
    return trace.Trace(
        window=u(0, 1000),
        modules={VERIFY: [u(100, 300), u(500, 700)], "jit_prefill_step": [u(850, 900)]},
        busy=[[u(100, 150), u(160, 300), u(500, 700), u(850, 900)]],
        ops={},
        spans=[(n, a * U, b * U) for n, a, b in spans],
        runs=runs,
        host=[],
    )


def _ctx(tr):
    at = lambda units: T_OPEN + units * U * 1e-9  # noqa: E731  host clock of a profiler time
    return types.SimpleNamespace(
        trace=tr, t_open=T_OPEN, t_close=at(1000),
        # [t_send, t_recv, queue_s, n_tokens, n_accepted, sid]; the last ends after the window
        fleet={"rounds": [[T_OPEN, T_OPEN + 0.05, 0.002, 3, 0, 1],
                          [T_OPEN + 0.02, T_OPEN + 0.09, 0.004, 3, 0, 2],
                          [T_OPEN + 0.09, T_OPEN + 0.2, 0.5, 3, 0, 3]]},
        traced_rounds=[types.SimpleNamespace(t=at(92), size=3), types.SimpleNamespace(t=at(495), size=1)],
    )


def test_readers_on_known_intervals():
    ctx = _ctx(_trace())
    got = {name: run.read_metric(name, ctx) for name in NEW}
    assert got["idle_host_share.rtt"] == pytest.approx(30.0)  # 300 of 1000 units
    assert got["verify_call_host_ms.rtt"] == pytest.approx(3.0)  # 230 - 200 units, both calls
    # recv 10 + 40, plan 10 + 10, commit 20 + 10, send 20 + 10 units over 2 calls
    assert got["loop_host_ms_per_verify.rtt"] == pytest.approx(6.5)
    # 60 ms round trip - 3 ms queue - (3 x 27 + 1 x 25) / 4 ms a call
    assert got["rtt_unaccounted_mean_ms.rtt"] == pytest.approx(30.5)


def test_calls_match_their_verify_span_across_clock_skew():
    """The harness stamps a call microseconds after its span opens, on a
    clock that may run a little behind the profiler's: a call stamped 0.2
    units (20 us) before its span still weighs that span."""
    ctx = _ctx(_trace())
    early = [types.SimpleNamespace(t=r.t - 2.2e-4, size=r.size) for r in ctx.traced_rounds]
    assert early[0].t < T_OPEN + 90 * U * 1e-9  # before the first sled.verify opens
    ctx.traced_rounds = early
    assert run.read_metric("rtt_unaccounted_mean_ms.rtt", ctx) == pytest.approx(30.5)


def _idle_by_kind(tr):
    """Seconds of idle inside programs and under sled.await_work, from the
    trace's own breakdown."""
    inside = waiting = 0.0
    for label, seconds in tr.idle_gaps(n=1 << 30):
        if label.startswith("inside "):
            inside += seconds
        elif label.startswith("sled.await_work"):
            waiting += seconds
    return inside, waiting


@pytest.mark.parametrize("spans", [SPANS, [s for s in SPANS if s[0] != "sled.await_work"]],
                         ids=["waits", "never-waits"])
def test_idle_shares_add_up(spans):
    tr = _trace(spans)
    ctx = _ctx(tr)
    inside, waiting = _idle_by_kind(tr)
    assert inside == pytest.approx(10 * U * 1e-9)
    total = run.read_metric("device_idle_share.rtt", ctx)
    host = run.read_metric("idle_host_share.rtt", ctx)
    assert total == pytest.approx(56.0)
    assert abs(total - (host + 100 * (inside + waiting) / tr.window_s)) < 1e-9


def test_nothing_to_read_without_the_program_spans():
    harness = {"sled.window", "sled.step", "sled.retire"}
    ctx = _ctx(_trace([s for s in SPANS if s[0] in harness]))
    assert run.read_metric("device_idle_share.rtt", ctx) == pytest.approx(56.0)
    assert all(run.read_metric(name, ctx) is None for name in NEW)
    ctx.trace = None
    assert all(run.read_metric(name, ctx) is None for name in NEW)


def test_nothing_to_read_on_a_recorded_trace_without_them():
    """A v5e trace of a program whose only spans are the harness's."""
    tr = trace.load(BENCH / "tests" / "data" / "tiny.xplane.pb")
    ctx = types.SimpleNamespace(trace=tr, t_open=0.0, t_close=1.0, fleet={"rounds": []},
                                traced_rounds=[])
    assert tr.module_seconds(VERIFY)
    assert all(run.read_metric(name, ctx) is None for name in NEW)
