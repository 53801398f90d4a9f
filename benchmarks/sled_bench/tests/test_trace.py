"""The trace reducer, on a small trace recorded on a TPU v5e by
``record_trace.py`` (the harness at the tiny size, one traced second)."""
import pytest

from helpers import HERE
import run

trace = run.load_file("trace.py")
DATA = HERE / "data" / "tiny.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return trace.load(DATA)


def test_window_and_busy(tr):
    assert 0.9 < tr.window_s < 1.5
    assert 0 < tr.busy_s < tr.window_s
    for iv in tr.busy:
        assert all(a < b for a, b in iv)
        assert all(b1 <= a2 for (_, b1), (a2, _) in zip(iv, iv[1:]))  # merged, sorted
        assert tr.window[0] <= iv[0][0] and iv[-1][1] <= tr.window[1]


def test_programs_of_the_served_path(tr):
    verify = tr.module_seconds("jit_paged_verify_step")
    assert verify and all(0 < s < 0.1 for s in verify)
    assert all(tr.window[0] <= a < tr.window[1] for a, _ in tr.modules["jit_paged_verify_step"])


def test_idle_gaps_add_up(tr):
    gaps = tr.idle_gaps(n=1000)
    idle = sum(s for _, s in gaps)
    assert idle == pytest.approx(tr.window_s - tr.busy_s, rel=1e-6)
    assert any(name.startswith("inside jit_paged_verify_step") for name, _ in gaps)
    assert len(tr.idle_gaps()) <= 10


def test_top_ops(tr):
    top = tr.top_ops()
    assert 0 < len(top) <= 10
    assert all(s > 0 for _, s in top)
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    assert sum(tr.ops.values()) * 1e-9 >= tr.busy_s * 0.999  # ops may overlap, never fall short


def test_readers_on_the_trace(tr):
    import types

    ctx = types.SimpleNamespace(trace=tr)
    assert 0 < run.read_metric("device_idle_share.rtt", ctx) < 100
    assert run.read_metric("verify_step_device_ms.rtt", ctx) > 0
    ctx.trace = None
    assert run.read_metric("verify_step_device_ms.rtt", ctx) is None  # nothing to read: no number
