"""The control: the plain reference put in the program's place at int8
(weights per output channel, activations per row, int32 accumulation),
the precision below the bf16 the configuration states.  At each served
position the token it puts first is scored by the float32 reference and
held to the same limit as the program's own tokens, through the same
check, so the control run must report ``correct`` false while the
program, in the same run, stays under the limit.  The sample is widened so
that the int8 forward meets enough near-ties at the tiny size.  (On the
chip, at each cell's size, ``run.py --control 1`` makes the same run;
PERF.md has its readings.)"""
import time

from helpers import tiny_spec
import run


def test_control_fails_where_the_program_passes(monkeypatch):
    monkeypatch.setattr(run, "SAMPLE_TOKENS", 10_000)
    monkeypatch.setattr(run, "SAMPLE_STREAMS", 200)
    spec = tiny_spec(rate=8.0)
    res = run.run(spec, seed=22, seconds=8.0, trace=False, control=True, t_start=time.monotonic())
    limit = spec["config"]["check"]["served_gap_max"]
    assert not res["correct"], res["check"]
    assert res["check"]["served_gap_max"]["value"] > limit
    assert res["_extra"]["program"]["served_gap_max"] <= limit, res["_extra"]
