"""The seeded schedule generator: same seed, same schedule; every seed the
same work in another order; the steady-state pre-admission."""
import json

import numpy as np
import pytest

from helpers import BENCH, tiny_spec
import schedule

SERVING = {"n_slots": 128, "max_len": 768, "k_max": 4}


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def steady(rate=10.0, round_trip=0.15):
    return schedule.merge(mix("jetson-steady"),
                          {"arrivals": {"rate_per_s": rate}, "round_trip_s": round_trip})


def bursts():
    """Bursts of 24 long prompts every 3 s: the generator's second arrival kind."""
    return schedule.merge(steady(), {
        "arrivals": {"kind": "bursts", "every_s": 3.0, "size": 24},
        "prompt": {"dist": "uniform", "min": 384, "max": 512},
        "answer_rounds": {"dist": "uniform", "min": 8, "max": 16},
    })


def sizes(s):
    return sorted(x.prompt.size for x in s.arrivals), sorted(x.rounds for x in s.arrivals)


def test_same_seed_same_schedule():
    a = schedule.build(steady(), SERVING, 151936, 2**31 + 99, 30.0)
    b = schedule.build(steady(), SERVING, 151936, 2**31 + 99, 30.0)
    assert [(s.sid, s.due, s.rounds) for s in a.streams] == [(s.sid, s.due, s.rounds) for s in b.streams]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a.streams, b.streams))


@pytest.mark.parametrize("name", ["jetson-steady", "bursts"])
def test_seeds_share_the_work(name):
    traffic = steady() if name == "jetson-steady" else bursts()
    a = schedule.build(traffic, SERVING, 151936, 1, 30.0)
    b = schedule.build(traffic, SERVING, 151936, 4_000_000_007, 30.0)
    assert sizes(a) == sizes(b)
    assert sorted(s.rounds for s in a.pre_admitted) == sorted(s.rounds for s in b.pre_admitted)
    assert [s.due for s in a.arrivals] != [s.due for s in b.arrivals] or name != "jetson-steady"


def test_schedule_seed_fixes_the_order_and_the_seed_draws_tokens():
    traffic = schedule.merge(steady(0.32), {"schedule_seed": 0})
    a = schedule.build(traffic, SERVING, 32064, 1, 51.0)
    b = schedule.build(traffic, SERVING, 32064, 4_000_000_007, 51.0)
    assert [(s.due, s.rounds, s.prompt.size, s.phase) for s in a.streams] == \
        [(s.due, s.rounds, s.prompt.size, s.phase) for s in b.streams]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a.streams, b.streams))


def test_arrivals_inside_the_window_and_rounded():
    s = schedule.build(steady(), SERVING, 151936, 5, 30.0)
    due = [x.due for x in s.arrivals]
    assert len(due) == 300 and min(due) >= 0 and max(due) < 30.0
    assert {x.prompt.size % 64 for x in s.streams} == {0}
    assert set(s.prompt_lengths()) <= set(range(64, 513, 64))
    assert all(8 <= x.rounds <= 128 for x in s.arrivals)


def test_preadmission_follows_littles_law():
    traffic = steady(10.0)
    s = schedule.build(traffic, SERVING, 151936, 5, 30.0)
    period = 4 / 21.0 + traffic["round_trip_s"]
    n = len(s.pre_admitted)
    mean_rounds = np.mean([x.rounds for x in schedule.build(steady(100.0), SERVING, 151936, 5, 30.0).arrivals])
    assert abs(n - 10.0 * (mean_rounds * period + 4 / 21.0)) < 0.05 * n
    assert all(0 <= x.phase < period for x in s.pre_admitted)
    assert sum(x.echo for x in s.pre_admitted) == traffic["echo"]["streams"]
    full = schedule.build(steady(50.0), SERVING, 151936, 5, 30.0)
    assert len(full.pre_admitted) == SERVING["n_slots"]


def test_bursts():
    s = schedule.build(bursts(), SERVING, 151936, 8, 30.0)
    due = np.array([x.due for x in s.arrivals])
    times, counts = np.unique(due, return_counts=True)
    assert set(counts) == {24} and np.allclose(np.diff(times), 3.0)
    assert s.prompt_lengths() == [448, 512]  # uniform on (384, 512], rounded up


def test_rows_hold_every_stream():
    for traffic in (steady(), bursts()):
        schedule.check_fits(schedule.build(traffic, SERVING, 151936, 3, 30.0), SERVING["max_len"])
    with pytest.raises(ValueError):
        schedule.check_fits(schedule.build(steady(), SERVING, 151936, 3, 30.0), 256)


def test_tiny_spec_builds():
    spec = tiny_spec()
    s = schedule.build(spec["traffic"], spec["serving"], 256, 1, 4.0)
    schedule.check_fits(s, spec["serving"]["max_len"])
    assert s.arrivals and s.pre_admitted
