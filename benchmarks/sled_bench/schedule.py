"""Seeded open-loop schedule of device streams for one run.

One general generator reads a traffic file (``traffic/<mix>.json``, with
any overrides from ``cells/<workload>.json``) and returns every stream the
emulated fleet will play.  The server process and the fleet process each
call :func:`build` with the same arguments and get the same schedule.

Every seed gets the same multiset of sizes and gaps, in another order:
sizes are the distribution's quantiles at ``(i + 0.5) / n``, shuffled by
the seed, so seeds change which stream gets which size and when, but not
how much work a run holds.  Token ids are drawn from the seed.  A cell
whose few streams make that order decide its tails sets
``schedule_seed``: the order then comes from that fixed seed, and the
run's seed draws only the token ids.

Two kinds of stream:

* arrivals, due at a time inside the window (open loop: their time to first
  token counts from when they were due, not from when they were sent);
* pre-admitted streams, which stand for the streams a server at steady
  state already holds when the window opens.  Their number is Little's law
  (arrival rate x mean stream life, at most the pool's slots); each has a
  residual life drawn from the length-biased residual of the answer
  distribution, and a phase within its round period.  A round's period is
  the drafting time plus ``round_trip_s``, the cell's mean round trip as
  measured on the chip at its rate: fixed data, so that a faster server
  gets the same work.  The first ``echo``
  of them draft the target's own greedy continuation (computed by the
  server during set-up), so the window also verifies accepted drafts.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Stream:
    sid: int  # device id on the wire
    prompt: np.ndarray  # int32 token ids, length a multiple of round_to
    rounds: int  # verify rounds this stream asks for
    due: Optional[float]  # seconds after the window opens; None: pre-admitted
    phase: float = 0.0  # pre-admitted: first draft is sent at this offset
    echo: bool = False  # drafts the target's greedy continuation
    draft_s: float = 0.0  # seconds one round's drafting takes on the device
    k: int = 4  # draft tokens per round


@dataclasses.dataclass
class Schedule:
    streams: List[Stream]
    seconds: float
    round_period_s: float  # draft time + the cell's measured mean round trip

    @property
    def pre_admitted(self) -> List[Stream]:
        return [s for s in self.streams if s.due is None]

    @property
    def arrivals(self) -> List[Stream]:
        return [s for s in self.streams if s.due is not None]

    def prompt_lengths(self) -> List[int]:
        return sorted({int(s.prompt.size) for s in self.streams})


def merge(base: dict, over: dict) -> dict:
    """Recursive dict merge: ``over`` wins (cell overrides on a traffic mix)."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / max(n, 1)


def _quantiles(dist: dict, u: np.ndarray) -> np.ndarray:
    """Inverse CDF of a size distribution at ``u``, clipped to [min, max]."""
    kind = dist["dist"]
    if kind == "lognormal":
        from statistics import NormalDist

        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown size distribution {kind!r}")
    return np.clip(x, dist["min"], dist["max"])


def _round_up(x: np.ndarray, to: int) -> np.ndarray:
    return (np.ceil(x / to) * to).astype(np.int64)


def _answer_pmf(dist: dict) -> Dict[int, float]:
    """Discrete answer-length distribution (rounds), from fine quantiles."""
    vals = np.rint(_quantiles(dist, _strata(4096))).astype(np.int64)
    uniq, counts = np.unique(vals, return_counts=True)
    return {int(v): c / vals.size for v, c in zip(uniq, counts)}


def build(traffic: dict, serving: dict, vocab: int, seed: int, seconds: float) -> Schedule:
    rng = np.random.default_rng(traffic.get("schedule_seed", seed))  # sizes, gaps, phases
    tok = np.random.default_rng(seed) if "schedule_seed" in traffic else rng  # token ids
    dev = traffic["device"]
    k = int(serving["k_max"])
    draft_s = k / float(dev["draft_rate"])
    period = draft_s + float(traffic["round_trip_s"])
    prompt_d, answer_d = traffic["prompt"], traffic["answer_rounds"]
    round_to = int(prompt_d["round_to"])

    # -- arrivals inside the window ------------------------------------------
    arr = traffic["arrivals"]
    if arr["kind"] == "poisson":
        rate = float(arr["rate_per_s"])
        n = int(round(rate * seconds))
        gaps = -np.log1p(-_strata(n)) / rate  # exponential quantiles
        gaps = rng.permutation(gaps)
        due = np.cumsum(gaps) - gaps[0] * rng.random()
        due = due * (seconds / max(due[-1] + gaps.mean(), 1e-9)) if n else due
    elif arr["kind"] == "bursts":
        every, size = float(arr["every_s"]), int(arr["size"])
        rate = size / every
        start = rng.random() * every
        times = np.arange(start, seconds, every)
        due = np.repeat(times, size)
        n = due.size
    else:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")

    def sizes(n_: int):
        p = _round_up(_quantiles(prompt_d, rng.permutation(_strata(n_))), round_to)
        a = np.rint(_quantiles(answer_d, rng.permutation(_strata(n_)))).astype(np.int64)
        return p, a

    plen, rounds = sizes(n)

    # -- streams the server holds when the window opens ----------------------
    pmf = _answer_pmf(answer_d)
    mean_rounds = sum(r * p for r, p in pmf.items())
    life = mean_rounds * period + draft_s
    n_pre = min(int(round(rate * life)), int(serving["n_slots"]))
    # residual rounds of a stream caught in progress: P(R = r) ~ P(N >= r)
    rs = np.arange(1, max(pmf) + 1)
    surv = np.array([sum(p for v, p in pmf.items() if v >= r) for r in rs])
    cdf = np.cumsum(surv) / surv.sum()
    echo = traffic.get("echo", {"streams": 0, "rounds": 0})
    n_echo = min(int(echo["streams"]), n_pre)
    resid = rs[np.searchsorted(cdf, rng.permutation(_strata(n_pre - n_echo)))]
    resid = np.concatenate([np.full(n_echo, int(echo["rounds"])), resid])
    pre_plen = _round_up(_quantiles(prompt_d, rng.permutation(_strata(n_pre))), round_to)
    phases = rng.permutation(_strata(n_pre)) * period

    streams: List[Stream] = []
    sid = 0
    for j in range(n_pre):
        is_echo = j < n_echo
        streams.append(Stream(
            sid=sid,
            prompt=tok.integers(0, vocab, int(pre_plen[j]), dtype=np.int64).astype(np.int32),
            rounds=int(resid[j]),
            due=None, phase=float(phases[j]), echo=is_echo, draft_s=draft_s, k=k,
        ))
        sid += 1
    for i in range(n):
        streams.append(Stream(
            sid=sid,
            prompt=tok.integers(0, vocab, int(plen[i]), dtype=np.int64).astype(np.int32),
            rounds=int(rounds[i]), due=float(due[i]), draft_s=draft_s, k=k,
        ))
        sid += 1
    return Schedule(streams=streams, seconds=float(seconds), round_period_s=period)


def max_tokens(s: Stream) -> int:
    """Most positions a stream can hold: prompt, every accepted draft, bonus."""
    per_round = s.k + 1 if s.echo else 1
    return int(s.prompt.size) + s.rounds * per_round + s.k + 1


def check_fits(schedule: Schedule, max_len: int) -> None:
    worst = max(max_tokens(s) for s in schedule.streams)
    if worst > max_len:
        raise ValueError(f"a stream can reach {worst} positions; the pool rows hold {max_len}")


def percentile(vals, q: float) -> float:
    """Linear-interpolated percentile; NaN for an empty sample."""
    if len(vals) == 0:
        return math.nan
    return float(np.percentile(np.asarray(vals, np.float64), q))
