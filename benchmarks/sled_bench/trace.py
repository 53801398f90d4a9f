"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Read with ``jax.profiler.ProfileData`` alone.  What a TPU trace holds:

* one plane per chip, ``/device:TPU:<i>``, with the lines ``XLA Modules``
  (one event per run of a compiled program, named ``jit_<fn>(<hash>)``)
  and ``XLA Ops`` (one event per HLO op on the device);
* ``/host:CPU``, whose Python thread carries the benchmark's own
  ``TraceAnnotation`` spans (``sled.*``).  Host and device events share one
  clock: nanoseconds from the start of the profile.

The measured window is the host span ``sled.window``; every device event is
clipped to it.  Busy time is the union of the ``XLA Ops`` intervals of a
chip, idle share is one minus busy over the window, averaged over the
chips that ran anything.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "sled.window"
SPAN_PREFIX = "sled."
_HASH = re.compile(r"\(\d+\)$")
_OP = re.compile(r"^%?([^\s=]+)")

Interval = Tuple[float, float]


@dataclasses.dataclass
class Trace:
    window: Interval  # ns
    modules: Dict[str, List[Interval]]  # program name -> runs inside the window (device 0)
    busy: List[List[Interval]]  # per chip that ran anything: merged op intervals
    ops: Dict[str, float]  # "<program>/<op>" -> ns inside the window (device 0)
    spans: List[Tuple[str, float, float]]  # host sled.* spans
    runs: List[Tuple[float, float, str]]  # every program run on device 0
    host: List[Tuple[str, float, float]]  # runtime events of the host's main threads

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips used."""
        if not self.busy:
            return 0.0
        return sum(sum(b - a for a, b in iv) for iv in self.busy) / len(self.busy) * 1e-9

    def module_seconds(self, name: str) -> List[float]:
        return [(b - a) * 1e-9 for a, b in self.modules.get(name, [])]

    def top_ops(self, n: int = 10) -> List[list]:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """Idle time of device 0 in the window, summed by where each gap
        falls: inside a run of a program (the device waits within it), or
        between programs, labelled by what the host was doing at the gap's
        middle: the innermost ``sled.*`` span there, and the innermost
        runtime event of the host's main threads (``>`` between them)."""
        if not self.busy:
            return []
        lo, hi = self.window
        edges = [lo] + [x for iv in self.busy[0] for x in iv] + [hi]
        runs = sorted(self.runs)
        sweeps = [_Sweep(s for s in self.spans if s[0] != WINDOW_SPAN), _Sweep(self.host)]
        by: Dict[str, List[float]] = collections.defaultdict(list)
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            owner = _owner(runs, mid)
            if owner != "?":
                by[f"inside {owner}"].append(b - a)
                continue
            ours, rt = (sw.innermost(mid) for sw in sweeps)
            label = (ours or "no sled span") + (f" > {rt}" if rt else "")
            by[label].append(b - a)
        rows = sorted(by.items(), key=lambda kv: -sum(kv[1]))[:n]
        return [[f"{k} ({len(v)} gaps)", sum(v) * 1e-9] for k, v in rows]


class _Sweep:
    """Innermost covering interval at increasing query times."""

    def __init__(self, events):
        self.events = sorted(events, key=lambda e: e[1])
        self.active: List[Tuple[str, float, float]] = []
        self.j = 0

    def innermost(self, t: float) -> Optional[str]:
        while self.j < len(self.events) and self.events[self.j][1] <= t:
            self.active.append(self.events[self.j])
            self.j += 1
        self.active = [e for e in self.active if e[2] > t]
        return max(self.active, key=lambda e: e[1])[0] if self.active else None


def _merge(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(a: float, b: float, lo: float, hi: float) -> Optional[Interval]:
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def find_xplane(trace_dir: Path) -> Path:
    paths = sorted(glob.glob(str(Path(trace_dir) / "plugins/profile/*/*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(paths[-1])


def load(path: Path) -> Trace:
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    spans: List[Tuple[str, float, float]] = []
    host: List[Tuple[str, float, float]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events
                       if not ev.name.startswith("$")]  # "$..." are Python-tracer frames
                ours = [e for e in evs if e[0].startswith(SPAN_PREFIX)]
                spans.extend(ours)
                if ours or line.name.startswith("main"):
                    host.extend(e for e in evs if not e[0].startswith(SPAN_PREFIX))
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span in the trace, found {len(win)}")
    lo, hi = win[0][1], win[0][2]
    devices.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    busy: List[List[Interval]] = []
    runs: List[Tuple[float, float, str]] = []
    modules: Dict[str, List[Interval]] = collections.defaultdict(list)
    ops: Dict[str, float] = collections.defaultdict(float)
    for i, plane in enumerate(devices):
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = sorted(
            (ev.start_ns, ev.start_ns + ev.duration_ns, _HASH.sub("", ev.name))
            for ev in lines.get("XLA Modules", [])
        )
        iv = []
        for ev in lines.get("XLA Ops", []):
            c = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
            if c is None:
                continue
            iv.append(c)
            if i == 0:
                ops[f"{_owner(mods, ev.start_ns)}/{_OP.match(ev.name).group(1)}"] += c[1] - c[0]
        if iv:
            busy.append(_merge(iv))
        if i == 0:
            runs = mods
            for a, b, name in mods:
                if lo <= a < hi:
                    modules[name].append((a, b))
    return Trace(window=(lo, hi), modules=dict(modules), busy=busy, ops=dict(ops), spans=spans, runs=runs,
                 host=sorted(host, key=lambda e: e[1]))


def _owner(mods: List[Tuple[float, float, str]], t: float) -> str:
    """Name of the program whose run covers time ``t`` (binary search)."""
    lo, hi = 0, len(mods)
    while lo < hi:
        mid = (lo + hi) // 2
        if mods[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and mods[lo - 1][0] <= t <= mods[lo - 1][1]:
        return mods[lo - 1][2]
    return "?"
