"""SLED verify-server benchmark: one cell, one run, one result line.

    python3 benchmarks/sled_bench/run.py --workload NAME --seed N --seconds S --trace 0|1

The server process (this one) builds the program's ``ServerEngine`` for the
cell's configuration, with weights made on the device from the seed, puts
the program's ``TransportServer`` in front of it on localhost TCP, warms up
every verify bucket and every prompt length the run's schedule holds, and
pre-admits the streams a server at steady state would hold.  An emulated
edge fleet (``fleet.py``, a process of its own, no accelerator) then plays
the seeded open-loop schedule against it for ``--seconds``.  Set-up is
everything from process start until the window opens.

After the window the run reads the device's peak memory, frees the
program's state and checks what was served against the plain reference
(``references/<family>.py``): see :func:`check`.  ``--trace 1`` runs the
window's last seconds under the JAX profiler and reports the cell's
per-layer metrics (readers in ``metrics/``) instead of its end-to-end ones.

Earlier lines of standard error give the set-up phases, the compile cache's
hits and misses, the compiles inside the window (0 expected), the peak
bytes in use and the fleet's lateness; the last ones give each number the
correctness check compared, beside its limit.  The last line of standard
output is the JSON result.  Without a TPU (or with fewer chips than the
cell asks for) the run prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

import cost  # noqa: E402
import schedule as sched_mod  # noqa: E402

OUT_DIR = ROOT / "bench_out" / "sled_bench"
SAMPLE_TOKENS = 400  # served tokens the check compares at least, where a run has them
SAMPLE_STREAMS = 16
TRACE_SECONDS = 5.0  # --trace 1 profiles this much of the window's end


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# instrumentation around the calls into the program's layers
# ---------------------------------------------------------------------------


class CompileCounter:
    """Counts programs lowered (compiled or loaded from the persistent
    cache) and persistent-cache hits and misses, through jax.monitoring."""

    def __init__(self):
        import jax

        self.lowered: List[float] = []
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered.append(time.monotonic())

    def _ev(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def between(self, a: float, b: float) -> int:
        return sum(a <= t <= b for t in self.lowered)


class Recorder:
    """Wraps the engine's layer calls: records each verify dispatch (its
    slots, draft lengths and the device array of committed counts) and
    each admission in call order, and, when tracing, wraps each call in a
    ``sled.*`` TraceAnnotation so the trace can say what the host did in
    each idle gap of the device."""

    def __init__(self, engine, traced: bool):
        import jax

        self.events: List[tuple] = []
        core = engine.core
        ann = jax.profiler.TraceAnnotation if traced else None

        def wrap(obj, name, span, after):
            inner = getattr(obj, name)

            def call(*a, **k):
                t = time.monotonic()
                if ann is None:
                    out = inner(*a, **k)
                else:
                    with ann(span):
                        out = inner(*a, **k)
                after(t, a, out)
                return out

            setattr(obj, name, call)

        wrap(core, "verify", "sled.verify_dispatch", self._verify)
        wrap(core, "prefill_slot", "sled.admit", self._admit)
        wrap(engine, "step", "sled.step", lambda *_: None)
        wrap(engine, "retire", "sled.retire", lambda *_: None)

    def _verify(self, t, args, out):
        slots, _prev, _toks, _qs, lens = args
        res, bucket, _ = out
        self.events.append(("verify", t, np.asarray(slots), np.asarray(lens), res.n_commit, bucket))

    def _admit(self, t, args, out):
        slot, prompt = args
        self.events.append(("admit", t, int(slot), int(np.shape(prompt)[0])))

    def rounds(self) -> List[SimpleNamespace]:
        """Every verify round in call order, with each real request's live
        length (the pool row's committed length when it was verified)."""
        live: Dict[int, int] = {}
        out = []
        for ev in self.events:
            if ev[0] == "admit":
                live[ev[2]] = ev[3] - 1  # the last prompt token is fed by round 1
                continue
            _, t, slots, lens, n_commit, bucket = ev
            n_commit = np.asarray(n_commit)[: slots.size]
            reqs = [(live.get(int(s), 0), int(k) + 1) for s, k in zip(slots, lens)]
            for s, n in zip(slots, n_commit):
                live[int(s)] = live.get(int(s), 0) + int(n)
            out.append(SimpleNamespace(t=t, size=int(slots.size), bucket=int(bucket), requests=reqs))
        return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def load_file(rel: str):
    """Import a file of the harness by its path under the harness (the
    files found by name: references, adapters, metric readers; and
    ``trace.py``, which shares its name with a module of the standard
    library)."""
    path = HERE / rel
    name = "sled_" + rel[:-3].replace("/", "_").replace(".", "_")
    if name not in sys.modules:
        mod_spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(mod_spec)
        sys.modules[name] = mod
        mod_spec.loader.exec_module(mod)
    return sys.modules[name]


def start_fleet() -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
    return subprocess.Popen(
        [sys.executable, str(HERE / "fleet.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, text=True,
    )


def build_engine(spec: dict, seed: int):
    import jax

    from repro.core.server_engine import ServerEngine

    config, serving = spec["config"], spec["serving"]
    ref = load_file(f"references/{config['reference']}.py")
    adapter = load_file(f"adapters/{config['reference']}.py")
    model = adapter.model(config)
    t = time.monotonic()
    params = ref.program_params(config, seed)
    jax.block_until_ready(params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), jax.eval_shape(model.init_params, jax.random.key(0)))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise ValueError("generated weights do not match the program's parameter tree")
    t_weights = time.monotonic() - t
    engine = ServerEngine(
        model, params,
        n_slots=int(serving["n_slots"]), max_len=int(serving["max_len"]),
        k_max=int(serving["k_max"]), policy=serving["policy"], greedy=True,
        kv_dtype=serving["kv_dtype"],
    )
    return engine, t_weights


def warm_and_fill(engine, schedule, vocab: int, seed: int) -> Dict[str, object]:
    """Compile every verify bucket and every prompt length of the schedule,
    compute the echo streams' greedy continuations, and pre-admit."""
    phases = {}
    t = time.monotonic()
    engine.warmup()
    phases["verify_buckets_s"] = time.monotonic() - t
    t = time.monotonic()
    probe = 1 << 30
    for n in schedule.prompt_lengths():
        engine.admit(probe, np.zeros(n, np.int32))
        engine.retire(probe)
    phases["prompt_lengths_s"] = time.monotonic() - t
    t = time.monotonic()
    echo = {}
    ech = [s for s in schedule.pre_admitted if s.echo]
    if ech:
        need = max(s.rounds * (s.k + 1) + s.k for s in ech)
        rng = np.random.default_rng([seed & 0xFFFFFFFFFFFF, 1 << 20])
        for s in ech:
            engine.admit(s.sid, s.prompt)
            echo[s.sid] = []
        while min(len(v) for v in echo.values()) < need:
            for s in ech:
                engine.submit(s.sid, rng.integers(0, vocab, s.k).astype(np.int32), 0.0)
            for v in engine.step(0.0):
                echo[v.device_id].extend(int(x) for x in v.tokens)
        for s in ech:
            engine.retire(s.sid)
    phases["echo_s"] = time.monotonic() - t
    t = time.monotonic()
    for s in schedule.pre_admitted:
        if engine.admit(s.sid, s.prompt, 0.0) is None:
            raise RuntimeError("pre-admission found the pool full")
    phases["preadmit_s"] = time.monotonic() - t
    return {"phases": phases, "echo": echo}


def host_counters() -> Dict[str, float]:
    """Seconds of this process's CPU time, of the host's CPU and memory
    pressure (PSI) and of cgroup CPU throttling, where the host has them:
    read at the window's open and close, they say whether a stall of the
    fleet was the host's."""
    import resource

    ru = resource.getrusage(resource.RUSAGE_SELF)
    out = {"server_cpu_s": ru.ru_utime + ru.ru_stime}
    for name in ("cpu", "memory"):
        try:
            for line in Path(f"/proc/pressure/{name}").read_text().splitlines():
                kind, *fields = line.split()
                out[f"{name}_pressure_{kind}_s"] = int(dict(f.split("=") for f in fields)["total"]) * 1e-6
        except OSError:
            pass
    try:
        stat = dict(line.split() for line in Path("/sys/fs/cgroup/cpu.stat").read_text().splitlines())
        out["cgroup_throttled_s"] = int(stat.get("throttled_usec", 0)) * 1e-6
        out["cgroup_throttled_n"] = float(stat.get("nr_throttled", 0))
    except OSError:
        pass
    return out


async def serve_window(engine, fleet: subprocess.Popen, hello: dict, seconds: float,
                       trace_dir: Optional[Path]) -> dict:
    import jax

    from repro.transport.links import tcp_listen
    from repro.transport.server import TransportServer

    loop = asyncio.get_running_loop()
    server = TransportServer(engine)
    listener, port = await tcp_listen(server.attach)

    async def readline() -> str:
        line = await loop.run_in_executor(None, fleet.stdout.readline)
        if not line:
            raise RuntimeError(f"the fleet process ended early (exit {fleet.poll()})")
        return line

    fleet.stdin.write(json.dumps(dict(hello, port=port)) + "\n")
    fleet.stdin.flush()
    line = await readline()
    if line.strip() != "READY":
        raise RuntimeError(f"fleet said {line!r}")
    traced = []  # (start, end) of the traced slice, on the host's clock
    window = []

    def open_trace():
        # the profiler records only the window's last TRACE_SECONDS, so the
        # trace stays small at any window length; it is written out after
        # the window, so that nothing holds the event loop inside it
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        window.append(jax.profiler.TraceAnnotation("sled.window"))  # made once the profiler runs
        window[0].__enter__()
        traced.append(time.monotonic())

    def close_trace():
        window[0].__exit__(None, None, None)
        traced.append(time.monotonic())

    host0 = host_counters()
    t_open = time.monotonic()
    if trace_dir is not None:
        loop.call_at(t_open + seconds - min(seconds, TRACE_SECONDS), open_trace)
        loop.call_at(t_open + seconds, close_trace)
    fleet.stdin.write(f"GO {t_open!r}\n")
    fleet.stdin.flush()
    records = json.loads(await readline())
    host1 = host_counters()
    log("host during the window: " + " ".join(f"{k} {host1[k] - host0[k]:.3f}" for k in host1 if k in host0))
    if trace_dir is not None:
        jax.profiler.stop_trace()
    await server.stop()
    for ep in server._endpoints:
        ep.close()
    listener.close()
    records["t_open"], records["t_close"] = t_open, t_open + seconds
    records["traced"] = traced
    if traced:
        log(f"traced slice: {traced[1] - traced[0]:.3f} s, opened "
            f"{traced[0] - (t_open + seconds - min(seconds, TRACE_SECONDS)):.3f} s late (start_trace)")
    return records


def run(spec: dict, *, seed: int, seconds: float, trace: bool, control: bool = False,
        t_start: float = T_START, device=None,
        patch: Optional[Callable] = None) -> dict:
    """One run of one cell.  ``patch(engine)`` may break the timed path
    (the tests' faults); ``device`` is the chip to read memory from."""
    import jax

    from repro.compile_cache import configure_compile_cache

    config, serving, traffic = spec["config"], spec["serving"], spec["traffic"]
    vocab = int(config["vocab_size"])
    schedule = sched_mod.build(traffic, serving, vocab, seed, seconds)
    sched_mod.check_fits(schedule, int(serving["max_len"]))
    fleet = start_fleet()
    try:
        configure_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        counter = CompileCounter()
        engine, t_weights = build_engine(spec, seed)
        if patch is not None:
            patch(engine)
        rec = Recorder(engine, traced=trace)
        prep = warm_and_fill(engine, schedule, vocab, seed)
        prep["phases"] = {"weights_s": t_weights, **prep["phases"]}
        trace_dir = None
        if trace:
            trace_dir = OUT_DIR / "trace" / spec["cell"]["name"]
            shutil.rmtree(trace_dir, ignore_errors=True)
        hello = {"spec": {k: spec[k] for k in ("config", "traffic", "serving")},
                 "seed": seed, "seconds": seconds,
                 "echo": {str(k): v for k, v in prep["echo"].items()}}
        fleet_rec = asyncio.run(serve_window(engine, fleet, hello, seconds, trace_dir))
        fleet.wait(timeout=60)
    finally:
        if fleet.poll() is None:
            fleet.kill()
            fleet.wait()
    t_open, t_close = fleet_rec["t_open"], fleet_rec["t_close"]
    setup_s = t_open - t_start
    in_window = counter.between(t_open, t_close)
    peak_bytes = None
    if device is not None:
        peak_bytes = int((device.memory_stats() or {}).get("peak_bytes_in_use", 0)) or None
    rounds = rec.rounds()
    rec.events.clear()
    del engine, rec
    gc.collect()

    log(f"setup: setup_s {setup_s:.3f} | " + " ".join(f"{k} {v:.3f}" for k, v in prep["phases"].items()))
    log(f"compile cache during set-up: {counter.hits} hits, {counter.misses} misses "
        f"({'warm' if counter.misses == 0 else 'cold'}); programs lowered in set-up "
        f"{counter.between(t_start, t_open)}")
    log(f"compiles inside the window: {in_window}")
    log(f"peak_bytes_in_use: {peak_bytes}")
    log(f"fleet lateness p99 {fleet_rec['lateness_p99_s'] * 1e3:.3f} ms, "
        f"max {fleet_rec['lateness_max_s'] * 1e3:.3f} ms; timeouts {fleet_rec['timeouts']}; "
        f"errors {len(fleet_rec['errors'])} {fleet_rec['errors'][:3]}; "
        f"fleet cpu {fleet_rec['cpu_s']:.2f} s; longest loop stall (s, at s) {fleet_rec['loop_stall']}")
    live = [live_streams(fleet_rec, t_open + f * seconds) for f in (0.0, 0.25, 0.5, 0.75, 1.0)]
    log(f"live streams at 0/25/50/75/100% of the window: {live} of {serving['n_slots']} slots; "
        f"pre-admitted {len(schedule.pre_admitted)}")

    ctx = SimpleNamespace(
        seconds=seconds, t_open=t_open, t_close=t_close, fleet=fleet_rec,
        rounds=[r for r in rounds if t_open <= r.t <= t_close],
        shapes=cost.Shapes.from_config(config), trace=None, device_kind=None,
    )
    if device is not None:
        ctx.device_kind = device.device_kind
    e2e = end_to_end(ctx, setup_s)
    log("end-to-end readings: " + " ".join(f"{k} {v}" for k, v in e2e.items()))
    metrics: Dict[str, dict] = {}
    breakdown = None
    if trace:
        trace_mod = load_file("trace.py")
        ctx.trace = trace_mod.load(trace_mod.find_xplane(trace_dir))
        ctx.t_open, ctx.t_close = fleet_rec["traced"]
        ctx.rounds = ctx.traced_rounds = [r for r in rounds if ctx.t_open <= r.t <= ctx.t_close]
        for m in spec["per_layer"]:
            v = read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": ctx.trace.top_ops(10), "idle_gaps": ctx.trace.idle_gaps(10)}
    else:
        for m in spec["end_to_end"]:
            if not np.isfinite(e2e[m["name"]]):
                raise RuntimeError(f"{m['name']}: nothing to measure in the window")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    compared, extra = check(spec, schedule, fleet_rec, seed, control)
    log("check gaps: " + " ".join(f"{who} {k} {v}" for who in ("program", "control")
                                  for k, v in extra.get(who, {}).items()))
    correct = all(c["ok"] for c in compared.values())
    attempted = sum(1 for r in fleet_rec["rounds"] if t_open <= r[0] <= t_close)
    attempted += sum(1 for s in fleet_rec["streams"] if s["due"] is not None)
    failed = fleet_rec["timeouts"] + len(fleet_rec["errors"])
    result = {
        "correct": bool(correct),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": None,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["_ctx"] = ctx
    result["_peak"] = peak_bytes
    result["_extra"] = extra
    result["check"] = {k: {"value": v["value"], "limit": v["limit"]} for k, v in compared.items()}
    return result


def live_streams(fleet_rec: dict, t: float) -> int:
    """Streams holding a pool slot at time ``t``: admitted (pre-admitted ones
    before the window) and not yet past their last verdict."""
    n = 0
    for s in fleet_rec["streams"]:
        start = fleet_rec["t_open"] if s["due"] is None else s["t_admit"]
        if start is not None and start <= t and not (s["finished"] and s["t_last"] <= t):
            n += 1
    return n


def end_to_end(ctx, setup_s: float) -> Dict[str, float]:
    f, lo, hi = ctx.fleet, ctx.t_open, ctx.t_close
    done = [r for r in f["rounds"] if lo <= r[1] <= hi]
    ttft = []
    for s in f["streams"]:
        if s["due"] is None or s["due"] >= ctx.seconds:
            continue
        due = lo + s["due"]
        first = s["t_first"]
        ttft.append((first if first is not None and first <= hi else hi) - due)
    rtt = [(r[1] - r[0]) * 1e3 for r in done]
    return {
        "verify_rtt_p95_ms": sched_mod.percentile(rtt, 95),
        "ttft_p95_ms": sched_mod.percentile([t * 1e3 for t in ttft], 95),
        "setup_s": setup_s,
        "verify_rtt_mean_ms": float(np.mean(rtt)) if rtt else float("nan"),  # sizes round_trip_s
    }


def read_metric(name: str, ctx) -> Optional[float]:
    """Run the metric's reader, ``metrics/<stem>.py``, where the stem is
    the part of ``name`` before its first dot: the suffix names the
    end-to-end metric it moves (``verify_mfu.rtt``), not another reader."""
    stem = name.split(".", 1)[0]
    return load_file(f"metrics/{stem}.py").read(ctx)


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def sample_streams(fleet_rec: dict, seed: int) -> List[dict]:
    """Streams the check compares: every finished echo stream, the longest
    finished stream, then finished streams drawn from the seed until the
    sample holds ``SAMPLE_TOKENS`` served tokens (or ``SAMPLE_STREAMS``)."""
    fin = [s for s in fleet_rec["streams"] if s["finished"] and not s["failed"] and s["tokens"]]
    if not fin:
        return []
    picked = {s["sid"]: s for s in fin if s["accepted"] > 0}
    longest = max(fin, key=lambda s: len(s["tokens"]))
    picked.setdefault(longest["sid"], longest)
    rng = np.random.default_rng([seed & 0xFFFFFFFFFFFF, 7])
    for i in rng.permutation(len(fin)):
        if sum(len(s["tokens"]) for s in picked.values()) >= SAMPLE_TOKENS:
            break
        if len(picked) >= SAMPLE_STREAMS:
            break
        picked.setdefault(fin[i]["sid"], fin[i])
    return sorted(picked.values(), key=lambda s: s["sid"])


def check(spec: dict, schedule, fleet_rec: dict, seed: int, control: bool):
    """Compare what was served with the plain reference.

    For a sample of finished streams, the reference runs once over each
    prompt with its served tokens; each served token is scored by how far
    its logit lies below the reference's best at that position, and each
    statistic of these gaps that the configuration's ``check`` names must
    stay under its limit.  The sample must also hold accepted drafts, so
    that the accept-and-commit path is among what was compared.

    ``control`` puts the reference at int8 in the program's place: at each
    of the same positions the token an int8 forward puts first is scored
    by the same gap and held to the same limits, so a sound control run
    reports ``correct`` false.  The program's own gaps are kept in ``extra``.
    """
    limits = spec["config"]["check"]
    ref = load_file(f"references/{spec['config']['reference']}.py")
    prompts = {s.sid: s.prompt for s in schedule.streams}
    picked = sample_streams(fleet_rec, seed)
    seqs = [np.concatenate([prompts[s["sid"]], np.asarray(s["tokens"], np.int32)]) for s in picked]
    served_from = [int(prompts[s["sid"]].size) for s in picked]
    extra = {"streams": len(picked), "tokens": int(sum(len(s["tokens"]) for s in picked))}
    if picked:
        t = time.monotonic()
        gaps = ref.served_gaps(spec["config"], seed, seqs, served_from, control=control,
                               length=int(spec["serving"]["max_len"]))
        extra["reference_s"] = time.monotonic() - t
        extra["program"] = gap_stats(gaps["gap"])
        if control:
            extra["control"] = gap_stats(gaps["control_gap"])
        stats = extra["control" if control else "program"]
    else:
        stats = {k: float("inf") for k in GAP_STATS}
    accepted = int(sum(s["accepted"] for s in picked))
    compared = {
        k: {"value": stats[k], "limit": limits[k], "ok": stats[k] <= limits[k]}
        for k in GAP_STATS if k in limits
    }
    compared.update({
        "served_tokens": {"value": extra["tokens"], "limit": limits["served_tokens_min"],
                          "ok": extra["tokens"] >= limits["served_tokens_min"]},
        "accepted_drafts": {"value": accepted, "limit": limits["accepted_drafts_min"],
                            "ok": accepted >= limits["accepted_drafts_min"]},
    })
    return compared, extra


GAP_STATS = ("served_gap_max", "served_gap_mean", "served_gap_p99", "served_flip_share")


def gap_stats(gaps: List[np.ndarray]) -> Dict[str, float]:
    """The gaps of every served token compared: the widest, their mean,
    their 99th percentile, and the share of tokens that are not the
    reference's first choice.  The configuration's ``check`` gives the
    limit of each one it compares."""
    g = np.concatenate(gaps)
    return {"served_gap_max": float(g.max()), "served_gap_mean": float(g.mean()),
            "served_gap_p99": float(np.percentile(g, 99)), "served_flip_share": float((g > 0).mean())}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the int8 reference in the program's place: correct must read false "
                         "(limit setting; not a benchmark run)")
    ap.add_argument("--rate", type=float, default=None,
                    help="arrival rate in place of the cell's (the knee sweep; not a benchmark run)")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log("the program (src/repro) is not in this checkout")
        return 3
    import spec as spec_mod

    spec = spec_mod.load(args.workload)
    if args.rate is not None:
        spec["traffic"]["arrivals"]["rate_per_s"] = args.rate
    import jax

    devices = jax.devices()
    chips = int(spec["cell"]["chips"])
    if devices[0].platform != "tpu" or len(devices) < chips:
        log(f"no TPU found, or fewer than {chips}: {devices}")
        return 2
    res = run(spec, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
              control=bool(args.control), device=devices[0])
    ctx, extra = res.pop("_ctx"), res.pop("_extra")
    peak = res.pop("_peak")
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": chips, "memory_peak_bytes": peak}
    if ctx.trace is not None:
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
    res["device"] = device
    res["check"] = res.pop("check")  # the compared numbers come last
    log("check sample: " + " ".join(f"{k} {v}" for k, v in extra.items() if not isinstance(v, dict)))
    for k, v in res["check"].items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
