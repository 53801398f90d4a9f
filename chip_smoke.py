#!/usr/bin/env python3
"""Bring-up smoke run: the served path and every Pallas kernel on one TPU.

    python chip_smoke.py            # one chip: serve + kernels
    python chip_smoke.py --chips 4  # four replicas on four chips, only

With no arguments the script builds ``examples/specs/chip_smoke.json``
(qwen2-1.5b at its published widths: 28 target layers, d_model 1536, vocab
151936; a 2-layer draft at the same widths; the transport backend over
loopback links) through ``System.build(spec)``, warms the verify bucket the
fleet fills, serves the fleet, and checks every committed stream against
the lock-step ``reference`` backend on the same built models with
``repro serve``'s own check (``repro.launch.serve.reference_check``:
identical, or split only at a greedy near-tie).  With random weights the
spec's draft never agrees with the target, so it serves the spec a second
time with the target as its own draft, where rounds accept drafted tokens,
and fails if none is accepted.  Then it runs each kernel of ``repro.kernels.ops`` compiled for the chip
(``interpret=False``) at qwen2-1.5b / mamba2-370m widths and compares it
with its oracle in ``repro.kernels.ref``.

``--chips 4`` runs only the replica phase: the same spec on the cluster
backend with four in-process replicas, each committed to its own device,
compared with the reference backend on chip 0 the same way.

Everything runs in this one process: a chip belongs to one process at a
time.  Weights are random, made from the spec's seed.  The script exits
non-zero, before printing any result, unless JAX's default platform is a
TPU; any phase that fails makes it exit non-zero too.  The last line of
standard output is one JSON object naming the device it ran on.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SPEC_PATH = ROOT / "examples" / "specs" / "chip_smoke.json"
sys.path.insert(0, str(ROOT / "src"))


def _gib(n: float) -> str:
    return f"{n / 2**30:.3f} GiB"


def describe_models(models) -> None:
    import jax

    for role, cfg, params in (("target", models.target_cfg, models.target_params),
                              ("draft", models.draft_cfg, models.draft_params)):
        n = sum(int(x.size) for x in jax.tree.leaves(params))
        print(f"model {role}: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
              f"heads={cfg.num_heads}/{cfg.num_kv_heads} d_ff={cfg.d_ff} "
              f"vocab={cfg.vocab_size} params={n}")


def serve_run(spec, models, label: str, *, need_acceptance: bool = False) -> bool:
    """``System.build(spec, models=models).serve()`` on the default device,
    warmed at the one bucket the whole fleet fills, and checked against the
    reference backend on the same models."""
    import jax

    from repro.api import System
    from repro.launch.serve import reference_check

    system = System.build(spec, models=models)
    fill = min(spec.devices, spec.slots_per_replica)
    bucket = next(b for b in system.engine.buckets if b >= fill)
    compile_log = system.warmup(buckets=[bucket])
    print(f"{label}: compile seconds bucket {bucket}: {compile_log[bucket]:.3f}")
    t0 = time.perf_counter()
    result = system.serve()
    serve_s = time.perf_counter() - t0
    st = result.engine
    used = sorted({r.bucket for r in system.engine.round_log})
    print(f"{label}: backend {spec.backend}, bucket warmed {bucket}, used {used}")
    print(f"{label}: tokens {result.total_tokens}")
    print(f"{label}: rounds {st.rounds}")
    print(f"{label}: acceptance {st.acceptance_rate:.4f}")
    print(f"{label}: serve seconds {serve_s:.3f} (includes the first prefill and "
          f"drafting compiles)")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"{label}: peak_bytes_in_use {stats['peak_bytes_in_use']} "
              f"({_gib(stats['peak_bytes_in_use'])})")
    if st.fallback_tokens:
        print(f"{label}: FAIL, {st.fallback_tokens} tokens were released unverified")
        return False
    if need_acceptance and st.acceptance_rate <= 0:
        print(f"{label}: FAIL, no drafted token was accepted")
        return False
    return reference_check(system, result, label)


def serve_phase(spec) -> bool:
    """The committed spec, then the same target drafting for itself."""
    from repro.api.system import build_models

    t0 = time.perf_counter()
    models = build_models(spec.model)
    print(f"serve: model build seconds {time.perf_counter() - t0:.3f}")
    describe_models(models)
    ok = serve_run(spec, models, "serve")
    # With random weights the spec's draft is an unrelated model: acceptance
    # is 0 and every round commits only the target's own token.  The target
    # as its own draft agrees with the verifier except at near-ties, so its
    # rounds accept drafted tokens and commit several positions at once.
    own = dataclasses.replace(models, draft_cfg=models.target_cfg, draft=models.target,
                              draft_params=models.target_params)
    print(f"self-draft: the draft is the target itself ({models.target_cfg.num_layers} "
          f"layers, the same weights)")
    ok &= serve_run(spec, own, "self-draft", need_acceptance=True)
    return ok


def replicas_phase(spec, n: int) -> bool:
    """The spec on the cluster backend with ``n`` in-process replicas, one
    per device, against the reference backend."""
    import jax

    from repro.api import ClusterSpec, System
    from repro.launch.serve import reference_check

    # every device joins at once, so each replica gets a stream of its own
    spec = spec.with_backend(
        "cluster", cluster=ClusterSpec(replicas=n),
        scheduler=dataclasses.replace(spec.scheduler, stagger_ticks=0))
    system = System.build(spec)
    describe_models(system.models)
    placed = [r.device for r in system.engine.replicas]
    print(f"replicas: {n} replicas on devices {[str(d) for d in placed]}")
    if len(set(placed)) != n:
        print("replicas: FAIL, replicas share a device")
        return False
    compile_log = system.warmup()
    print(f"replicas: compile seconds total {sum(compile_log.values()):.3f}")
    t0 = time.perf_counter()
    result = system.serve()
    print(f"replicas: serve seconds {time.perf_counter() - t0:.3f}")
    rounds = [s.rounds for s in system.engine.replica_stats()]
    print(f"replicas: tokens {result.total_tokens}, rounds per replica {rounds}, "
          f"migrations {system.engine.migrations}")
    if not all(rounds):
        print("replicas: FAIL, a replica verified no round")
        return False
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        print(f"replicas: {d} bytes_in_use {stats.get('bytes_in_use')} "
              f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    return reference_check(system, result, "replicas")


# kernel phase shapes: qwen2-1.5b verify attention, mamba2-370m SSD scan
ATTN = dict(B=4, Sq=5, Hq=12, Hkv=2, D=128, Skv=4096, n_slots=4)
SSD = dict(B=1, S=512, H=32, P=64, N=128, chunk=256)


def kernel_phase() -> bool:
    """Every kernel in repro.kernels.ops, compiled for the chip, against its
    repro.kernels.ref oracle, at the tolerances tests/test_kernels.py holds
    them to."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    a = ATTN
    ks = jax.random.split(jax.random.key(0), 8)
    q = jax.random.normal(ks[0], (a["B"], a["Sq"], a["Hq"], a["D"]), jnp.bfloat16)
    kf = jax.random.normal(ks[1], (a["n_slots"] + 1, a["Skv"], a["Hkv"], a["D"]))
    vf = jax.random.normal(ks[2], (a["n_slots"] + 1, a["Skv"], a["Hkv"], a["D"]))
    kv_valid = jax.random.randint(ks[3], (a["B"],), a["Sq"], a["Skv"] + 1)
    slots = jnp.asarray([2, 0, a["n_slots"], a["n_slots"]], jnp.int32)[: a["B"]]
    k_scale = jnp.abs(kf).max(axis=(1, 3)) / 127.0
    v_scale = jnp.abs(vf).max(axis=(1, 3)) / 127.0
    k8 = jnp.clip(jnp.round(kf / k_scale[:, None, :, None]), -127, 127).astype(jnp.int8)
    v8 = jnp.clip(jnp.round(vf / v_scale[:, None, :, None]), -127, 127).astype(jnp.int8)
    kb, vb = kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16)

    s = SSD
    xs = jax.random.split(ks[4], 6)
    x = jax.random.normal(xs[0], (s["B"], s["S"], s["H"], s["P"]), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(xs[1], (s["B"], s["S"], s["H"])))
    A = -jnp.exp(jax.random.normal(xs[2], (s["H"],)) * 0.5)
    Bm = jax.random.normal(xs[3], (s["B"], s["S"], s["N"]), jnp.bfloat16)
    Cm = jax.random.normal(xs[4], (s["B"], s["S"], s["N"]), jnp.bfloat16)
    h0 = jax.random.normal(xs[5], (s["B"], s["H"], s["P"], s["N"]))

    def ssd_pair():
        y, h = ops.ssd_scan(x, dt, A, Bm, Cm, h0, chunk=s["chunk"])
        yw, hw = ref.ssd_scan_ref(x, dt, A, Bm, Cm, h0)
        return (y, h), (yw, hw)

    cases = {
        "verify_attention": (
            lambda: (ops.verify_attention(q, kb[: a["B"]], vb[: a["B"]], kv_valid),),
            lambda: (ref.verify_attention_ref(q, kb[: a["B"]], vb[: a["B"]], kv_valid),),
            2e-2),
        "verify_attention_paged bf16": (
            lambda: (ops.verify_attention_paged(q, kb, vb, slots, kv_valid),),
            lambda: (ref.verify_attention_paged_ref(q, kb, vb, slots, kv_valid),),
            2e-2),
        "verify_attention_paged int8": (
            lambda: (ops.verify_attention_paged(q, k8, v8, slots, kv_valid, k_scale,
                                                v_scale),),
            lambda: (ref.verify_attention_paged_ref(q, k8, v8, slots, kv_valid,
                                                    k_scale=k_scale, v_scale=v_scale),),
            2e-2),
        "ssd_scan": (lambda: ssd_pair()[0], lambda: ssd_pair()[1], 4e-2),
    }
    ok = True
    for name, (run, oracle, tol) in cases.items():
        t0 = time.perf_counter()
        got = jax.block_until_ready(run())
        secs = time.perf_counter() - t0
        want = oracle()
        errs = [float(np.max(np.abs(np.asarray(g, np.float32) - np.asarray(w, np.float32))))
                for g, w in zip(got, want)]
        close = all(
            np.allclose(np.asarray(g, np.float32), np.asarray(w, np.float32),
                        rtol=tol, atol=tol)
            and np.isfinite(np.asarray(g, np.float32)).all()
            for g, w in zip(got, want)
        )
        ok &= close
        print(f"kernels: {name} interpret=False max_abs_err {max(errs):.6f} "
              f"tol {tol} first call {secs:.3f} s {'PASS' if close else 'FAIL'}")
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-replica phase, one replica per chip")
    args = ap.parse_args(argv)

    from repro.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    print(f"jax {jax.__version__}")
    print(f"devices {jax.devices()}")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU found: JAX's default platform is {dev.platform!r}; this "
              f"smoke run has no CPU fallback", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, JAX sees "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    print(f"compile cache {cache_dir}")

    from repro.api import ServeSpec

    spec = ServeSpec.from_json(SPEC_PATH.read_text())
    if args.chips == 4:
        phases = [("replicas", lambda: replicas_phase(spec, 4))]
    else:
        phases = [("serve", lambda: serve_phase(spec)), ("kernels", kernel_phase)]
    failed = []
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            ok = phase()
        except Exception:
            traceback.print_exc()
            ok = False
        print(f"phase {name}: {'PASS' if ok else 'FAIL'} in {time.perf_counter() - t0:.3f} s")
        if not ok:
            failed.append(name)
    if failed:
        print(f"failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
