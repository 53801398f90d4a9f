#!/usr/bin/env python3
"""Diagnostic: the SSD scan kernel against its oracle on a TPU, by matmul
precision and head layout.

    PYTHONPATH=src python3 benchmarks/ssd_precision.py [--seeds 0 1 2]

At mamba2-370m widths (the shapes of chip_smoke.py's kernel phase; seed 0
makes its exact inputs) it runs every combination of

  * the kernel's matmul precision: DEFAULT (one bf16 pass) or HIGHEST;
  * the oracle's contraction precision: DEFAULT or HIGHEST;
  * heads per program: 8 (the kernel's choice) or all 32 in one body;

and prints, per seed and combination, the kernel's largest error against
the oracle, how many elements fail chip_smoke.py's test (``np.allclose``
with rtol = atol = 4e-2), and how many elements of the kernel's and of the
oracle's output fail that test against a float64 scan on the host.  It
exits non-zero when JAX's default platform is not a TPU.
"""

from __future__ import annotations

import argparse
import itertools
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref, ssd_scan

SHAPE = dict(B=1, S=512, H=32, P=64, N=128, chunk=256)
TOL = 4e-2
PRECISIONS = {"DEFAULT": jax.lax.Precision.DEFAULT, "HIGHEST": jax.lax.Precision.HIGHEST}


def inputs(seed: int):
    """chip_smoke.py's SSD inputs, from ``key(seed)``."""
    s = SHAPE
    xs = jax.random.split(jax.random.split(jax.random.key(seed), 8)[4], 6)
    x = jax.random.normal(xs[0], (s["B"], s["S"], s["H"], s["P"]), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(xs[1], (s["B"], s["S"], s["H"])))
    A = -jnp.exp(jax.random.normal(xs[2], (s["H"],)) * 0.5)
    Bm = jax.random.normal(xs[3], (s["B"], s["S"], s["N"]), jnp.bfloat16)
    Cm = jax.random.normal(xs[4], (s["B"], s["S"], s["N"]), jnp.bfloat16)
    h0 = jax.random.normal(xs[5], (s["B"], s["H"], s["P"], s["N"]))
    return x, dt, A, Bm, Cm, h0


def scan_f64(x, dt, A, Bm, Cm, h0):
    """The SSD recurrence in float64 on the host: the truth both sides are
    held to."""
    x, dt, A, Bm, Cm, h = (np.asarray(a, np.float64) for a in (x, dt, A, Bm, Cm, h0))
    ys = np.empty(x.shape)
    for t in range(x.shape[1]):
        decay = np.exp(dt[:, t] * A[None])  # (B, H)
        h = decay[..., None, None] * h + np.einsum(
            "bh,bn,bhp->bhpn", dt[:, t], Bm[:, t], x[:, t])
        ys[:, t] = np.einsum("bn,bhpn->bhp", Cm[:, t], h)
    return ys, h


def failing(got, want) -> int:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return int((np.abs(got - want) > TOL + TOL * np.abs(want)).sum())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[0, 1, 2])
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("no TPU found: this diagnostic compiles the kernel for the chip",
              file=sys.stderr)
        return 2
    print(f"jax {jax.__version__} on {jax.devices()[0].device_kind}")

    data = {seed: inputs(seed) for seed in args.seeds}
    truth = {seed: scan_f64(*d) for seed, d in data.items()}
    for seed, (y, h) in truth.items():
        print(f"seed {seed}: float64 |y| max {np.abs(y).max():.3f} median "
              f"{np.median(np.abs(y)):.3f}, |h| max {np.abs(h).max():.3f}")

    kernel, oracle = {}, {}
    for heads, kp in itertools.product((8, SHAPE["H"]), PRECISIONS):
        with mock.patch.object(ssd_scan, "_HI", PRECISIONS[kp]), \
                mock.patch.object(ssd_scan, "_heads_per_block", lambda H, P: heads):
            run = jax.jit(lambda *a: ssd_scan.ssd_scan_chunked(*a, chunk=SHAPE["chunk"]))  # traced under the patches
            for seed, d in data.items():
                kernel[heads, kp, seed] = jax.device_get(run(*d))
    for op in PRECISIONS:
        with mock.patch.object(ref, "_HI", PRECISIONS[op]):
            run = jax.jit(lambda *a: ref.ssd_scan_ref(*a))  # a fresh trace per precision
            for seed, d in data.items():
                oracle[op, seed] = jax.device_get(run(*d))

    for seed in args.seeds:
        ty, th = truth[seed]
        for op in PRECISIONS:
            oy, oh = oracle[op, seed]
            print(f"seed {seed} oracle {op}: elements failing against float64: "
                  f"y {failing(oy, ty)}, h {failing(oh, th)}")
        for (heads, kp), op in itertools.product(
                itertools.product((8, SHAPE["H"]), PRECISIONS), PRECISIONS):
            (ky, kh), (oy, oh) = kernel[heads, kp, seed], oracle[op, seed]
            err = np.abs(np.asarray(ky, np.float64) - np.asarray(oy, np.float64))
            worst = np.unravel_index(np.argmax(err), err.shape)
            print(f"seed {seed} heads/program {heads} kernel {kp} oracle {op}: "
                  f"y max_abs_err {err.max():.6f} (oracle {float(oy[worst]):.4f}), "
                  f"failing against the oracle y {failing(ky, oy)} h {failing(kh, oh)}; "
                  f"kernel failing against float64 y {failing(ky, ty)} h {failing(kh, th)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
