"""Record the small device trace that ``test_trace.py`` reads.

TPU only: runs the harness on the tiny test model (``data/tiny-dense.json``)
for one traced second and copies the ``.xplane.pb`` to ``--out``, then
prints what the reducer makes of it.  ``data/tiny.xplane.pb`` was made so:

    python3 benchmarks/sled_bench/tests/record_trace.py --out bench_out/tiny.xplane.pb
"""
from __future__ import annotations

import argparse
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import helpers  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("no TPU found", file=sys.stderr)
        return 2
    import run

    trace_mod = run.load_file("trace.py")
    spec = helpers.tiny_spec()
    res = run.run(spec, seed=3, seconds=1.0, trace=True, t_start=time.monotonic(),
                  device=jax.devices()[0])
    path = trace_mod.find_xplane(run.OUT_DIR / "trace" / spec["cell"]["name"])
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, args.out)
    tr = res["_ctx"].trace
    print("bytes", path.stat().st_size, "window_s", tr.window_s, "busy_s", tr.busy_s)
    print("modules", {k: len(v) for k, v in tr.modules.items()})
    print("metrics", res["metrics"])
    print("breakdown", res["breakdown"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
