"""CPU benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per benchmark.  ``--quick``
shrinks sim horizons for CI.  Chip numbers come from ``sled_bench`` (PERF.md).
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", type=str, default=None)
    args = ap.parse_args()

    from benchmarks import availability, capacity, confidence, pareto
    from benchmarks import speclen, wstgr

    suites = {
        "availability": availability.run,
        "table1_capacity": capacity.run,
        "fig3_confidence": confidence.run,
        "fig4_wstgr": wstgr.run,
        "fig5_speclen": speclen.run,
        "fig6_pareto": pareto.run,
    }
    failures = []
    for name, fn in suites.items():
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            fn(quick=args.quick)
            print(f"# {name}: done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            print(f"# {name}: FAILED {e!r}", file=sys.stderr)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
