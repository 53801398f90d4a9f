import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
# The dry-run (and ONLY the dry-run) builds the production 16x16 (x2 pods)
# mesh out of host platform devices; smoke tests/benches see 1 device.

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) cell.

Per cell this records:
  * compiled.memory_analysis()  — proves the step fits per-device HBM
  * compiled.cost_analysis()    — per-device HLO FLOPs / bytes accessed
  * collective bytes parsed from the optimized HLO (roofline/analysis.py)
  * the three roofline terms + dominant bottleneck

Usage:
  python -m repro.launch.dryrun --arch qwen2-1.5b --shape decode_32k
  python -m repro.launch.dryrun --all --mesh pod          # 16x16, all cells
  python -m repro.launch.dryrun --all --mesh multipod     # 2x16x16
Results accumulate in experiments/dryrun/<mesh>/<arch>__<shape>.json.
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs.base import SHAPES, all_cells, get_config, shape_applicable
from repro.launch.cells import build_cell
from repro.launch.mesh import make_production_mesh
from repro.roofline.analysis import Roofline, model_flops
from repro.roofline.hlo_cost import HloCostModel

OUT_DIR = "experiments/dryrun"


def run_cell(cfg, shape, mesh, mesh_name: str, *, verbose: bool = True,
             save: bool = True, attn_chunk: int = 1024, tag: str = "",
             kv_bits: int = 16) -> dict:
    t0 = time.time()
    cell = build_cell(cfg, shape, mesh, attn_chunk=attn_chunk, kv_bits=kv_bits)
    with jax.set_mesh(mesh):
        lowered = cell.lower()
        t_lower = time.time() - t0
        compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    # trip-count-aware HLO walk: XLA's cost_analysis counts loop bodies ONCE
    # (scan-over-layers / grad-accum would be undercounted by 88x / 8x)
    costs = HloCostModel(hlo).totals()
    chips = mesh.size

    r = Roofline(
        arch=cfg.name, shape=shape.name, mesh=mesh_name, chips=chips,
        hlo_flops=costs["flops"],
        hlo_bytes=costs["bytes"],
        collective_bytes=costs["collective_bytes"],
        model_flops=model_flops(cfg, shape),
        arg_bytes=int(ma.argument_size_in_bytes),
        temp_bytes=int(ma.temp_size_in_bytes),
        out_bytes=int(ma.output_size_in_bytes),
    )
    rec = r.to_dict()
    rec.update(
        alias_bytes=int(ma.alias_size_in_bytes),
        collectives_by_kind=costs["collective_by_kind"],
        xla_flops_nomult=float(ca.get("flops", 0.0)),
        xla_bytes_nomult=float(ca.get("bytes accessed", 0.0)),
        t_lower_s=round(t_lower, 1), t_compile_s=round(t_compile, 1),
        status="ok", tag=tag,
    )
    if verbose:
        hbm = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes) / 1e9
        print(
            f"[{mesh_name}] {cfg.name} x {shape.name}: OK "
            f"per-dev HBM ~{hbm:.2f} GB (args {ma.argument_size_in_bytes/1e9:.2f} "
            f"+ temp {ma.temp_size_in_bytes/1e9:.2f} - alias {ma.alias_size_in_bytes/1e9:.2f}), "
            f"flops/dev {r.hlo_flops:.3g}, coll {costs['collective_bytes']/1e6:.1f} MB -> "
            f"compute {r.t_compute*1e3:.2f} ms | memory {r.t_memory*1e3:.2f} ms | "
            f"collective {r.t_collective*1e3:.2f} ms  [{r.bottleneck}-bound] "
            f"useful-flops {r.useful_flops_frac:.2f} "
            f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)"
        )
    if save:
        _save(rec, mesh_name, cfg.name, shape.name, tag)
    return rec


def _save(rec: dict, mesh_name: str, arch: str, shape: str, tag: str = "") -> None:
    d = os.path.join(OUT_DIR, mesh_name)
    os.makedirs(d, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    with open(os.path.join(d, f"{arch}__{shape}{suffix}.json"), "w") as f:
        json.dump(rec, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", type=str, default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true", help="every applicable cell")
    ap.add_argument("--attn-chunk", type=int, default=1024)
    ap.add_argument("--attn-impl", type=str, default="xla", choices=["xla", "stub"],
                    help="stub = fused-kernel traffic model (see models/layers.py)")
    ap.add_argument("--tag", type=str, default="", help="perf-iteration tag")
    ap.add_argument("--combine-bf16", action="store_true",
                    help="§Perf A2: bf16 flash-decoding combine")
    ap.add_argument("--ssd-headshard", action="store_true",
                    help="§Perf B1 variant (refuted): SSD head sharding")
    ap.add_argument("--ssd-impl", type=str, default="xla", choices=["xla", "stub"],
                    help="§Perf B2: stub = ssd_scan kernel traffic model")
    ap.add_argument("--kv-bits", type=int, default=16, choices=[8, 16],
                    help="int8 KV cache (beyond-paper fit/bandwidth feature)")
    ap.add_argument("--keep-going", action="store_true")
    args = ap.parse_args()

    if args.attn_impl != "xla":
        from repro.models import layers as _L
        _L.ATTN_IMPL = args.attn_impl
        if not args.tag:
            args.tag = f"attn_{args.attn_impl}"
    if args.combine_bf16:
        import jax.numpy as jnp
        from repro.distributed import collectives as _C
        _C.COMBINE_DTYPE = jnp.bfloat16
    if args.ssd_headshard:
        from repro.models import mamba2 as _M2
        _M2.HEAD_SHARD = True
        if not args.tag:
            args.tag = "headshard"
    if args.ssd_impl != "xla":
        from repro.models import mamba2 as _M2
        _M2.SSD_IMPL = args.ssd_impl
        if not args.tag:
            args.tag = f"ssd_{args.ssd_impl}"

    meshes = []
    if args.mesh in ("pod", "both"):
        meshes.append(("pod", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multipod", "both"):
        meshes.append(("multipod", make_production_mesh(multi_pod=True)))

    if args.all:
        cells = all_cells()
    else:
        cfg = get_config(args.arch)
        shapes = [SHAPES[args.shape]] if args.shape else [
            s for s in SHAPES.values() if shape_applicable(cfg, s)
        ]
        cells = [(cfg, s) for s in shapes]

    failures = []
    for mesh_name, mesh in meshes:
        for cfg, shape in cells:
            if not shape_applicable(cfg, shape):
                print(f"[{mesh_name}] {cfg.name} x {shape.name}: SKIP "
                      f"(long-context requires sub-quadratic mixing; see DESIGN.md)")
                continue
            try:
                run_cell(cfg, shape, mesh, mesh_name, attn_chunk=args.attn_chunk,
                         tag=args.tag, kv_bits=args.kv_bits)
            except Exception as e:  # noqa: BLE001 — report & continue
                failures.append((mesh_name, cfg.name, shape.name, repr(e)))
                print(f"[{mesh_name}] {cfg.name} x {shape.name}: FAIL {e!r}")
                _save({"status": "fail", "error": traceback.format_exc()},
                      mesh_name, cfg.name, shape.name, args.tag)
                if not args.keep_going:
                    raise

    print(f"\ndone: {len(failures)} failures")
    for f in failures:
        print("  FAIL", *f)


if __name__ == "__main__":
    main()
