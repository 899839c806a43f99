"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two ``os.environ`` lines below MUST stay first: jax locks the device
count on first init, and the dry-run needs 512 placeholder host devices to
build the production mesh. (Do not set this anywhere global — smoke tests
and benches see 1 device.)

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape decode_32k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod-only]
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b \
        --shape decode_32k --smoke      # smoke config, 2x4 mesh, CPU-feasible
Artifacts (HLO text + stats JSON) go to experiments/dryrun/. ``--smoke``
compiles the reduced config on a small 2x4 mesh with scaled-down shapes —
the artifacts exercise the same roofline pipeline (tests/test_roofline.py,
benchmarks/bench_roofline.py) without a pod-scale compile.
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse
import dataclasses
import json
import pathlib
import sys
import time
import traceback

import jax

from repro.configs.base import (SHAPES, arch_shape_cells, get_config, shape_for)
from repro.launch.mesh import _make_mesh, make_production_mesh
from repro.models.steps import build_step, input_specs  # noqa: F401 (public API)

ART_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save_hlo: bool = True, verbose: bool = True,
             smoke: bool = False) -> dict:
    cfg = get_config(arch, smoke=smoke)
    shape = shape_for(shape_name)
    if smoke:
        import dataclasses as _dc
        shape = _dc.replace(shape, name=shape.name + "-smoke",
                            seq_len=min(shape.seq_len, 256),
                            global_batch=max(min(shape.global_batch, 8), 2))
        mesh = _make_mesh((2, 4), ("data", "model"))
        mesh_tag = "2x4smoke"
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_tag = "2x16x16" if multi_pod else "16x16"
    t0 = time.time()
    built = build_step(cfg, mesh, shape)
    with mesh:
        lowered = built.lower()
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    rec = {
        "arch": arch,
        "shape": shape_name,
        "smoke": smoke,
        "kind": shape.kind,
        "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "mesh": mesh_tag,
        "chips": int(len(mesh.devices.reshape(-1))),
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(t2 - t1, 2),
        "flops_per_device": float(ca.get("flops", -1.0)),
        "bytes_accessed_per_device": float(ca.get("bytes accessed", -1.0)),
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_bytes_estimate": int(ma.argument_size_in_bytes +
                                   ma.output_size_in_bytes +
                                   ma.temp_size_in_bytes -
                                   ma.alias_size_in_bytes),
        "ok": True,
    }
    ART_DIR.mkdir(parents=True, exist_ok=True)
    stem = f"{arch}_{shape_name}_{mesh_tag}"
    if save_hlo:
        hlo_path = ART_DIR / f"{stem}.hlo.txt"
        hlo_path.write_text(compiled.as_text())
        rec["hlo_path"] = str(hlo_path)
    (ART_DIR / f"{stem}.json").write_text(json.dumps(rec, indent=2))
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {mesh_tag}: "
              f"compile {rec['compile_s']}s, "
              f"peak/device {rec['peak_bytes_estimate']/2**30:.2f} GiB, "
              f"flops/device {rec['flops_per_device']:.3e}")
        print("  memory_analysis:", ma)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multipod-only", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-hlo", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="smoke configs on a 2x4 mesh (CPU-feasible)")
    args = ap.parse_args()

    meshes = [False, True]
    if args.single_pod_only:
        meshes = [False]
    if args.multipod_only:
        meshes = [True]
    if args.multipod:
        meshes = [True]

    if args.all:
        cells = arch_shape_cells()
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape)]

    if args.smoke:
        meshes = [False]

    failures = []
    for arch, shape_name in cells:
        for mp in meshes:
            try:
                run_cell(arch, shape_name, mp, save_hlo=not args.no_hlo,
                         smoke=args.smoke)
            except Exception:
                failures.append((arch, shape_name, mp))
                traceback.print_exc()
    if failures:
        print("FAILED cells:", failures)
        return 1
    print(f"dry-run OK: {len(cells)} cells x {len(meshes)} mesh(es)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
