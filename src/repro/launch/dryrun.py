import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init) — they give this process 512 placeholder CPU devices
# so the production meshes can be built.  Only the dry-run gets this flag.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell:
  * build ShapeDtypeStruct inputs (no allocation) and the jitted step
    function with production shardings (launch/specs.py);
  * ``.lower().compile()`` on the 16x16 single-pod mesh and the 2x16x16
    multi-pod mesh;
  * record ``memory_analysis()`` (proves it fits), ``cost_analysis()``
    (FLOPs/bytes for the roofline) and the collective traffic parsed from
    the post-SPMD HLO;
  * append the result to ``experiments/dryrun/<cell>.json`` — incremental:
    finished cells are skipped on re-run.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs import ARCHS, get_config
from repro.launch import hlo_analysis, hlo_cost
from repro.launch.mesh import make_production_mesh, make_serve_mesh
from repro.launch.specs import build_case, skip_reason
from repro.models.config import SHAPES

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def cell_path(arch: str, shape: str, mesh_name: str, serve_mode: str,
              variant: str | None = None) -> str:
    suffix = "" if serve_mode == "2d" else f"__{serve_mode}"
    if variant:
        suffix += f"__{variant}"
    return os.path.join(OUT_DIR, f"{arch}__{shape}__{mesh_name}{suffix}.json")


def _mesh_for(arch: str, shape: str, multi_pod: bool, serve_mode: str):
    if SHAPES[shape].kind == "train" or serve_mode == "flat":
        return make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    if cfg.family == "rwkv6":
        kv, hd = cfg.num_rwkv_heads, cfg.rwkv_head_size
    else:
        kv, hd = cfg.num_kv_heads, cfg.head_dim
    return make_serve_mesh(kv, hd, multi_pod=multi_pod)


def run_cell(arch: str, shape: str, multi_pod: bool, force: bool = False,
             serve_mode: str = "2d", variant: str | None = None) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    path = cell_path(arch, shape, mesh_name, serve_mode, variant)
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    reason = skip_reason(arch, shape)
    result: dict = {
        "arch": arch, "shape": shape, "mesh": mesh_name,
        "serve_mode": serve_mode, "variant": variant,
        "chips": 512 if multi_pod else 256,
    }
    if reason:
        result["status"] = "skipped"
        result["reason"] = reason
        _write(path, result)
        return result
    mesh = _mesh_for(arch, shape, multi_pod, serve_mode)
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            case = build_case(arch, shape, mesh, serve_mode, variant)
            lowered = case.fn.lower(*case.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo_text = compiled.as_text()
            # loop-aware per-device costs (XLA's cost_analysis counts scan
            # bodies once; this multiplies known_trip_count)
            la = hlo_cost.analyze(hlo_text)
            coll = hlo_analysis.CollectiveStats(
                counts={k: int(v) for k, v in
                        la["collective_counts"].items()},
                bytes_by_kind={k: int(v) for k, v in
                               la["collective_bytes_by_kind"].items()},
            )
            terms = hlo_analysis.roofline(
                {"flops": la["flops"], "bytes accessed": la["bytes"]},
                coll, chips=mesh.size,
                model_flops=case.model_flops_per_step,
            )
        result.update({
            "status": "ok",
            "kind": case.kind,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "generated_code_bytes": getattr(
                    mem, "generated_code_size_in_bytes", None
                ),
                "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            },
            "cost": {k: cost.get(k) for k in
                     ("flops", "bytes accessed", "transcendentals")
                     if k in cost},
            "collectives": {
                "counts": coll.counts,
                "bytes_by_kind": coll.bytes_by_kind,
                "total_bytes_per_device": coll.total_bytes,
            },
            "scopes": {
                "bytes": la["bytes_by_scope"],
                "flops": la["flops_by_scope"],
            },
            "roofline": terms.to_dict(),
        })
    except Exception as e:  # a failing cell is a bug — record it loudly
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]
    _write(path, result)
    return result


def _write(path: str, result: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(result, f, indent=1, default=str)


def summarize(results: list[dict]) -> None:
    print(f"\n{'cell':52s} {'status':8s} {'dom':10s} "
          f"{'bound':>9s} {'MFU@roof':>8s} {'mem/chip':>9s}")
    for r in results:
        cell = f"{r['arch']}x{r['shape']}x{r['mesh']}"
        if r["status"] != "ok":
            print(f"{cell:52s} {r['status']:8s} {r.get('reason', r.get('error', ''))[:60]}")
            continue
        t = r["roofline"]
        bound = max(t["compute_s"], t["memory_s"], t["collective_s"])
        mem = (r["memory"]["argument_bytes"] or 0) + (
            r["memory"]["temp_bytes"] or 0
        )
        print(f"{cell:52s} {r['status']:8s} {t['dominant']:10s} "
              f"{hlo_analysis.fmt_seconds(bound):>9s} "
              f"{t['roofline_fraction']:8.2%} {mem/2**30:8.2f}G")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--serve-mode", choices=("2d", "flat"), default="2d",
                    help="flat = baseline 1-D model axis for serve cells")
    ap.add_argument("--variant", default=None,
                    help="perf-iteration variant (see specs.VARIANTS)")
    args = ap.parse_args()

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]
    cells = []
    if args.all:
        for arch in ARCHS:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    results = []
    for multi in meshes:
        for arch, shape in cells:
            r = run_cell(arch, shape, multi, force=args.force,
                         serve_mode=args.serve_mode, variant=args.variant)
            results.append(r)
            status = r["status"]
            extra = ""
            if status == "ok":
                extra = (f"compile={r['compile_s']}s "
                         f"dom={r['roofline']['dominant']}")
            elif status == "error":
                extra = r["error"][:100]
            print(f"[{status:7s}] {arch} x {shape} x {r['mesh']} {extra}",
                  flush=True)
    summarize(results)


if __name__ == "__main__":
    main()
