import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run (deliverable e).

For every (architecture × input shape × mesh) cell: build the step bundle,
``jit(...).lower(...)``, ``.compile()``, and record
``memory_analysis`` / ``cost_analysis`` / collective-bytes (parsed from the
HLO) into ``experiments/dryrun/<arch>__<shape>__<mesh>.json``.

The single-pod 16×16 mesh feeds the roofline table; the 2×16×16 multi-pod
mesh proves the ``pod`` axis shards.  Any failure here (sharding mismatch,
compile-time OOM, unsupported collective) is a bug in the framework.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-4b \
        --shape train_4k --mesh single [--compile-only]
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from ..configs import get_config, list_archs
from ..roofline.analysis import (TARGET_KIND, collective_bytes_from_hlo,
                                 roofline_terms)
from .mesh import make_production_mesh
from .steps import SHAPES, build_bundle, shape_applicable

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def run_cell(arch: str, shape: str, mesh_kind: str, remat: str = "dots",
             skip_existing: bool = True, do_cost: bool = True,
             variant: str = "", overrides: dict = None) -> dict:
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    suffix = f"__{variant}" if variant else ""
    out_path = OUT_DIR / f"{arch}__{shape}__{mesh_kind}{suffix}.json"
    if skip_existing and out_path.exists():
        prev = json.loads(out_path.read_text())
        # re-run when a cost pass is requested but missing from the record
        if not (do_cost and mesh_kind == "single"
                and prev.get("status") == "ok"
                and "roofline" not in prev):
            return prev

    import dataclasses as _dc
    cfg = get_config(arch)
    if overrides:
        cfg = _dc.replace(cfg, **overrides)
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind, "remat": remat,
           "variant": variant, "overrides": overrides or {}}
    skip = shape_applicable(cfg, shape)
    if skip:
        rec["status"] = "skipped"
        rec["reason"] = skip
        out_path.write_text(json.dumps(rec, indent=2))
        return rec

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
        with jax.set_mesh(mesh):
            bundle = build_bundle(cfg, mesh, shape, remat=remat)
            jitted = jax.jit(bundle.fn, in_shardings=bundle.in_shardings)
            lowered = jitted.lower(*bundle.args)
            rec["lower_s"] = round(time.time() - t0, 1)
            hlo = lowered.as_text()
            rec["collective_bytes"] = collective_bytes_from_hlo(hlo)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 1)
            mem = compiled.memory_analysis()
            rec["memory"] = {
                k: int(getattr(mem, k, 0) or 0)
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes",
                          "alias_size_in_bytes")}
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            rec["flops_scanned"] = float((cost or {}).get("flops", 0.0))
            rec["bytes_scanned"] = float(
                (cost or {}).get("bytes accessed", 0.0))

            # --- cost pass (single-pod only): scan-free/unrolled variant.
            # XLA cost analysis counts while-loop bodies ONCE, so the
            # scanned program undercounts; the unrolled cost-mode COMPILED
            # module gives trip-correct, fusion-real, post-SPMD PER-DEVICE
            # flops / bytes / collective traffic (roofline methodology in
            # EXPERIMENTS.md).
            if mesh_kind == "single" and do_cost:
                t2 = time.time()
                cost_bundle = build_bundle(cfg, mesh, shape, remat="none",
                                           cost_mode=True)
                cost_lowered = jax.jit(
                    cost_bundle.fn,
                    in_shardings=cost_bundle.in_shardings).lower(
                        *cost_bundle.args)
                ccost_lo = cost_lowered.cost_analysis() or {}
                # global (pre-SPMD) flops — fallback + cross-check
                rec["flops_global_lowered"] = float(
                    ccost_lo.get("flops", 0.0))
                n = mesh.devices.size
                try:
                    cost_compiled = cost_lowered.compile()
                    ccost = cost_compiled.cost_analysis()
                    if isinstance(ccost, (list, tuple)):
                        ccost = ccost[0] if ccost else {}
                    rec["flops_per_device"] = float(ccost.get("flops", 0.0))
                    rec["bytes_per_device"] = float(
                        ccost.get("bytes accessed", 0.0))
                    rec["coll_bytes_per_device"] = collective_bytes_from_hlo(
                        cost_compiled.as_text())
                    rec["cost_compiled"] = True
                except Exception as ce:  # noqa: BLE001 — degrade gracefully
                    rec["cost_compiled"] = False
                    rec["cost_compile_error"] = f"{type(ce).__name__}: {ce}"
                    rec["flops_per_device"] = \
                        rec["flops_global_lowered"] / n
                    f = (rec["flops_global_lowered"] /
                         (rec["flops_scanned"] * n)
                         if rec["flops_scanned"] else 1.0)
                    rec["bytes_per_device"] = rec["bytes_scanned"] * max(f, 1)
                    rec["coll_bytes_per_device"] = \
                        collective_bytes_from_hlo(hlo)
                rec["cost_pass_s"] = round(time.time() - t2, 1)
                rec["flops"] = rec["flops_per_device"] * n
                rec["roofline"] = roofline_terms(
                    flops=rec["flops_per_device"],
                    bytes_accessed=rec["bytes_per_device"],
                    collective_bytes=rec["coll_bytes_per_device"],
                    n_chips=1,  # all quantities are per-device already
                    device_kind=TARGET_KIND)
            rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
    rec["total_s"] = round(time.time() - t0, 1)
    out_path.write_text(json.dumps(rec, indent=2))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multipod"])
    ap.add_argument("--remat", default="dots",
                    choices=["none", "dots", "full"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-cost", action="store_true",
                    help="skip the (expensive) unrolled cost pass")
    args = ap.parse_args()

    cells = []
    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = ["single", "multipod"] if args.all else [args.mesh]
    for a in archs:
        for s in shapes:
            for m in meshes:
                cells.append((a, s, m))

    for arch, shape, mesh_kind in cells:
        rec = run_cell(arch, shape, mesh_kind, remat=args.remat,
                       skip_existing=not args.force,
                       do_cost=not args.no_cost)
        status = rec["status"]
        extra = (f"flops={rec.get('flops', 0):.3e} "
                 f"coll={rec.get('collective_bytes', 0):.3e}B "
                 f"t={rec.get('total_s', '?')}s"
                 if status == "ok" else rec.get("reason",
                                                rec.get("error", ""))[:90])
        print(f"[{status:7s}] {arch:24s} {shape:12s} {mesh_kind:8s} {extra}",
              flush=True)


if __name__ == "__main__":
    main()
