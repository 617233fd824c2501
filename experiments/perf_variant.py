"""§Perf lower-only variant comparator.

Full cost-mode COMPILES take ~15 min/cell on this 1-core host, so hillclimb
iterations are compared on the cost-mode LOWERING (seconds–minutes):

* ``flops``: trip-correct global FLOPs (scan-free/unrolled program);
* ``shard_map collective bytes``: the embed-psum / vocab-parallel-CE /
  MoE-all-to-all traffic is explicit pre-SPMD (these are exactly the
  collectives the hillclimb levers touch); GSPMD-inserted gradient
  all-reduces are invariant across these variants (same params).

The anchored baseline for each cell is its full compiled record from
``experiments/dryrun``.

    PYTHONPATH=src python experiments/perf_variant.py qwen3-moe-235b-a22b \
        train_4k v_cap105 capacity_factor=1.05
"""
import json
import sys
import time

# device-count flag must precede any jax import
from repro.launch.dryrun import OUT_DIR  # noqa: F401  (sets XLA_FLAGS)
import jax

from repro.configs import get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_bundle
from repro.roofline.analysis import collective_bytes_from_hlo


def parse_val(v):
    for cast in (int, float):
        try:
            return cast(v)
        except ValueError:
            pass
    return v


def main():
    arch, shape, variant = sys.argv[1:4]
    overrides = {k: parse_val(v) for k, v in
                 (kv.split("=", 1) for kv in sys.argv[4:])}
    import dataclasses
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    mesh = make_production_mesh()
    t0 = time.time()
    with jax.set_mesh(mesh):
        b = build_bundle(cfg, mesh, shape, remat="none", cost_mode=True)
        lo = jax.jit(b.fn, in_shardings=b.in_shardings).lower(*b.args)
        ca = lo.cost_analysis() or {}
        txt = lo.as_text()
    rec = {
        "arch": arch, "shape": shape, "variant": variant,
        "overrides": overrides,
        "flops_global": float(ca.get("flops", 0.0)),
        "shardmap_collective_bytes": collective_bytes_from_hlo(txt),
        "lower_s": round(time.time() - t0, 1),
    }
    out = OUT_DIR / f"perf__{arch}__{shape}__{variant}.json"
    out.write_text(json.dumps(rec, indent=2))
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
