"""Assemble the §Roofline table from experiments/dryrun/*.json.

    PYTHONPATH=src python -m repro.roofline.report [--markdown]
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

from ..configs import get_config
from ..launch.steps import SHAPES
from .analysis import (TARGET_KIND, analytic_bytes_per_device, analytic_flops,
                       model_flops, peaks, roofline_terms)

DRYRUN = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

ADVICE = {
    "compute": "raise MXU utilization: larger fused matmul tiles / drop "
               "the causal-masking FLOP waste in attention",
    "memory": "cut HBM traffic: fuse producer→consumer chains, keep "
              "attention blocks VMEM-resident (flash kernel), bf16 "
              "activations end-to-end",
    "collective": "overlap or shrink collectives: reduce-scatter instead "
                  "of all-reduce, seq-parallel embed, int8 grad compression",
}


def load_cells(mesh="single"):
    cells = []
    for f in sorted(DRYRUN.glob(f"*__{mesh}.json")):
        cells.append(json.loads(f.read_text()))
    return cells


def build_rows(mesh="single"):
    rows = []
    for rec in load_cells(mesh):
        arch, shape = rec["arch"], rec["shape"]
        row = {"arch": arch, "shape": shape, "status": rec["status"]}
        if rec["status"] == "skipped":
            row["note"] = rec["reason"][:60]
            rows.append(row)
            continue
        if rec["status"] != "ok":
            row["note"] = rec.get("error", "")[:60]
            rows.append(row)
            continue
        seq, batch, kind = SHAPES[shape]
        cfg = get_config(arch)
        mf = model_flops(cfg, seq, batch, kind)
        if "roofline" not in rec:
            # analytic fallback: the unrolled cost compile has not landed
            # for this cell — estimate terms from analytic FLOPs + the
            # scanned compile's (loop-body-once) traffic, clearly marked
            n = 256
            af = analytic_flops(cfg, seq, batch, kind)
            fscan = rec.get("flops_scanned", 0.0) * n
            scale = af / fscan if fscan else 1.0
            rec = dict(rec)
            rec["flops"] = af
            rec["cost_compiled"] = False
            rec["roofline"] = roofline_terms(
                flops=af / n,
                bytes_accessed=rec.get("bytes_scanned", 0.0) * max(scale, 1),
                collective_bytes=rec.get("collective_bytes", 0.0),
                n_chips=1, device_kind=TARGET_KIND)
        r = rec["roofline"]
        hlo_total = rec.get("flops", 0.0)
        ab = analytic_bytes_per_device(cfg, seq, batch, kind)
        mem_an = ab / peaks(TARGET_KIND).hbm_bw
        # verdict uses the analytic production-path memory: the HLO memory
        # number is an upper bound inflated by cost-mode dense attention
        # (and trip-scaling for fallback cells) — both are reported
        terms = {"compute": r["compute_s"], "memory": mem_an,
                 "collective": r["collective_s"]}
        dom = max(terms, key=terms.get)
        bound = max(terms.values())
        r = dict(r, bottleneck=dom, step_lower_bound_s=bound,
                 roofline_fraction_compute=(r["compute_s"] / bound
                                            if bound else 0.0))
        row.update({
            "memory_s_analytic": mem_an,
            "compute_s": r["compute_s"], "memory_s": r["memory_s"],
            "collective_s": r["collective_s"],
            "bottleneck": r["bottleneck"],
            "bound_s": r["step_lower_bound_s"],
            "roofline_frac": r["roofline_fraction_compute"],
            "model_flops": mf,
            "hlo_flops": hlo_total,
            "useful_ratio": mf / hlo_total if hlo_total else float("nan"),
            "cost_compiled": rec.get("cost_compiled", False),
            "advice": ADVICE[r["bottleneck"]],
        })
        rows.append(row)
    return rows


def markdown(rows) -> str:
    out = ["| arch | shape | compute_s | memory_s (HLO) | memory_s (analytic) "
           "| collective_s | bottleneck | roofline-frac | MODEL/HLO | note |",
           "|---|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if "bottleneck" not in r:
            out.append(f"| {r['arch']} | {r['shape']} | — | — | — | — | "
                       f"{r['status']} | — | — | {r.get('note','')} |")
            continue
        flag = "" if r["cost_compiled"] else " (est)"
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.2e} | "
            f"{r['memory_s']:.2e} | {r.get('memory_s_analytic', 0):.2e} | "
            f"{r['collective_s']:.2e} | "
            f"**{r['bottleneck']}** | {r['roofline_frac']:.2f} | "
            f"{r['useful_ratio']:.2f}{flag} | {r['advice'][:44]}… |")
    return "\n".join(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single")
    args = ap.parse_args()
    rows = build_rows(args.mesh)
    print(markdown(rows))
    ok = [r for r in rows if "bottleneck" in r]
    if ok:
        from collections import Counter
        c = Counter(r["bottleneck"] for r in ok)
        print(f"\nbottleneck distribution: {dict(c)}")
        worst = sorted(ok, key=lambda r: r["roofline_frac"])[:3]
        print("lowest roofline fractions:",
              [(r["arch"], r["shape"], round(r["roofline_frac"], 3))
               for r in worst])
        coll = sorted(ok, key=lambda r: -(r["collective_s"] /
                                          max(r["bound_s"], 1e-12)))[:3]
        print("most collective-bound:",
              [(r["arch"], r["shape"]) for r in coll])


if __name__ == "__main__":
    main()
