"""Read a cell's compared numbers and its control's, on several seeds in one
process, on the chip.

    python3 chipbench/control.py --workload stablelm-3b.chat --seconds 30 --seeds 11 12 13

Each seed is one run of the cell as the benchmark makes it (set-up, the
window at the cell's own load, the comparison with the reference), and
then the control in the program's place: the reference one precision below
the configuration's (bfloat16 rows for float32 tables, fp8 weights for a
bfloat16 model), held to the same checks, names and limits.  Prints one
JSON line per seed with ``correct`` for the program and for the control,
as a benchmark run decides it; exits 1 if any control comes out correct.
Benchmark runs never run the control.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from chipbench import harness
    spec = harness.cell_spec(ROOT, args.workload)
    harness.enable_compile_cache(ROOT)
    try:
        devices = harness.require_chips(spec.workload["chips"])
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    rc = 0
    for seed in args.seeds:
        run, out = harness.drive(spec, seed, args.seconds, False,
                                 devices=devices, t0=time.perf_counter(),
                                 control=True)
        line = judge(out)
        print(json.dumps({"workload": spec.name, "seed": seed,
                          "setup_s": run.facts["setup_s"], **line}),
              flush=True)
        rc |= int(line["control_correct"])
        del run, out
        gc.collect()
    return rc


def judge(out: dict) -> dict:
    """``correct`` of the program and of the control, by the benchmark's
    own rule, with every number beside its limit."""
    from chipbench import harness
    return {"correct": harness.correct(out["checks"]),
            "control_correct": harness.correct(out["control_checks"]),
            "checks": harness.check_line(out["checks"]),
            "control_checks": harness.check_line(out["control_checks"]),
            **out.get("readings", {})}


if __name__ == "__main__":
    sys.exit(main())
