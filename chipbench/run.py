"""Run one benchmark cell once, on the TPU this process finds.

    python3 chipbench/run.py --workload dlrm-v2.zipf --seed 7 --seconds 10 --trace 0

The cell is an entry of ``BENCHMARK.json`` at the checkout's root.  Its
configuration file names the path driver (``chipbench/drivers/<driver>.py``),
the traffic mix is ``chipbench/traffic/<traffic>.json`` and every metric is
read by ``chipbench/metrics/<metric>.py``.  With ``--trace 0`` the result
carries the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, the device's busy time from a profiler trace and a breakdown.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` when
traced, ``checks`` last); the last lines of standard error give each
number compared with its limit.  With no TPU, or fewer chips than the
cell asks for, the run exits non-zero and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# the checkout's root (for ``chipbench``) and the program's sources; the
# script's own directory is dropped so its modules never shadow others
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chipbench.harness import run_cell
    return run_cell(ROOT, args.workload, args.seed, args.seconds,
                    bool(args.trace), t0=T0)


if __name__ == "__main__":
    sys.exit(main())
