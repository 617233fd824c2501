#!/usr/bin/env python
"""Fail CI when a freshly-measured BENCH_*.json regresses a tracked
metric vs. the committed baseline by more than 10%.

The tier1 workflow refreshes the ``BENCH_*.json`` records in the workspace
(``scripts/tier1.sh --fast``); this script diffs the tracked metrics
against the versions committed at HEAD (``git show``).  Each metric is
direction-aware: exchange-bytes and serving-latency metrics are
lower-is-better (a >10% increase fails), serving-throughput metrics are
higher-is-better (a >10% drop fails).  Rate metrics tagged ``abs``
compare absolutely (baseline + 0.10), since a relative band around a 0.0
baseline is degenerate.  A metric missing on either side is reported and
skipped (new benches and schema growth are not regressions), as is a
record whose benchmark ``config`` differs from the baseline's (numbers
are only comparable within one workload — the mismatch is a warning and
exit 0, never a failure).

The workflow passes the PR's merge base (``origin/<base branch>``) or, on
push, ``HEAD^`` as the baseline ref — never the commit under test, which
could carry its own regressed records.  An unresolvable ref degrades to
all-skip (first push of a branch), not a failure.  A *malformed* record —
a fresh or committed ``BENCH_*.json`` that is not valid JSON — is a hard
error with a clear one-line message (exit 2, no traceback): silent skips
would let a corrupted baseline disable the gate.

    python scripts/check_bench_regression.py [--baseline-ref HEAD]

Exit codes: 0 ok (possibly with warnings), 1 regression(s), 2 malformed
records.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

#: (file, dotted metric path, direction[, mode[, tolerance]]).  "lower" =
#: lower is better, a +10% increase fails; "higher" = higher is better, a
#: -10% drop fails.  mode "abs" (rates in [0, 1]) replaces the relative
#: band with an absolute one: fresh may not exceed baseline + tolerance.
#: A per-metric tolerance (5th element) overrides the global 10% band for
#: metrics that need a wider one (noisy wall-clock ratios).
#: The exchange metrics are deterministic byte counts; the serving
#: metrics are wall-clock service numbers (the 10% band absorbs machine
#: noise at the smoke sizes tier1.sh --fast runs them at).
METRICS = (
    ("BENCH_sharded.json", "exchange_measured.index_bytes_per_step",
     "lower"),
    ("BENCH_sharded.json", "exchange_measured.row_bytes_per_step",
     "lower"),
    ("BENCH_sharded.json",
     "exchange_ablation.collective.index_bytes_per_step", "lower"),
    ("BENCH_sharded.json",
     "exchange_ablation.collective.row_bytes_per_step", "lower"),
    ("BENCH_locality.json", "exchange_index_bytes_per_step.hot_cold",
     "lower"),
    # adaptive hot slab (PR 9): under the drifting-head workload the
    # re-classifier must keep routed exchange near the stationary optimum
    # (bytes may not grow >10%) and the windowed hot hit-rate it recovers
    # after the final head rotation may not drop >10%
    ("BENCH_locality.json",
     "non_stationary.adaptive_routed_bytes_per_step", "lower"),
    ("BENCH_locality.json", "non_stationary.post_drift_hot_hit_rate",
     "higher"),
    # serving loop (PR 6): p99 service latency must not inflate, and
    # open-loop throughput may not fall behind the committed baseline
    ("BENCH_serving.json", "open_loop.saturating.ttft_ms.p99", "lower"),
    ("BENCH_serving.json", "open_loop.saturating.token_latency_ms.p99",
     "lower"),
    ("BENCH_serving.json", "open_loop.saturating.tokens_per_sec",
     "higher"),
    # fault tolerance (PR 7): the saturating point must not start shedding
    # where the baseline didn't — a shed-rate jump >0.10 absolute means
    # the server got slower and the SLO admission is covering for it
    ("BENCH_serving.json", "open_loop.saturating.shed_rate", "lower",
     "abs"),
    # disaggregated tier (PR 8): killing a replica mid-load must fail
    # ZERO steps (baseline 0; abs mode means any failure trips), and
    # the steady-state RPC overhead ratio must stay bounded — gated with
    # a loose per-metric tolerance (5th element) since it is a wall-clock
    # ratio of two small numbers
    ("BENCH_disagg.json", "disagg.failed_steps", "lower", "abs"),
    ("BENCH_disagg.json", "steady_state.overhead_ratio", "lower", "rel",
     0.5),
    # AOT serving artifact (PR 10): the artifact-loaded boot's TTFT may
    # not grow >10%, and its outputs must stay bit-identical to a fresh
    # compile — gated with zero relative tolerance (baseline 1; "abs"
    # would only bound above, so a 1 -> 0 flip must trip the rel band)
    ("BENCH_coldstart.json", "artifact_boot.ttft_s", "lower"),
    ("BENCH_coldstart.json", "artifact_boot.bit_identical", "higher",
     "rel", 0.0),
)

TOLERANCE = 0.10


def dig(record: dict, path: str):
    cur = record
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def baseline_json(ref: str, name: str):
    """Returns ``(record, reason)``: record is the parsed baseline or
    None, reason one of "ok" | "no-ref" (unresolvable baseline ref — skip
    everything) | "missing" (file absent at the ref — a new bench) |
    "malformed" (present but not JSON — a hard error)."""
    p = subprocess.run(["git", "show", f"{ref}:{name}"],
                       capture_output=True, text=True, cwd=REPO)
    if p.returncode != 0:
        err = p.stderr.lower()
        if "invalid object name" in err or "unknown revision" in err or \
                "bad revision" in err:
            return None, "no-ref"
        return None, "missing"
    try:
        return json.loads(p.stdout), "ok"
    except json.JSONDecodeError as e:
        print(f"ERROR {name}@{ref}: baseline record is not valid JSON "
              f"({e})", file=sys.stderr)
        return None, "malformed"


def fresh_json(path: Path):
    """Parse a workspace record; a malformed file is a clear one-line
    error (never a traceback)."""
    try:
        return json.loads(path.read_text()), "ok"
    except json.JSONDecodeError as e:
        print(f"ERROR {path.name}: fresh record is not valid JSON ({e})",
              file=sys.stderr)
        return None, "malformed"
    except OSError as e:
        print(f"ERROR {path.name}: cannot read fresh record ({e})",
              file=sys.stderr)
        return None, "malformed"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--baseline-ref", default="HEAD",
                    help="git ref holding the committed baseline records")
    args = ap.parse_args()

    failures = []
    malformed = []
    config_mismatches = []
    records: dict = {}    # file name -> (fresh_rec, base_rec, comparable)
    for metric in METRICS:
        name, path, direction = metric[0], metric[1], metric[2]
        mode = metric[3] if len(metric) > 3 else "rel"
        tol = metric[4] if len(metric) > 4 else TOLERANCE
        if name not in records:
            records[name] = _load_pair(name, malformed, config_mismatches,
                                       args.baseline_ref)
        fresh_rec, base_rec, comparable = records[name]
        if not comparable:
            continue
        fresh = dig(fresh_rec, path)
        base = dig(base_rec, path) if base_rec else None
        if fresh is None or base is None:
            print(f"SKIP {name}:{path} (metric absent: "
                  f"fresh={fresh} baseline={base})")
            continue
        if mode == "abs":
            limit = base + tol
            bad = fresh > limit
        elif direction == "lower":
            limit = base * (1 + tol)
            bad = fresh > limit
        else:
            limit = base * (1 - tol)
            bad = fresh < limit
        status = "FAIL" if bad else "ok"
        print(f"{status:4} {name}:{path} [{direction}"
              f"{',abs' if mode == 'abs' else ''}]  baseline={base}  "
              f"fresh={fresh}  limit={limit:.4g}")
        if bad:
            failures.append((name, path, base, fresh))
    if config_mismatches:
        print(f"\nWARNING: {len(config_mismatches)} record(s) skipped on "
              f"config mismatch (baselines measured under a different "
              f"workload): {', '.join(sorted(set(config_mismatches)))}")
    if malformed:
        print(f"\n{len(malformed)} malformed benchmark record(s): "
              f"{', '.join(sorted(set(malformed)))} — regenerate with "
              f"scripts/tier1.sh --fast", file=sys.stderr)
        return 2
    if failures:
        print(f"\n{len(failures)} benchmark regression(s) > "
              f"{TOLERANCE:.0%} vs {args.baseline_ref}", file=sys.stderr)
        return 1
    return 0


def _load_pair(name: str, malformed: list, config_mismatches: list,
               ref: str):
    """Load fresh + baseline records for one file; returns
    ``(fresh, base, comparable)``, recording malformed records and
    config mismatches for the summary."""
    fresh_path = REPO / name
    if not fresh_path.exists():
        print(f"SKIP {name} (no fresh record)")
        return None, None, False
    fresh_rec, fstate = fresh_json(fresh_path)
    if fstate == "malformed":
        malformed.append(name)
        return None, None, False
    base_rec, bstate = baseline_json(ref, name)
    if bstate == "malformed":
        malformed.append(f"{name}@{ref}")
        return fresh_rec, None, False
    if bstate == "no-ref":
        print(f"SKIP {name} (baseline ref {ref!r} not resolvable — "
              f"first push?)")
        return fresh_rec, None, False
    if bstate == "missing":
        print(f"SKIP {name} (no baseline at {ref} — new bench)")
        return fresh_rec, None, False
    # metrics are only comparable between runs of the same workload:
    # a baseline committed from a full-size run must not silently
    # gate (or trip on) a --fast measurement
    fresh_cfg = (fresh_rec or {}).get("config")
    base_cfg = (base_rec or {}).get("config")
    if fresh_cfg != base_cfg:
        print(f"SKIP {name} (configs differ: fresh={fresh_cfg} "
              f"baseline={base_cfg})")
        config_mismatches.append(name)
        return fresh_rec, base_rec, False
    return fresh_rec, base_rec, True


if __name__ == "__main__":
    sys.exit(main())
