#!/usr/bin/env bash
# Tier-1 verification entrypoint (the ROADMAP command, with PYTHONPATH set).
#
#   scripts/tier1.sh            # exactly the ROADMAP tier-1 run (full
#                               # differential sweep: >=200 generated cases)
#   scripts/tier1.sh --fast     # + no cacheprovider (clean CI workspaces)
#                               # + differential smoke subset (pytest --fast)
#                               # + steady-state executor bench smoke run
#   scripts/tier1.sh [pytest args...]   # extra args forwarded to pytest
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

FAST=0
EXTRA=()
if [[ "${1:-}" == "--fast" ]]; then
  FAST=1
  # --fast (tests/conftest.py) gates the generated differential cases to a
  # smoke subset, the same way this script gates the benches below
  EXTRA+=(-p no:cacheprovider --fast)
  shift
fi
python -m pytest -x -q "${EXTRA[@]}" "$@"

if [[ "$FAST" == 1 ]]; then
  # steady-state throughput smoke: asserts the partitioner's VMEM audit,
  # the overlap>=cached ordering, and refreshes BENCH_steady_state.json
  # (small sizes; seconds, not minutes)
  python benchmarks/bench_steady_state.py --fast
  # vocab-sharded smoke (the bench respawns itself in a subprocess with a
  # forced 2-device CPU mesh — no env leak into this shell): asserts
  # sharded numerics == replicated for BOTH exchange modes, fewer host
  # syncs + reduce-scattered output bytes on the collective path, and the
  # per-device footprint halving, refreshes BENCH_sharded.json
  python benchmarks/bench_sharded.py --fast --exchange=both
  # locality-aware hot/cold sharding smoke (same respawn pattern): asserts
  # outputs identical to the interleaved PR-3 path AND >= 2x less routed
  # exchange volume on the Zipf stream; the non-stationary leg rotates the
  # Zipf head every N steps and asserts the adaptive re-classifier holds
  # routed exchange <= 2x the stationary optimum (static degrades >= 4x)
  # with outputs bit-identical to a cold-built oracle across every slab
  # swap, incl. collective+host exchange, the spill router and the disagg
  # republish path; refreshes BENCH_locality.json
  python benchmarks/bench_locality.py --fast
  # open-loop serving smoke: continuous-batching server under Poisson load
  # at 2 QPS points + a 16x overload point (asserts the SLO admission
  # sheds instead of queueing unboundedly), refreshes BENCH_serving.json
  python benchmarks/bench_serving.py --fast
  # disaggregated embedding tier smoke: asserts disagg outputs are
  # bit-identical to in-process, measures the steady-state RPC overhead
  # ratio, and runs the kill-a-replica-mid-load leg (failover + respawn +
  # checkpoint re-warm; failed_steps==0 required), refreshes
  # BENCH_disagg.json
  python benchmarks/bench_disagg.py --fast
  # cold-start smoke: boots the same program three ways in subprocesses
  # (cold compile / in-process warm caches / AOT serving artifact) and
  # asserts the artifact boot loads instead of compiling
  # (compile_source=artifact, zero AOT compiles), is bit-identical to the
  # fresh compile, and >= 3x faster TTFT; refreshes BENCH_coldstart.json
  python benchmarks/bench_coldstart.py --fast
  # chaos leg: the seeded fault-injection suite replayed under a pinned
  # seed — per-site executor recovery, wave watchdog + bounded retry,
  # hardening policies, and the rpc/service sites of the disaggregated
  # tier (rpc_send/rpc_recv severing + service_crash respawn).  The full
  # pytest above already ran it once with the default seed; this replay
  # pins the probabilistic schedules.
  CHAOS_SEED=7 python -m pytest -x -q -p no:cacheprovider --fast \
    tests/test_faults.py tests/test_disagg.py
fi
