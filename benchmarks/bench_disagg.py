"""Disaggregated embedding tier graded as a service (PR-8 tentpole).

Three legs:

* **Bit identity** — the same program stepped through an in-process
  executor and through the disaggregated service path
  (``service="disagg"``: streams over the RPC tier, tables resident in
  the replica processes) must produce byte-identical outputs.  Asserted
  here, recorded for the gate.

* **Steady state** — median us/step of both paths on the same inputs.
  ``overhead_ratio`` (disagg/inproc) is what the submit/result overlap is
  supposed to bound: the request leaves at submit, the reply is consumed
  at result, so the extra hop hides behind the work between them.  Gated
  in CI with a loose per-metric tolerance (wall-clock ratio of two small
  numbers is noisy).

* **Kill a replica mid-load** — a model's embedding program
  (``lm.embedding_program(slots, 1)``) stepped through a disaggregated
  executor over a 2-replica pool with the heartbeat monitor armed; one
  replica gets SIGKILL mid load.  Required: every step returns the
  in-process executor's outputs and ``failed_steps == 0`` (the pool's
  in-step failover absorbs the crash), the pool recovers the replica via
  respawn + checkpoint re-warm, and ``recovery_s`` is recorded from the
  pool's breaker-open→probe-pass timestamps.  ``failed_steps`` is gated
  absolutely: the baseline is 0, any failure trips CI.

Writes ``BENCH_disagg.json``; registered in ``benchmarks/run.py`` as
``disagg``.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

DEFAULT_OUT = Path(__file__).resolve().parent.parent / "BENCH_disagg.json"

ARCH = "zamba2-7b"              # no experts: two token gathers a step

POOL_KW = dict(rpc_timeout_s=30.0, backoff_s=0.01)


def _program():
    from repro.core.ops import EmbeddingOp, EmbeddingProgram
    sls = EmbeddingOp("sls", num_segments=32, num_embeddings=2048,
                      emb_len=64, avg_lookups=16, weighted=True)
    gather = EmbeddingOp("gather", num_segments=16, num_embeddings=512,
                         emb_len=64, block_rows=2)
    return EmbeddingProgram("bench_disagg", (("sls0", sls), ("g0", gather)))


def _median_us_per_step(ex, ins, steps: int, repeats: int) -> float:
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        ex.run_steps([ins] * steps)
        ts.append((time.perf_counter() - t0) / steps * 1e6)
    return float(np.median(ts))


def _identity_and_steady(pool, fast: bool) -> tuple:
    from repro.core.executor import executor_for
    from repro.core.ops import make_program_inputs
    prog = _program()
    ins = make_program_inputs(prog, seed=0)
    steps, repeats = (8, 3) if fast else (32, 5)

    inproc = executor_for(prog, backend="jax")
    disagg = executor_for(prog, backend="jax", service="disagg",
                          service_pool=pool)
    ref = inproc.run_steps([ins] * 3)
    out = disagg.run_steps([ins] * 3)
    identical = all(
        np.array_equal(np.asarray(r[k]), np.asarray(o[k]))
        for r, o in zip(ref, out) for k in r)
    assert identical, "disagg outputs diverged from in-process"

    # both paths warmed above; measure steady state
    us_in = _median_us_per_step(inproc, ins, steps, repeats)
    us_di = _median_us_per_step(disagg, ins, steps, repeats)
    return ({"identical": bool(identical), "steps_compared": 3},
            {"inproc_us_per_step": round(us_in, 1),
             "disagg_us_per_step": round(us_di, 1),
             "overhead_ratio": round(us_di / us_in, 3),
             "rpc_steps": disagg.stats["rpc_steps"]})


def _kill_leg(fast: bool) -> dict:
    import jax
    from repro.configs import get_reduced
    from repro.core.access_plan import EmberFault
    from repro.core.executor import executor_for
    from repro.models import LM
    from repro.runtime.embedding_service import ServicePool

    cfg = get_reduced(ARCH)
    lm = LM(cfg)
    table = lm.init(jax.random.PRNGKey(0))["embed"]
    n_steps, slots = (24, 2) if fast else (64, 4)
    prog = lm.embedding_program(slots, 1)
    rng = np.random.default_rng(0)
    steps = []
    for _ in range(n_steps):
        toks = rng.integers(0, cfg.vocab_size, slots).astype(np.int32)
        steps.append({name: {"table": table, "idxs": toks}
                      for name, _ in prog.ops})
    ref = executor_for(prog, backend="jax").run_steps(steps)

    with ServicePool(2, heartbeat_interval_s=0.05, **POOL_KW) as pool:
        ex = executor_for(prog, backend="jax", service="disagg",
                          service_pool=pool)
        ex.step(steps[0])               # warm the service bind

        # one replica SIGKILLed a third of the way through the steps
        kill_at = n_steps // 3
        failed = mismatched = 0
        for i, ins in enumerate(steps):
            if i == kill_at:
                victim = next(j for j, r in enumerate(pool.replicas)
                              if r.state == "live")
                pool.kill_replica(victim)
            try:
                out = ex.step(ins)
            except EmberFault:
                failed += 1
                continue
            mismatched += not all(
                np.array_equal(np.asarray(out[k]), np.asarray(ref[i][k]))
                for k in ref[i])

        # the monitor thread drives respawn + artifact re-warm; wait for
        # the pool to be whole again so recovery_s lands in the record
        t_rec = time.perf_counter()
        while any(r.state != "live" for r in pool.replicas):
            time.sleep(0.05)
            assert time.perf_counter() - t_rec < 180, \
                "replica never recovered"
        stats = pool.stats()

    assert mismatched == 0, \
        f"{mismatched} steps diverged from the in-process executor"
    return {"steps": n_steps,
            "failed_steps": failed,
            "recovery_s": round(stats["recoveries_s"][-1], 3)
            if stats["recoveries_s"] else None,
            "rewarm_source": stats["warm_sources"][-1],
            "compile_source": stats["compile_sources"][-1],
            "pool": {k: stats[k] for k in
                     ("failovers", "retries", "respawns", "breaker_open",
                      "heartbeats", "hb_misses")}}


def run_disagg(fast: bool) -> dict:
    from repro.runtime.embedding_service import ServicePool
    with ServicePool(2, **POOL_KW) as pool:
        identity, steady = _identity_and_steady(pool, fast)
    kill = _kill_leg(fast)
    assert kill["failed_steps"] == 0, \
        f"replica kill failed {kill['failed_steps']} steps"
    assert kill["rewarm_source"] == "artifact", \
        "respawned replica did not re-warm from the checkpoint artifact"
    assert kill["compile_source"] == "artifact", \
        "respawned replica recompiled instead of loading the AOT artifact"
    return {"config": {"fast": fast, "arch": ARCH, "replicas": 2,
                       "rpc_timeout_s": POOL_KW["rpc_timeout_s"]},
            "bit_identity": identity,
            "steady_state": steady,
            "disagg": kill}


def run(report, fast: bool = True, out_path: Path = DEFAULT_OUT) -> dict:
    rec = run_disagg(fast)
    report("disagg/bit_identity", 0, rec["bit_identity"]["identical"])
    ss = rec["steady_state"]
    report("disagg/steady_state_us", ss["disagg_us_per_step"],
           f"inproc={ss['inproc_us_per_step']} "
           f"ratio={ss['overhead_ratio']}")
    k = rec["disagg"]
    report("disagg/kill_recovery_s", 0,
           f"recovery={k['recovery_s']}s failed={k['failed_steps']} "
           f"rewarm={k['rewarm_source']} compile={k['compile_source']}")
    out_path.write_text(json.dumps(rec, indent=2))
    report("disagg/json", 0, str(out_path))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="smoke sizes (tier1.sh --fast)")
    ap.add_argument("--out", type=Path, default=DEFAULT_OUT)
    args = ap.parse_args()

    def report(name, us, derived):
        print(f"{name},{us:.1f},{derived}", flush=True)

    rec = run(report, fast=args.fast, out_path=args.out)
    print(f"disagg overhead {rec['steady_state']['overhead_ratio']}x; "
          f"kill leg: {rec['disagg']['failed_steps']} failed, "
          f"recovered in {rec['disagg']['recovery_s']}s "
          f"({rec['disagg']['rewarm_source']})")


if __name__ == "__main__":
    main()
