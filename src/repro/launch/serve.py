"""Production serving launcher (batched decode; see runtime/server.py).

    PYTHONPATH=src python -m repro.launch.serve --arch zamba2-7b --reduced

Fault-tolerance knobs (PR 7): ``--index-policy`` hardens prompt/offset
streams, ``--ttft-slo``/``--capacity-rps`` turn on SLO-aware shedding,
``--wave-deadline`` arms the wave watchdog, and ``--chaos-site``/
``--chaos-at`` inject a seeded fault schedule (see runtime/faults.py) to
exercise the recovery path from the command line.

Disaggregated embedding tier (PR 8): ``--disagg`` moves the stacked
tables into ``--replicas`` embedding-service processes
(runtime/embedding_service.py) reached over the fault-tolerant RPC tier —
``--rpc-timeout-s`` bounds every call, ``--degrade-policy`` decides what
a step does while every replica is dark (hot-slab lookups always serve
locally).
"""
from __future__ import annotations

import argparse
import collections

import jax
import numpy as np

from ..configs import get_config, get_reduced
from ..models import LM
from ..runtime.faults import FaultInjector, FaultSpec
from ..runtime.server import DecodeServer, Request
from .compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--pipeline", action="store_true",
                    help="cross-program pipelining: feed each wave's "
                         "access streams through the PipelineGroup")
    ap.add_argument("--index-policy", default="strict",
                    choices=("strict", "clamp", "drop"),
                    help="offset-stream hardening: strict fails the "
                         "request typed, clamp/drop repair and count")
    ap.add_argument("--ttft-slo", type=float, default=None, metavar="S",
                    help="server-wide TTFT budget (seconds); lapsed "
                         "requests expire, hopeless ones shed")
    ap.add_argument("--capacity-rps", default=None,
                    type=lambda s: s if s == "auto" else float(s),
                    help="calibrated service capacity (requests/s) for "
                         "submit-time predicted-wait shedding, or 'auto' "
                         "to self-calibrate from the measured wave-time "
                         "EWMA after a warmup wave count (live estimate "
                         "surfaced as serve_stats.capacity_rps_live)")
    ap.add_argument("--wave-deadline", type=float, default=None,
                    metavar="S", help="wave watchdog deadline (seconds)")
    ap.add_argument("--wave-retries", type=int, default=1)
    ap.add_argument("--disagg", action="store_true",
                    help="serve the embedding programs from a pool of "
                         "embedding-service replica processes (the "
                         "disaggregated tier) instead of in-process")
    ap.add_argument("--replicas", type=int, default=2,
                    help="embedding-service replicas behind --disagg")
    ap.add_argument("--rpc-timeout-s", type=float, default=30.0,
                    help="per-call RPC deadline of the service client")
    ap.add_argument("--artifact-dir", default=None,
                    help="AOT serving artifact directory "
                         "(core/artifact.py): boot loads the compiled "
                         "program + serialized executables from here "
                         "instead of compiling (fingerprint-gated, falls "
                         "back to a fresh compile); a fresh compile is "
                         "saved back after the first wave")
    ap.add_argument("--degrade-policy", default="fail",
                    choices=("fail", "stale"),
                    help="cold-lookup resolution while every replica is "
                         "dark: fail typed, or serve the local (possibly "
                         "stale) table copy")
    ap.add_argument("--chaos-site", default=None,
                    choices=("marshal", "transfer", "dispatch", "result",
                             "wave", "rpc_send", "rpc_recv", "heartbeat",
                             "service_crash"),
                    help="inject an InjectedFailure at this site")
    ap.add_argument("--chaos-at", type=int, nargs="*", default=[1],
                    help="1-based call ordinals of the site to fire at")
    ap.add_argument("--chaos-seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    faults = None
    if args.chaos_site is not None:
        faults = FaultInjector(
            [FaultSpec(args.chaos_site, at=tuple(args.chaos_at))],
            seed=args.chaos_seed)
    pool = None
    if args.disagg:
        from ..runtime.embedding_service import ServicePool
        pool = ServicePool(args.replicas, rpc_timeout_s=args.rpc_timeout_s,
                           heartbeat_interval_s=0.5, faults=faults)
    try:
        srv = DecodeServer(lm, params, batch_slots=args.slots,
                           max_len=args.max_len,
                           prefill_chunk=args.prefill_chunk,
                           pipeline=args.pipeline,
                           index_policy=args.index_policy,
                           capacity_rps=args.capacity_rps,
                           ttft_slo_s=args.ttft_slo,
                           wave_deadline_s=args.wave_deadline,
                           wave_retries=args.wave_retries,
                           faults=faults,
                           service="disagg" if args.disagg else "inproc",
                           service_pool=pool,
                           degrade_policy=args.degrade_policy,
                           artifact_dir=args.artifact_dir)
        _drive(srv, lm, cfg, args, faults, pool)
    finally:
        if pool is not None:
            pool.close()


def _drive(srv, lm, cfg, args, faults, pool):
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, 8).astype(
        np.int32), max_new_tokens=16) for _ in range(args.requests)]
    for r in reqs:
        srv.submit(r)
    steps = srv.run_until_drained()
    statuses = collections.Counter(r.status for r in reqs)
    print(f"served {len(reqs)} requests in {steps} serving iterations; "
          f"all done={all(r.done for r in reqs)}; "
          f"statuses={dict(statuses)}")
    print("serve_stats:", srv.serve_stats)
    if args.artifact_dir and srv.compile_stats is not None:
        print("artifact:", srv.compile_stats.get("artifact", {}))
    if pool is not None:
        print("service_pool:", pool.stats())
    if faults is not None:
        print("chaos:", faults.stats())
    if srv.pipeline_group is not None:
        print("pipeline_group:",
              srv.compile_stats.get("pipeline_group", {}))


if __name__ == "__main__":
    main()
