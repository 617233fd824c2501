"""Production serving launcher (batched decode; see runtime/server.py).

    PYTHONPATH=src python -m repro.launch.serve --arch zamba2-7b --reduced

Fault-tolerance knobs (PR 7): ``--index-policy`` hardens prompts,
``--ttft-slo``/``--capacity-rps`` turn on SLO-aware shedding,
``--wave-deadline`` arms the wave watchdog, and ``--chaos-site wave``/
``--chaos-at`` inject a seeded fault schedule (see runtime/faults.py) to
exercise the wave retry path from the command line.
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
    ap.add_argument("--index-policy", default="strict",
                    choices=("strict", "clamp", "drop"),
                    help="prompt hardening: strict fails the "
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
    ap.add_argument("--chaos-site", default=None, choices=("wave",),
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
    srv = DecodeServer(lm, params, batch_slots=args.slots,
                       max_len=args.max_len,
                       prefill_chunk=args.prefill_chunk,
                       index_policy=args.index_policy,
                       capacity_rps=args.capacity_rps,
                       ttft_slo_s=args.ttft_slo,
                       wave_deadline_s=args.wave_deadline,
                       wave_retries=args.wave_retries,
                       faults=faults)
    _drive(srv, cfg, args, faults)


def _drive(srv, cfg, args, faults):
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
    if faults is not None:
        print("chaos:", faults.stats())


if __name__ == "__main__":
    main()
