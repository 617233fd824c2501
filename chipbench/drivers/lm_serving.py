"""LM serving through the program's ``DecodeServer``.

The timed path is ``DecodeServer.submit`` / ``DecodeServer.step``, built as
``launch/serve.py`` builds it from the configuration's ``serving`` block.
Every wave embeds its tokens, runs the masked decode micro-steps through
the KV cache and the output head, and takes the argmax on the host.

Set-up makes the weights on the device in one jitted call from the seed
(``references/lm_dense.py``) and draws the request pool from the seed.
It then runs a ramp that staggers the clients: each client's first
request has a prompt of one prefill chunk and ``2 + client * stagger /
clients`` new tokens, where ``stagger`` is the waves of a median request,
so both wave shapes compile and the clients reach the pool one quarter
of a request apart.  The window opens once every ramp request is done and
``stagger`` waves have run, with the pool requests in flight at fixed,
staggered phases.  Clients run a closed loop: each submits its next pool
request as soon as its last one is done.

``correct``: once the window has closed, the server keeps serving what it
holds, admitting nothing new, until requests of the window with
``CHECK_TOKENS`` served tokens between them have finished (at most
``DRAIN_S``).  With the server gone,
a sample of them (the one with most served tokens, then others drawn from
the seed) is run through the float32 reference.  At each served token's
position the reference's best logit lies some gap above the served
token's; ``token_gap_mean`` is the mean of those gaps over the sample.
Its widest gap is printed too, but is no compared number: with random
weights it is bounded by the small margins between the top logits, so
the fp8 control reads only 2-3 times the program (PERF.md, section 6).
"""
from __future__ import annotations

import gc
import sys
import time
from pathlib import Path

import numpy as np

from chipbench import counts
from chipbench.generate import lm_requests
from chipbench.harness import Check, load_module, memory_peak
from chipbench.peaks import peaks

#: served tokens the correctness sample reaches for, and its most
#: requests (the reference's batch is always ``SAMPLE_MAX`` rows, so every
#: seed checks with the same shapes)
SAMPLE_TOKENS, SAMPLE_MAX = 256, 16
#: after the window: serve on until finished requests hold this many
#: served tokens, for at most this long
CHECK_TOKENS, DRAIN_S = 256, 60.0
#: mean gap of a served token's logit below the reference's best, in
#: logits; see PERF.md for the readings it was set from
TOKEN_GAP_LIMIT = 0.005


def _ref():
    return load_module(Path(__file__).resolve().parent.parent
                       / "references" / "lm_dense.py")


def model_config(cfg: dict):
    from repro.models.common import ModelConfig
    return ModelConfig(**{k: tuple(v) if isinstance(v, list) else v
                          for k, v in cfg.items()})


class WaveLog:
    """Wraps the server's jitted wave: the tokens each wave feeds and the
    live context they attend over, with the wave's start time."""

    def __init__(self, srv):
        self.srv = srv
        self.inner = srv._wave
        self.waves = []             # (t, tokens fed, sum of contexts)
        srv._wave = self

    def __call__(self, params, tokens, lens, caches):
        n = np.asarray(lens).astype(np.int64)
        pos = self.srv._pos.astype(np.int64)
        ctx = int((n * pos + n * (n + 1) // 2).sum())
        self.waves.append((time.perf_counter(), int(n.sum()), ctx))
        return self.inner(params, tokens, lens, caches)


def serve(run, srv, pool: list):
    """Ramp, then the window.  Returns every request and the pool
    requests among them."""
    from repro.runtime.server import Request
    clients = int(run.traffic["clients"])
    chunk = srv.prefill_chunk
    rng = run.rng(4)
    reqs, pool_reqs = [], []
    nxt = 0

    def submit(prompt, new, from_pool):
        r = Request(prompt=prompt, max_new_tokens=new)
        srv.submit(r)
        reqs.append(r)
        if from_pool:
            pool_reqs.append(r)
        return r

    def next_from_pool():
        nonlocal nxt
        prompt, new = pool[nxt % len(pool)]
        nxt += 1
        return submit(prompt, new, True)

    vocab = run.config["model"]["vocab_size"]
    stagger = -(-int(run.traffic["prompt"]["median"]) // chunk) + \
        int(run.traffic["output"]["median"])
    current = [submit(rng.integers(0, vocab, chunk, dtype=np.int32),
                      2 + round(c * stagger / clients), False)
               for c in range(clients)]
    ramp = list(current)
    with run.spans("ramp"):
        while srv.waves < stagger or not all(r.done for r in ramp):
            srv.step()
            for c in range(clients):
                if current[c].done:
                    current[c] = next_from_pool()
    steps = []                  # (t0, t1, process CPU seconds) per step
    pauses = GcPauses()
    t_end = run.open_window() + run.seconds
    while True:
        now = time.perf_counter()
        run.poll(now)
        if now >= t_end:
            break
        t0, cpu = time.perf_counter(), time.process_time()
        with run.spans("wave"):
            srv.step()
        steps.append((t0, time.perf_counter(), time.process_time() - cpu))
        for c in range(clients):
            if current[c].done:
                current[c] = next_from_pool()
    t_close = run.close_window()
    pauses.stop()
    report_steps(steps, run.facts["t_open"], pauses)
    with run.spans("drain"):
        while time.perf_counter() - t_close < DRAIN_S and any(
                r is not None for r in srv.active) and sum(
                len(r.out) for r in pool_reqs
                if r.status == "ok" and r.t_done >= run.facts["t_open"]
        ) < CHECK_TOKENS:
            srv.step()
    return reqs, pool_reqs


class GcPauses:
    """Seconds the interpreter's garbage collector runs while active."""

    def __init__(self):
        self.seconds, self.longest, self._t = 0.0, 0.0, None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            dt = time.perf_counter() - self._t
            self.seconds += dt
            self.longest = max(self.longest, dt)

    def stop(self):
        gc.callbacks.remove(self._on)


def report_steps(steps: list, t_open: float, pauses: GcPauses) -> None:
    """On standard error: the window's longest steps and the longest time
    between two steps, each with when it began and the process CPU time
    of the step, and the garbage collector's pauses, so that a stall shows
    where it sat."""
    if not steps:
        return
    longest = sorted(steps, key=lambda s: s[0] - s[1])[:3]
    between = max(((b[0] - a[1], a[1]) for a, b in zip(steps, steps[1:])),
                  default=(0.0, t_open))
    print("window steps: " + ", ".join(
        f"{1e3 * (t1 - t0):.1f} ms at +{t0 - t_open:.2f} s "
        f"(cpu {1e3 * c:.1f} ms)" for t0, t1, c in longest)
        + f"; longest between steps {1e3 * between[0]:.1f} ms at "
        f"+{between[1] - t_open:.2f} s; gc {1e3 * pauses.seconds:.1f} ms "
        f"(longest {1e3 * pauses.longest:.1f} ms)", file=sys.stderr)


def sample(run, finished: list) -> list:
    """The finished request with most served tokens, then others in an
    order drawn from the seed, up to ``SAMPLE_TOKENS`` served tokens."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: -len(r.out))
    first, rest = order[0], order[1:]
    rest = [rest[i] for i in run.rng(5).permutation(len(rest))]
    out, n = [first], len(first.out)
    for r in rest:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        out.append(r)
        n += len(r.out)
    return out


def check_batch(reqs: list):
    """Right-padded sequences ``prompt + out[:-1]``, with the served token
    due at each position and where one is due; rows past the sample are
    padding and ask for nothing."""
    lens = [len(r.prompt) + len(r.out) - 1 for r in reqs]
    ref = _ref()
    t = ref.pad_to(max(lens))
    rows = max(SAMPLE_MAX, len(reqs))
    tokens = np.zeros((rows, t), np.int32)
    tok = np.zeros((rows, t), np.int32)
    ask = np.zeros((rows, t), bool)
    for i, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        tokens[i, :len(seq)] = seq
        p = len(r.prompt) - 1
        tok[i, p:p + len(r.out)] = r.out
        ask[i, p:p + len(r.out)] = True
    return tokens, tok, ask


def run(run) -> dict:
    import jax
    from repro.core.executor import clear_executor_cache
    from repro.core.pipeline import clear_compile_cache
    from repro.models import LM
    from repro.runtime.server import DecodeServer
    cfg = run.config["model"]
    serving = run.config["serving"]
    mcfg = model_config(cfg)
    lm = LM(mcfg)
    # the server first, the weights after: DecodeServer stacks its KV
    # cache eagerly, layer by layer, and beside 5.3 GB of weights that
    # needs more than the chip's 16 GB (PERF.md, section 7)
    srv = DecodeServer(lm, None, batch_slots=serving["batch_slots"],
                       max_len=serving["max_len"],
                       prefill_chunk=serving["prefill_chunk"])
    srv.params = params = _ref().init_params(cfg, run.key(),
                                             mcfg.padded_vocab)
    log = WaveLog(srv)
    pool = lm_requests(run.traffic, cfg["vocab_size"], run.seed)
    reqs, pool_reqs = serve(run, srv, pool)
    peak = memory_peak(run.devices)
    f = run.facts
    lo, hi = f["t_open"], f["t_close"]
    # every request that was in the system during the window
    live = [r for r in reqs if r.t_submit < hi and
            (r.t_done is None or r.t_done >= lo)]
    failed = sum(1 for r in live if r.done and r.status != "ok")
    finished = [r for r in pool_reqs if r.status == "ok" and
                r.t_done >= lo]
    picked = sample(run, finished)
    wave_log = log.waves
    log.srv = log.inner = None
    del srv, params, lm, log
    clear_executor_cache()
    clear_compile_cache()
    jax.clear_caches()
    gc.collect()

    stamps = np.array([t for r in reqs for t in r.token_times])
    f["token_stamps"] = stamps[(stamps >= lo) & (stamps <= hi)]
    f["itl_s"] = np.array([b - a for r in reqs
                           for a, b in zip(r.token_times, r.token_times[1:])
                           if a >= lo and b <= hi])
    # a wait that spans the profiler's stop in a traced run is left out
    pa, pb = f.get("paused", (hi, hi))
    f["ttft_s"] = np.array([r.t_first - r.t_submit for r in reqs
                            if r.t_first is not None and lo <= r.t_first
                            <= hi and not r.t_submit < pb < r.t_first])
    f["wave_s"] = run.spans.durations("wave", lo, hi)
    waves = [w for w in wave_log if lo <= w[0] <= hi]
    f["flops"] = sum(counts.dense_lm_flops(cfg, n, ctx)
                     for _, n, ctx in waves)
    f["peaks"] = peaks(run.devices[0].device_kind)

    mean = widest = float("inf")
    ctrl = None
    if picked:
        tokens, tok, ask = check_batch(picked)
        ref = _ref()
        gaps = ref.token_gaps(cfg, run.key(), tokens, tok, ask)[ask]
        mean, widest = float(gaps.mean()), float(gaps.max())
        if run.control:
            # the control: the forward over fp8 weights puts its own token
            # first at each position; read that token's gap
            low = ref.argmax_tokens(cfg, run.key(), tokens,
                                    quant="float8_e4m3fn")
            ctrl = ref.token_gaps(cfg, run.key(), tokens, low, ask)[ask]
    served = sum(len(r.out) for r in picked)
    in_window = [r for r in finished if r.t_done <= hi]
    print(f"window: {len(in_window)} requests finished, {len(live)} live, "
          f"{len(f['itl_s'])} gaps, {len(f['token_stamps'])} tokens")
    print(f"compared {served} served tokens of {len(picked)} requests "
          f"({len(finished)} finished); widest gap {widest!r}")
    out = {"attempted": len(live), "failed": failed,
           "checks": [Check("token_gap_mean", mean, TOKEN_GAP_LIMIT)],
           "memory_peak_bytes": peak}
    if run.control:
        out["control_checks"] = [
            Check("token_gap_mean", float(ctrl.mean()), TOKEN_GAP_LIMIT)]
        out["readings"] = {"token_gap": widest,
                           "control.token_gap": float(ctrl.max()),
                           "served_tokens": served}
    return out
