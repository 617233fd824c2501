"""Serving of an MLA + MoE model's one-chip share through the program's
``DecodeServer``.

The timed path, the ramp, the window and the drain are ``lm_serving.py``'s
(:func:`serve`): ``DecodeServer`` built as ``launch/serve.py`` builds it
from the configuration's ``serving`` block, closed-loop clients one per
slot.  What differs is the model: the weights and the reference come from
``references/lm_mla_moe.py`` and the counts from ``moe_counts.py``.  Each
wave's expert counters (``LM.wave_step``'s third value: held-expert
assignments and the (layer, micro-step, held expert) triples that got a
token) stay on the device until the window has closed.

Facts: besides ``lm_serving``'s, ``moe_waves``, one ``(start, assignments,
touched)`` per wave of the window; ``wave_bytes``, one ``(step start, step
end, least bytes)`` per wave of the window, for
``metrics/step_hbm_roofline.moe.py``; ``flops`` for ``step_mfu.lm``.

``correct``: ``token_gap_mean`` as ``lm_serving`` defines it, over the same
sample, against the float32 reference of the share.  With random weights a
token's gap is set by the nearest competing logit, and a routing decision
that rounding flips moves a layer's output, so the limit is the cell's own,
set from the readings in PERF.md.
"""
from __future__ import annotations

import bisect
import gc
from pathlib import Path

import numpy as np

from chipbench import moe_counts
from chipbench.generate import lm_requests
from chipbench.harness import SPAN_PREFIX, Check, load_module, memory_peak
from chipbench.peaks import peaks

HERE = Path(__file__).resolve().parent
_serving = load_module(HERE / "lm_serving.py")
serve, sample, WaveLog = _serving.serve, _serving.sample, _serving.WaveLog
model_config = _serving.model_config

#: mean gap of a served token's logit below the reference's best, in
#: logits; see PERF.md for the readings it was set from
TOKEN_GAP_LIMIT = 0.012


def _ref():
    return load_module(HERE.parent / "references" / "lm_mla_moe.py")


class ExpertWaveLog(WaveLog):
    """:class:`WaveLog`, plus each wave's micro-steps that feed a slot and
    its expert counters (device arrays, fetched after the window)."""

    def __init__(self, srv):
        super().__init__(srv)
        self.steps, self.counts = [], []

    def __call__(self, params, tokens, lens, caches):
        out = super().__call__(params, tokens, lens, caches)
        self.steps.append(int(np.asarray(lens).max()))
        self.counts.append(out[2])
        return out


def check_batch(reqs: list):
    """Right-padded sequences ``prompt + out[:-1]``, with the served token
    due at each position and where one is due.  Rows are the sample's,
    padded to a power of two, so few shapes compile."""
    lens = [len(r.prompt) + len(r.out) - 1 for r in reqs]
    t = _ref().pad_to(max(lens))
    rows = 1 << (len(reqs) - 1).bit_length()
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


def _wave_facts(run, log, counts) -> None:
    """Per wave of the window: its counters, least bytes with the step span
    it ran in, and the window's FLOPs."""
    cfg = run.config["model"]
    f = run.facts
    lo, hi = f["t_open"], f["t_close"]
    spans = sorted((t0, t1) for n, t0, t1 in run.spans.records
                   if n == SPAN_PREFIX + "wave")
    starts = [s[0] for s in spans]
    f["moe_waves"], f["wave_bytes"], f["flops"] = [], [], 0.0
    for (t, n, ctx), steps, (assign, touched) in zip(log.waves, log.steps,
                                                      counts):
        if not lo <= t <= hi:
            continue
        f["moe_waves"].append((t, int(assign), int(touched)))
        f["flops"] += moe_counts.wave_flops(cfg, n, int(assign), ctx)
        k = bisect.bisect_right(starts, t) - 1
        if k >= 0 and spans[k][0] <= t <= spans[k][1]:
            f["wave_bytes"].append((*spans[k], moe_counts.wave_least_bytes(
                cfg, steps, n, int(touched), ctx)))


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
    # the server first, the weights after: DecodeServer stacks its cache
    # eagerly, which briefly holds it twice
    srv = DecodeServer(lm, None, batch_slots=serving["batch_slots"],
                       max_len=serving["max_len"],
                       prefill_chunk=serving["prefill_chunk"])
    srv.params = params = _ref().init_params(cfg, run.key(),
                                             mcfg.padded_vocab)
    log = ExpertWaveLog(srv)
    pool = lm_requests(run.traffic, cfg["vocab_size"], run.seed)
    reqs, pool_reqs = serve(run, srv, pool)
    peak = memory_peak(run.devices)
    f = run.facts
    lo, hi = f["t_open"], f["t_close"]
    live = [r for r in reqs if r.t_submit < hi and
            (r.t_done is None or r.t_done >= lo)]
    failed = sum(1 for r in live if r.done and r.status != "ok")
    finished = [r for r in pool_reqs if r.status == "ok" and
                r.t_done >= lo]
    picked = sample(run, finished)
    counts = jax.device_get(log.counts)
    log.srv = log.inner = None
    del srv, params, lm
    clear_executor_cache()
    clear_compile_cache()
    jax.clear_caches()
    gc.collect()

    stamps = np.array([t for r in reqs for t in r.token_times])
    f["token_stamps"] = stamps[(stamps >= lo) & (stamps <= hi)]
    f["itl_s"] = np.array([b - a for r in reqs
                           for a, b in zip(r.token_times, r.token_times[1:])
                           if a >= lo and b <= hi])
    pa, pb = f.get("paused", (hi, hi))
    f["ttft_s"] = np.array([r.t_first - r.t_submit for r in reqs
                            if r.t_first is not None and lo <= r.t_first
                            <= hi and not r.t_submit < pb < r.t_first])
    f["wave_s"] = run.spans.durations("wave", lo, hi)
    _wave_facts(run, log, counts)
    f["peaks"] = peaks(run.devices[0].device_kind)

    mean = widest = float("inf")
    ctrl = None
    if picked:
        tokens, tok, ask = check_batch(picked)
        ref = _ref()
        gaps = ref.token_gaps(cfg, run.key(), tokens, tok, ask)[ask]
        mean, widest = float(gaps.mean()), float(gaps.max())
        if run.control:
            low = ref.argmax_tokens(cfg, run.key(), tokens,
                                    quant="float8_e4m3fn")
            ctrl = ref.token_gaps(cfg, run.key(), tokens, low, ask)[ask]
    served = sum(len(r.out) for r in picked)
    held = sum(a for _, a, _ in f["moe_waves"])
    touched = sum(t for _, _, t in f["moe_waves"])
    print(f"window: {len(finished)} requests finished, {len(live)} live, "
          f"{len(f['itl_s'])} gaps, {len(f['token_stamps'])} tokens, "
          f"{len(f['moe_waves'])} waves, {held} held-expert assignments, "
          f"{touched} held experts touched")
    print(f"compared {served} served tokens of {len(picked)} requests; "
          f"widest gap {widest!r}")
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
