"""Roofline term derivation from compiled dry-run artifacts (deliverable g).

    compute term    = HLO_FLOPs   / (chips × peak_FLOP/s)
    memory term     = HLO_bytes   / (chips × HBM_bw)
    collective term = coll_bytes  / (chips × link_bw)

``cost_analysis`` provides FLOPs and bytes; collective bytes are parsed from
the HLO text by summing operand sizes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute.
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks of one TPU generation."""
    peak_flops: float                 # bf16 FLOP/s
    hbm_bw: float                     # HBM B/s
    ici_bw: float                     # chip-to-chip B/s per link
    source: str


#: Peaks keyed by ``jax.Device.device_kind``.  A device that is not listed
#: has no peaks: :func:`peaks` raises rather than guessing.
PEAKS = {
    # 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s ICI over 4 links
    "TPU v5 lite": Peaks(197e12, 819e9, 50e9,
                         "Google Cloud documentation, \"TPU v5e\""),
}

#: The chip the dry-run meshes model (a v5e pod).
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to repro.roofline.analysis.PEAKS with their source") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_from_hlo(hlo_text: str) -> float:
    """Sum of result sizes of every collective op in the (stable)HLO text.

    Works on both pre-SPMD lowered stablehlo (jax lowered.as_text()) and
    post-partitioning HLO (compiled.as_text()).  Counts each op's *result*
    shape — for all-reduce that equals the payload; for all-gather the
    gathered result; a consistent, comparable proxy for link traffic.
    """
    total = 0
    pending = False
    for line in hlo_text.splitlines():
        s = line.strip()
        # HLO form: `%x = bf16[256,1024] all-reduce(...)`
        m = re.match(r"%?[\w.\-]+\s*=\s*(\([^)]*\)|[\w\[\],]+)\s+"
                     r"([\w\-]+)", s)
        if m and any(m.group(2).startswith(c) for c in _COLLECTIVES):
            total += _shape_bytes(m.group(1))
            continue
        # stablehlo form: `stablehlo.all_reduce` — region ops may carry the
        # result type on a later `}) : (...) -> tensor<...>` line
        m2 = re.search(r"stablehlo\.(all_gather|all_reduce|reduce_scatter|"
                       r"all_to_all|collective_permute)", s)
        if m2:
            tm = re.findall(r"->\s*tensor<([^>]+)>", s) or \
                re.findall(r"tensor<([^>]+)>", s)
            if tm:
                total += _tensor_bytes(tm[-1])
            else:
                pending = True
            continue
        if pending and "-> tensor<" in s:
            tm = re.findall(r"->\s*tensor<([^>]+)>", s)
            if tm:
                total += _tensor_bytes(tm[-1])
            pending = False
    return float(total)


def _tensor_bytes(t: str) -> int:
    parts = t.split("x")
    dt = parts[-1].strip()
    bytes_per = {"f32": 4, "bf16": 2, "f16": 2, "i32": 4, "ui32": 4,
                 "i8": 1, "i64": 8, "f64": 8, "i1": 1}.get(dt, 4)
    n = 1
    for p in parts[:-1]:
        try:
            n *= int(p)
        except ValueError:
            return 0
    return n * bytes_per


def roofline_terms(*, flops: float, bytes_accessed: float,
                   collective_bytes: float, n_chips: int,
                   device_kind: str) -> dict:
    hw = peaks(device_kind)
    compute_s = flops / (n_chips * hw.peak_flops)
    memory_s = bytes_accessed / (n_chips * hw.hbm_bw)
    coll_s = collective_bytes / (n_chips * hw.ici_bw)
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_s}
    dom = max(terms, key=terms.get)
    bound = max(compute_s, memory_s, coll_s)
    return {**terms, "bottleneck": dom.replace("_s", ""),
            "step_lower_bound_s": bound,
            "roofline_fraction_compute": compute_s / bound if bound else 0.0}


def analytic_flops(cfg, seq: int, batch: int, kind: str) -> float:
    """MODEL_FLOPS + attention/linear-scan terms — the fallback compute
    estimate for cells whose unrolled cost compile did not finish."""
    base = model_flops(cfg, seq, batch, kind)
    if kind == "decode":
        return base
    mult = 3.0 if kind == "train" else 1.0   # fwd+bwd vs fwd
    b, s = batch, seq
    attn = 0.0
    for k in cfg.lead_pattern + (cfg.block_pattern * cfg.n_super) + \
            cfg.remainder_pattern:
        if k in ("dense", "moe", "mla", "mla_dense", "shared_attn",
                 "enc_dense", "xdec"):
            attn += 4.0 * b * s * s * cfg.num_heads * cfg.hd
            if k == "xdec":
                attn += 4.0 * b * s * s * cfg.num_heads * cfg.hd
        elif k == "dense_local":
            w = min(cfg.sliding_window, s)
            attn += 4.0 * b * s * w * cfg.num_heads * cfg.hd
        elif k in ("mamba", "mlstm"):
            L = cfg.ssm_chunk
            p_h = (2 * cfg.d_model // cfg.num_heads if k == "mamba"
                   else cfg.d_model // cfg.num_heads)
            attn += b * s * cfg.num_heads * (2 * L * p_h +
                                             4 * p_h * cfg.ssm_state)
    if cfg.enc_layers:
        attn += cfg.enc_layers * 4.0 * b * s * s * cfg.num_heads * cfg.hd
    return base + mult * attn


def model_flops(cfg, seq: int, batch: int, kind: str) -> float:
    """MODEL_FLOPS = 6·N_active·D tokens (train) or 2·N_active·D (fwd)."""
    n_active = active_params(cfg)
    tokens = seq * batch if kind != "decode" else batch
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_active * tokens


def active_params(cfg) -> float:
    """Active (per-token) parameter count of a ModelConfig."""
    d, v = cfg.d_model, cfg.vocab_size
    total = v * d  # embedding (tied unembedding counted once for lookups)
    per_layer = {}
    hd = cfg.hd
    for kind in cfg.lead_pattern + (cfg.block_pattern * cfg.n_super) + \
            cfg.remainder_pattern:
        attn = d * cfg.num_heads * hd + 2 * d * cfg.num_kv_heads * hd \
            + cfg.num_heads * hd * d
        mlp = 3 * d * cfg.d_ff
        if kind in ("dense", "dense_local", "enc_dense"):
            n = attn + mlp
        elif kind == "moe":
            n = attn + 3 * d * cfg.moe_d_ff * cfg.experts_per_tok \
                + 3 * d * cfg.moe_d_ff * cfg.num_shared_experts
        elif kind in ("mla", "mla_dense"):
            r, rd = cfg.kv_lora_rank, cfg.rope_head_dim
            n = (d * cfg.num_heads * (hd + rd) + d * r +
                 r * 2 * cfg.num_heads * hd + d * rd +
                 cfg.num_heads * hd * d)
            n += mlp if kind == "mla_dense" else \
                3 * d * cfg.moe_d_ff * (cfg.experts_per_tok +
                                        cfg.num_shared_experts)
        elif kind == "mamba":
            di = 2 * d
            n = d * (2 * di + 2 * cfg.ssm_state + cfg.num_heads) + di * d
        elif kind == "mlstm":
            n = 5 * d * d
        elif kind == "slstm":
            n = 4 * d * d + d * d + cfg.num_heads * (d // cfg.num_heads) ** 2 * 4
        elif kind == "shared_attn":
            n = attn + mlp  # shared weights but active per occurrence
        elif kind == "xdec":
            n = 2 * attn + mlp
        else:
            n = 0
        per_layer[kind] = n
        total += n
    if cfg.enc_layers:
        attn = 4 * d * cfg.num_heads * hd
        total += cfg.enc_layers * (attn + 3 * d * cfg.d_ff)
    return float(total)


def analytic_bytes_per_device(cfg, seq: int, batch: int, kind: str,
                              n_data: int = 16, n_model: int = 16) -> float:
    """Production-path HBM traffic estimate per device per step.

    The cost-mode HLO memory number materializes dense-attention S² logits
    that the production blockwise path keeps on-chip; this analytic estimate
    is the companion column for attention-heavy cells (methodology note in
    EXPERIMENTS.md)."""
    P_loc = active_params(cfg) / n_model
    tok_loc = seq * batch / n_data if kind != "decode" else batch / n_data
    d = cfg.d_model
    if kind == "train":
        param_io = P_loc * 2 * 4            # read fwd+bwd, grad w, update rw
        opt_io = P_loc * 4 * 4              # two fp32 moments, read+write
        act_io = 14 * tok_loc * d * 2 * (cfg.num_layers + cfg.enc_layers)
        return param_io + opt_io + act_io
    if kind == "prefill":
        return P_loc * 2 + 8 * tok_loc * d * 2 * cfg.num_layers
    # decode: params once + KV/state cache traffic
    cache = 0.0
    for k in cfg.lead_pattern + (cfg.block_pattern * cfg.n_super) + \
            cfg.remainder_pattern:
        if k in ("dense", "moe", "shared_attn", "xdec", "enc_dense"):
            cache += 2 * seq * cfg.num_kv_heads * cfg.hd * 2
        elif k == "dense_local":
            cache += 2 * min(seq, cfg.sliding_window) *                 cfg.num_kv_heads * cfg.hd * 2
        elif k in ("mla", "mla_dense"):
            cache += seq * (cfg.kv_lora_rank + cfg.rope_head_dim) * 2
        elif k == "mamba":
            cache += cfg.num_heads * (2 * d // cfg.num_heads) *                 cfg.ssm_state * 2 * 2
        elif k == "mlstm":
            cache += cfg.num_heads * (d // cfg.num_heads) ** 2 * 2 * 2
        elif k == "slstm":
            cache += 4 * d * 4
    cache_loc = cache * batch / max(n_data, 1) / n_model * n_model  # heads/model
    cache_loc = cache * batch / (n_data * n_model)
    return P_loc * 2 + cache_loc
