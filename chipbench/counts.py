"""The least bytes and the operations a step needs, from its shapes alone.

They count what any implementation of the step has to move or compute, so
that a share of the roofline reads the same whatever implements it: an
implementation that reads a repeated row once is not counted as reading
less than the least.
"""
from __future__ import annotations

import numpy as np

INDEX_BYTES = 4     # int32 offsets and indices


def sls_least_bytes(ptrs: np.ndarray, idxs: np.ndarray, row_bytes: int,
                    out_row_bytes: int) -> int:
    """One pooled table of one batch: each distinct row read once, the
    pooled rows written once, the index and offset streams read once."""
    segments = len(ptrs) - 1
    return (len(np.unique(idxs)) * row_bytes + segments * out_row_bytes
            + (len(idxs) + len(ptrs)) * INDEX_BYTES)


def sls_flops(lookups: int, width: int) -> int:
    """One add per element of every row looked up."""
    return lookups * width


def dense_lm_matmul_params(cfg: dict) -> int:
    """Weights each token multiplies by in a dense decoder with a tied
    output head: attention and gated MLP of every layer, and the head."""
    d, h, hkv = cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"]
    hd = cfg.get("head_dim") or d // h
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    mlp = 3 * d * cfg["d_ff"]
    return cfg["num_layers"] * (attn + mlp) + cfg["vocab_size"] * d


def dense_lm_flops(cfg: dict, tokens: int, context_sum: int) -> float:
    """FLOPs of ``tokens`` tokens whose live contexts (each its own
    position included) add up to ``context_sum``: the matmuls, plus scores
    and weighted values over the context in every layer."""
    d, h = cfg["d_model"], cfg["num_heads"]
    hd = cfg.get("head_dim") or d // h
    return 2.0 * dense_lm_matmul_params(cfg) * tokens + \
        4.0 * cfg["num_layers"] * h * hd * context_sum
