"""Model substrate: configuration + shared layer primitives.

Every assigned architecture is an instance of :class:`ModelConfig`: a stack
of *super-blocks* (``block_pattern``) repeated ``num_layers //
len(pattern)`` times via ``jax.lax.scan`` (keeping HLO size O(1) in depth —
required for 94-layer dry-runs and the right structure at cluster scale),
plus an unscanned remainder when the depth is not a multiple of the
pattern.  ``first_k_dense`` leading layers (DeepSeek's
``first_k_dense_replace``) come before the scan, unscanned: MLA attention
with a dense MLP of width ``d_ff``.

Block kinds:

=============  ============================================================
``dense``      GQA attention (+RoPE/partial-RoPE) + gated MLP
``dense_local``same, sliding-window attention
``moe``        GQA attention + mixture-of-experts FFN (EP dispatch)
``mla``        DeepSeek MLA attention (compressed KV) + MoE FFN
``mla_dense``  DeepSeek MLA attention + gated MLP (a leading dense layer)
``mlstm``      xLSTM mLSTM block (matrix memory, chunked linear attention)
``slstm``      xLSTM sLSTM block (scalar memory, recurrent scan)
``mamba``      Mamba2 SSD block (chunked state-space scan)
``shared_attn``Zamba-style global-attention block inserted in an SSM stack
``enc_dense``  bidirectional attention + MLP (whisper encoder)
``xdec``       causal self-attn + cross-attn + MLP (whisper decoder)
=============  ============================================================
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense|moe|ssm|hybrid|vlm|audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    block_pattern: Tuple[str, ...] = ("dense",)
    head_dim: Optional[int] = None
    # attention
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0          # chatglm partial rotary
    sliding_window: int = 4096
    attn_chunk: int = 512            # kv/q chunk for blockwise attention
    # moe
    num_experts: int = 0
    experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25
    norm_topk_prob: bool = True      # renormalise the top-k router weights
    experts_held: int = 0            # experts 0..held-1 live here; 0 = all
    # leading layers with a dense MLP (width d_ff) before the block pattern
    first_k_dense: int = 0
    tie_embeddings: bool = True      # False: a separate lm_head
    # mla
    kv_lora_rank: int = 0
    rope_head_dim: int = 64
    # YaRN rope scaling (DeepSeek-V2 ``rope_scaling``); factor 1 is none
    rope_factor: float = 1.0
    rope_original_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # ssm / xlstm
    ssm_state: int = 64
    ssm_chunk: int = 256
    # encoder-decoder (whisper)
    enc_layers: int = 0
    enc_seq: int = 1500
    # frontends
    modality: str = "text"           # text | audio-stub | vision-stub
    act: str = "silu"                # mlp activation
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # serving
    kv_cache_dtype: str = "model"    # model dtype | "int8" (quantized cache)
    # embedding engine strategy (Ember integration)
    embed_strategy: str = "masked_psum"
    # applicability notes (DESIGN.md §Arch-applicability)
    long_context_ok: bool = False    # sub-quadratic → long_500k runs

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def padded_vocab(self) -> int:
        """Embedding-table rows: vocab padded to a multiple of 256 so the
        vocab dim shards evenly over any mesh model axis ≤256 (standard
        table padding; ids never address the pad rows, decode slices the
        logits back to the logical vocab)."""
        return -(-self.vocab_size // 256) * 256

    @property
    def n_super(self) -> int:
        return (self.num_layers - self.first_k_dense) // \
            len(self.block_pattern)

    @property
    def remainder_pattern(self) -> Tuple[str, ...]:
        r = (self.num_layers - self.first_k_dense) % len(self.block_pattern)
        return self.block_pattern[:r]

    @property
    def lead_pattern(self) -> Tuple[str, ...]:
        """Block kinds of the leading dense layers (DeepSeek's: MLA
        attention and a dense MLP)."""
        return ("mla_dense",) * self.first_k_dense

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.num_experts


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------

def rms_norm(x, gamma, eps=1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) \
        * gamma


def init_rms(key, d, dtype):
    del key
    return jnp.ones((d,), dtype)


def dense_init(key, shape, dtype, scale=None):
    fan_in = shape[0]
    s = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)


def rope_freqs(positions, head_dim, theta, rotary_pct=1.0):
    """positions (..., S) -> (cos, sin) of shape (..., S, rot/2)."""
    rot = int(head_dim * rotary_pct) // 2 * 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def yarn_mscale(scale: float, mscale: float) -> float:
    """DeepSeek's ``yarn_get_mscale``: 0.1·m·ln(s) + 1 (1 for s ≤ 1)."""
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(cfg: ModelConfig, dim: int):
    """Inverse frequencies of ``dim`` rotary dims under YaRN, as DeepSeek's
    ``DeepseekV2YarnRotaryEmbedding`` makes them: the pairs below
    ``yarn_find_correction_range``'s ``low`` keep ``freq``, those above
    ``high`` take ``freq / factor``, with a linear ramp between."""
    freq = 1.0 / (cfg.rope_theta ** (jnp.arange(0, dim, 2,
                                                dtype=jnp.float32) / dim))
    if cfg.rope_factor <= 1:
        return freq

    def corr(rotations):
        return dim * math.log(cfg.rope_original_len /
                              (rotations * 2 * math.pi)) / \
            (2 * math.log(cfg.rope_theta))
    low = max(math.floor(corr(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(corr(cfg.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) /
                    (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return freq / cfg.rope_factor * (1.0 - keep) + freq * keep


def yarn_rope(positions, cfg: ModelConfig, dim: int):
    """positions (..., S) -> (cos, sin) (..., S, dim/2) under YaRN, scaled
    by mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    ang = positions[..., None].astype(jnp.float32) * yarn_inv_freq(cfg, dim)
    m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / \
        yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def apply_rope(x, cos, sin, rotary_pct=1.0):
    """x (..., S, H, D); cos/sin (..., S, rot/2)."""
    d = x.shape[-1]
    rot = int(d * rotary_pct) // 2 * 2
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., ::2], xr[..., 1::2]
    c = cos[..., None, :]
    s = sin[..., None, :]
    o1 = x1 * c - x2 * s
    o2 = x2 * c + x1 * s
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape).astype(x.dtype)
    return jnp.concatenate([out, xp], axis=-1) if rot < d else out


_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


def pick_chunk(s: int, preferred: int) -> int:
    """Largest chunk ≤ preferred that divides s (gcd fallback)."""
    return preferred if s % preferred == 0 else math.gcd(s, preferred)


def gated_mlp(x, p, act="silu"):
    h = _ACTS[act](x @ p["wi_gate"]) * (x @ p["wi_up"])
    return h @ p["wo"]


def init_mlp(key, d_model, d_ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi_gate": dense_init(k1, (d_model, d_ff), dtype),
        "wi_up": dense_init(k2, (d_model, d_ff), dtype),
        "wo": dense_init(k3, (d_ff, d_model), dtype),
    }
