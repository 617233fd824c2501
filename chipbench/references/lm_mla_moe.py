"""Plain reference of DeepSeek-V2-Lite's one-chip share, and the
benchmark's weights for it.

The configuration (``model`` in ``chipbench/configs/<config>.json``) is the
published architecture (arXiv:2405.04434, the model's ``config.json``):
pre-norm blocks of RMSNorm; multi-head latent attention with no q-LoRA —
``q = x W_q`` split into ``q_nope`` (``head_dim``) and ``q_pe``
(``rope_head_dim``), the latent ``c = RMSNorm(x W_dkv)`` (``kv_lora_rank``
wide), ``k_nope = c W_uk``, ``v = c W_uv`` per head and one shared ``k_pe
= x W_kr``; YaRN rope on ``q_pe`` and ``k_pe`` in DeepSeek's form (each
rope head permuted to its even dims then its odd dims, then rotate-half,
with ``yarn_find_correction_range``'s frequencies); softmax scale
``(head_dim + rope_head_dim)^-0.5 · mscale(factor, mscale_all_dim)²``.
The first ``first_k_dense`` layers have a SiLU-gated MLP of width ``d_ff``;
the others route each token to the top ``experts_per_tok`` of a float32
softmax over all ``num_experts`` (weights renormalised only under
``norm_topk_prob``), and add ``num_shared_experts`` shared experts.  A final
RMSNorm and an untied ``lm_head`` give the logits.

The share: of each MoE layer's experts only ``0 .. experts_held - 1`` live
here; the routed part is what those experts give (a token's pick of an
absent expert adds nothing), and the shared experts are added whole.

Weights: :func:`init_params` makes all of them in one jitted call from a
key, in the type they are served in, laid out as the program takes them
(``embed``, ``lm_head``, ``final_norm``, ``lead`` = the dense layers,
``scan`` = one MoE block whose leaves are stacked over the MoE layers).
Leaf ``j`` of layer ``l`` (``l`` counts from the first layer) is drawn
from ``fold_in(fold_in(fold_in(key, 2), l), j)``, and expert ``i`` of an
expert leaf from ``fold_in`` of that with ``i``, so the reference makes one
layer again alone and an expert's weights do not depend on how many are
held.  The router is float32, as the program holds it.

Reference: :func:`token_gaps` runs whole sequences through the layers one
at a time in float32 at the highest matmul precision, with no cache and no
batching across sequences in attention, and returns, at each position
asked for, how far the given token's logit lies below the best logit.
:func:`argmax_tokens` with ``quant="float8_e4m3fn"`` is the control: the
same forward over weights rounded to fp8 (per-tensor absmax scale), one
precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import numpy as np

EMBED_STD = 0.02
NORM_STD = 0.1
# leaves of one layer, in key order
ATTN = ("norm1", "wq", "w_dkv", "kv_norm", "w_uk", "w_uv", "w_kr", "wo",
        "norm2")
DENSE = ATTN + ("wi_gate", "wi_up", "wo_mlp")
MOE = ATTN + ("router", "e_gate", "e_up", "e_down", "s_gate", "s_up",
              "s_down")


def _items(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if not isinstance(v, list)))


def _shapes(cfg: dict, dense: bool) -> dict:
    d, h, hd = cfg["d_model"], cfg["num_heads"], cfg["head_dim"]
    r, rd = cfg["kv_lora_rank"], cfg["rope_head_dim"]
    out = {"norm1": (d,), "wq": (d, h * (hd + rd)), "w_dkv": (d, r),
           "kv_norm": (r,), "w_uk": (r, h * hd), "w_uv": (r, h * hd),
           "w_kr": (d, rd), "wo": (h * hd, d), "norm2": (d,)}
    if dense:
        ff = cfg["d_ff"]
        out.update(wi_gate=(d, ff), wi_up=(d, ff), wo_mlp=(ff, d))
    else:
        f, held = cfg["moe_d_ff"], cfg["experts_held"]
        fs = f * cfg["num_shared_experts"]
        out.update(router=(d, cfg["num_experts"]), e_gate=(held, d, f),
                   e_up=(held, d, f), e_down=(held, f, d), s_gate=(d, fs),
                   s_up=(d, fs), s_down=(fs, d))
    return out


def _leaf(key, name: str, shape: tuple, dtype):
    import jax
    import jax.numpy as jnp
    if name.startswith("e_"):
        # expert i from its own key; fan-in is the matrix's first dim
        return jax.vmap(lambda i: _leaf(jax.random.fold_in(key, i), "w",
                                        shape[1:], dtype))(
            jnp.arange(shape[0]))
    x = jax.random.normal(key, shape, jnp.float32)
    if name.startswith("norm") or name == "kv_norm":
        return (1.0 + NORM_STD * x).astype(dtype)
    x = x * shape[0] ** -0.5
    return x if name == "router" else x.astype(dtype)


def _layer(cfg_items: tuple, key, layer, dtype, dense: bool):
    import jax
    cfg = dict(cfg_items)
    kl = jax.random.fold_in(jax.random.fold_in(key, 2), layer)
    shapes = _shapes(cfg, dense)
    return {n: _leaf(jax.random.fold_in(kl, j), n, shapes[n], dtype)
            for j, n in enumerate(DENSE if dense else MOE)}


def _program_layout(layer: dict) -> dict:
    out = {"norm1": layer["norm1"],
           "attn": {k: layer[k] for k in ATTN[1:-1]},
           "norm2": layer["norm2"]}
    if "wi_gate" in layer:
        out["mlp"] = {"wi_gate": layer["wi_gate"], "wi_up": layer["wi_up"],
                      "wo": layer["wo_mlp"]}
    else:
        out["moe"] = {"router": layer["router"], "wi_gate": layer["e_gate"],
                      "wi_up": layer["e_up"], "wo": layer["e_down"],
                      "shared": {"wi_gate": layer["s_gate"],
                                 "wi_up": layer["s_up"],
                                 "wo": layer["s_down"]}}
    return out


def _embed(key, rows: int, d: int, dtype):
    import jax
    import jax.numpy as jnp
    return (jax.random.normal(jax.random.fold_in(key, 0), (rows, d),
                              jnp.float32) * EMBED_STD).astype(dtype)


def _final_norm(key, d: int, dtype):
    import jax
    return _leaf(jax.random.fold_in(key, 1), "norm", (d,), dtype)


def _lm_head(key, rows: int, d: int, dtype):
    import jax
    return _leaf(jax.random.fold_in(key, 3), "w", (d, rows), dtype).T


def init_params(cfg: dict, key, embed_rows: int) -> dict:
    """Every weight in one jitted call, in the program's layout.  The
    embedding's and the head's ``embed_rows`` rows are the vocabulary's,
    then zero rows of padding that no token addresses."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(cfg["dtype"])
    items = _items(cfg)
    lead, layers = cfg["first_k_dense"], cfg["num_layers"]
    d, vocab = cfg["d_model"], cfg["vocab_size"]
    pad = jnp.zeros((embed_rows - vocab, d), dtype)

    @jax.jit
    def make(key):
        moe = jax.vmap(lambda l: _layer(items, key, l, dtype, False))(
            jnp.arange(lead, layers))
        return {"embed": jnp.concatenate([_embed(key, vocab, d, dtype),
                                          pad]),
                "lm_head": jnp.concatenate([_lm_head(key, vocab, d, dtype),
                                            pad]),
                "final_norm": _final_norm(key, d, dtype),
                "lead": tuple(_program_layout(
                    _layer(items, key, l, dtype, True))
                    for l in range(lead)),
                "scan": (_program_layout(moe),), "rest": ()}
    return make(key)


def _dequant(w, quant):
    """The served weight in float32, or rounded to ``quant`` first with a
    per-tensor absmax scale (the control)."""
    import jax.numpy as jnp
    w = w.astype(jnp.float32)
    if quant is None or w.ndim == 1:
        return w
    qt = jnp.dtype(quant)
    scale = jnp.max(jnp.abs(w)) / float(jnp.finfo(qt).max)
    return (w / scale).astype(qt).astype(jnp.float32) * scale


@functools.lru_cache(maxsize=None)
def _layer_fn(items: tuple, quant, dense: bool):
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(dict(items)["dtype"])

    @jax.jit
    def f(key, layer):
        p = _layer(items, key, layer, dtype, dense)
        return {k: _dequant(v, quant) for k, v in p.items()}
    return f


def _rms(x, g, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _mscale(scale: float, m: float) -> float:
    return 0.1 * m * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn(cfg: dict):
    """``(inv_freq (rd/2,), rope mscale, softmax scale)`` as DeepSeek's
    ``DeepseekV2YarnRotaryEmbedding`` and ``DeepseekV2Attention`` make
    them, in float64."""
    dim, base = cfg["rope_head_dim"], cfg["rope_theta"]
    factor = cfg["rope_factor"]
    freq = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    scale = (cfg["head_dim"] + dim) ** -0.5
    if factor <= 1:
        return freq, 1.0, scale

    def correction_dim(rotations):
        return dim * math.log(cfg["rope_original_len"] /
                              (rotations * 2 * math.pi)) / \
            (2 * math.log(base))
    low = max(math.floor(correction_dim(cfg["rope_beta_fast"])), 0)
    high = min(math.ceil(correction_dim(cfg["rope_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extra = 1.0 - ramp
    inv_freq = freq / factor * (1.0 - extra) + freq * extra
    all_dim = _mscale(factor, cfg["rope_mscale_all_dim"])
    return (inv_freq, _mscale(factor, cfg["rope_mscale"]) / all_dim,
            scale * all_dim ** 2)


def _rope(x, cos, sin):
    """DeepSeek's rope: x (..., T, H, rd) permuted to its even dims then
    its odd dims, then ``x cos + rotate_half(x) sin``; cos, sin (T, rd)."""
    import jax.numpy as jnp
    rd = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (rd // 2, 2)).swapaxes(-1, -2).reshape(
        x.shape)
    half = jnp.concatenate([-x[..., rd // 2:], x[..., :rd // 2]], -1)
    return x * cos[:, None] + half * sin[:, None]


def _attention(hn, p, cfg: dict):
    """MLA over whole sequences ``hn`` (S, T, d), one sequence at a time."""
    import jax
    import jax.numpy as jnp
    s_, t_ = hn.shape[:2]
    h, hd, rd = cfg["num_heads"], cfg["head_dim"], cfg["rope_head_dim"]
    inv_freq, rope_m, scale = yarn(cfg)
    ang = jnp.arange(t_, dtype=jnp.float32)[:, None] * \
        jnp.asarray(inv_freq, jnp.float32)
    ang = jnp.concatenate([ang, ang], -1)
    cos, sin = jnp.cos(ang) * rope_m, jnp.sin(ang) * rope_m
    q = (hn @ p["wq"]).reshape(s_, t_, h, hd + rd)
    c = _rms(hn @ p["w_dkv"], p["kv_norm"], cfg["norm_eps"])
    k_nope = (c @ p["w_uk"]).reshape(s_, t_, h, hd)
    v = (c @ p["w_uv"]).reshape(s_, t_, h, hd)
    q_pe = _rope(q[..., hd:], cos, sin)
    k_pe = _rope((hn @ p["w_kr"])[:, :, None], cos, sin)[:, :, 0]
    causal = jnp.arange(t_)[:, None] >= jnp.arange(t_)[None, :]

    def one(args):
        qn, qp, kn, kp, vv = args
        sc = (jnp.einsum("qhd,khd->hqk", qn, kn) +
              jnp.einsum("qhd,kd->hqk", qp, kp)) * scale
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", pr, vv)
    o = jax.lax.map(one, (q[..., :hd], q_pe, k_nope, k_pe, v))
    return o.reshape(s_, t_, h * hd) @ p["wo"]


def _experts(x, p, cfg: dict):
    """The held experts' routed part plus the shared experts, and the
    experts each row picks (N, experts_per_tok); x (N, d)."""
    import jax
    import jax.numpy as jnp
    probs = jax.nn.softmax(x @ p["router"], -1)
    topw, tope = jax.lax.top_k(probs, cfg["experts_per_tok"])
    if cfg["norm_topk_prob"]:
        topw = topw / topw.sum(-1, keepdims=True)
    def expert(e, out):
        gate = jnp.where(tope == e, topw, 0.0).sum(-1)
        y = (jax.nn.silu(x @ p["e_gate"][e]) * (x @ p["e_up"][e])) \
            @ p["e_down"][e]
        return out + gate[:, None] * y
    out = (jax.nn.silu(x @ p["s_gate"]) * (x @ p["s_up"])) @ p["s_down"]
    return jax.lax.fori_loop(0, cfg["experts_held"], expert, out), tope


@functools.lru_cache(maxsize=None)
def _block_fn(items: tuple, dense: bool):
    import jax
    cfg = dict(items)
    eps = cfg["norm_eps"]

    @jax.jit
    def f(x, p):
        """The layer's output, and for a MoE layer the experts each
        position picks (S, T, experts_per_tok)."""
        with jax.default_matmul_precision("highest"):
            x = x + _attention(_rms(x, p["norm1"], eps), p, cfg)
            hn = _rms(x, p["norm2"], eps)
            if dense:
                return x + (jax.nn.silu(hn @ p["wi_gate"]) *
                            (hn @ p["wi_up"])) @ p["wo_mlp"], None
            mlp, picks = _experts(hn.reshape(-1, hn.shape[-1]), p, cfg)
            return x + mlp.reshape(hn.shape), \
                picks.reshape(hn.shape[:2] + picks.shape[-1:])
    return f


@functools.lru_cache(maxsize=None)
def _head_fn(items: tuple, quant):
    import jax
    import jax.numpy as jnp
    cfg = dict(items)
    dtype = jnp.dtype(cfg["dtype"])
    d, vocab, eps = cfg["d_model"], cfg["vocab_size"], cfg["norm_eps"]

    @jax.jit
    def f(key, x, tok, ask):
        """Gaps of ``tok`` below the best logit where ``ask``; and the
        argmax token at every position (one sequence's logits at a
        time)."""
        head = _dequant(_lm_head(key, vocab, d, dtype), quant)
        g = _final_norm(key, d, dtype).astype(jnp.float32)

        def one(args):
            x_, tok_, ask_ = args
            with jax.default_matmul_precision("highest"):
                logits = _rms(x_, g, eps) @ head.T
            best = logits.max(-1)
            at = jnp.take_along_axis(logits, tok_[:, None], -1)[:, 0]
            return jnp.where(ask_, best - at, 0.0), logits.argmax(-1)
        return jax.lax.map(one, (x, tok, ask))
    return f


def _forward(cfg: dict, key, tokens: np.ndarray, quant):
    """The last layer's output (S, T, d), and each MoE layer's picks."""
    import jax.numpy as jnp
    items = _items(cfg)
    dtype = jnp.dtype(cfg["dtype"])
    emb = _embed(key, cfg["vocab_size"], cfg["d_model"], dtype)
    x = _dequant(emb, quant)[jnp.asarray(tokens)]
    del emb
    picks = []
    for l in range(cfg["num_layers"]):
        dense = l < cfg["first_k_dense"]
        x, pk = _block_fn(items, dense)(x, _layer_fn(items, quant, dense)(
            key, l))
        if pk is not None:
            picks.append(pk)
    return x, picks


def routing(cfg: dict, key, tokens: np.ndarray) -> np.ndarray:
    """The experts each position of ``tokens`` (S, T) picks in each MoE
    layer: (MoE layers, S, T, experts_per_tok)."""
    return np.stack([np.asarray(p) for p in
                     _forward(cfg, key, tokens, None)[1]])


def _run(cfg: dict, key, tokens: np.ndarray, tok: np.ndarray,
         ask: np.ndarray, quant):
    import jax.numpy as jnp
    x = _forward(cfg, key, tokens, quant)[0]
    gap, top = _head_fn(_items(cfg), quant)(key, x, jnp.asarray(tok),
                                            jnp.asarray(ask))
    return np.asarray(gap), np.asarray(top)


def logits(cfg: dict, key, tokens: np.ndarray) -> np.ndarray:
    """The logits (S, T, vocab_size) at every position of ``tokens``."""
    import jax
    import jax.numpy as jnp
    x = _forward(cfg, key, tokens, None)[0]
    dtype = jnp.dtype(cfg["dtype"])
    d, vocab = cfg["d_model"], cfg["vocab_size"]
    with jax.default_matmul_precision("highest"):
        head = _lm_head(key, vocab, d, dtype).astype(jnp.float32)
        g = _final_norm(key, d, dtype).astype(jnp.float32)
        return np.asarray(_rms(x, g, cfg["norm_eps"]) @ head.T)


def pad_to(n: int, block: int = 512) -> int:
    return -(-n // block) * block


def token_gaps(cfg: dict, key, tokens: np.ndarray, tok: np.ndarray,
               ask: np.ndarray) -> np.ndarray:
    """``tokens`` (S, T) int32, right-padded.  Gap, at each position where
    ``ask``, of the logit of ``tok`` below the best logit, in float32."""
    return _run(cfg, key, tokens, tok, ask, None)[0]


def argmax_tokens(cfg: dict, key, tokens: np.ndarray,
                  quant=None) -> np.ndarray:
    """The token each position puts first, with weights rounded to
    ``quant`` (the control) or as served."""
    zeros = np.zeros(tokens.shape, np.int32)
    return _run(cfg, key, tokens, zeros, zeros.astype(bool), quant)[1]
