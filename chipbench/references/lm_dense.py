"""Plain reference of a dense decoder LM with a tied output head, and the
benchmark's weights for it.

The configuration (``model`` in ``chipbench/configs/<config>.json``) is the
architecture as the program serves it: pre-norm blocks of RMSNorm,
multi-head attention with rotary embedding on the first ``rotary_pct`` of
each head's dimensions (pairs ``(2i, 2i+1)`` rotate together), a SiLU-gated
MLP, a final RMSNorm and logits against the embedding table.

Weights: :func:`init_params` makes all of them in one jitted call from a
key, in the type they are served in, laid out as the program takes them
(``embed``, ``final_norm``, and ``scan`` = one block whose leaves are
stacked over the layers).  Leaf ``j`` of layer ``l`` is drawn from
``fold_in(fold_in(fold_in(key, 2), l), j)``, so the reference makes one
layer again alone.

Reference: :func:`token_gaps` runs the whole sequences through the layers
one at a time in float32 at the highest matmul precision, and returns, at
each position asked for, how far the given token's logit lies below the
best logit.  :func:`argmax_tokens` with ``quant="float8_e4m3fn"`` is the
control: the same forward over weights rounded to fp8 (per-tensor absmax
scale), one precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import numpy as np

EMBED_STD = 0.02
NORM_STD = 0.1
# leaves of one layer, in key order
LEAVES = ("norm1", "wq", "wk", "wv", "wo", "norm2", "wi_gate", "wi_up",
          "wo_mlp")


def _dims(cfg: dict):
    d, h, hkv = cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, h, hkv, hd, cfg["d_ff"]


def _shapes(cfg: dict) -> dict:
    d, h, hkv, hd, ff = _dims(cfg)
    return {"norm1": (d,), "wq": (d, h * hd), "wk": (d, hkv * hd),
            "wv": (d, hkv * hd), "wo": (h * hd, d), "norm2": (d,),
            "wi_gate": (d, ff), "wi_up": (d, ff), "wo_mlp": (ff, d)}


def _leaf(key, name: str, shape: tuple, dtype):
    import jax
    import jax.numpy as jnp
    x = jax.random.normal(key, shape, jnp.float32)
    if name.startswith("norm"):
        x = 1.0 + NORM_STD * x
    else:
        x = x * shape[0] ** -0.5
    return x.astype(dtype)


def _layer(cfg_items: tuple, key, layer, dtype):
    import jax
    cfg = dict(cfg_items)
    kl = jax.random.fold_in(jax.random.fold_in(key, 2), layer)
    shapes = _shapes(cfg)
    return {n: _leaf(jax.random.fold_in(kl, j), n, shapes[n], dtype)
            for j, n in enumerate(LEAVES)}


def _program_layout(layer: dict) -> dict:
    return {"norm1": layer["norm1"],
            "attn": {k: layer[k] for k in ("wq", "wk", "wv", "wo")},
            "norm2": layer["norm2"],
            "mlp": {"wi_gate": layer["wi_gate"], "wi_up": layer["wi_up"],
                    "wo": layer["wo_mlp"]}}


def _embed(key, rows: int, d: int, dtype):
    import jax
    import jax.numpy as jnp
    return (jax.random.normal(jax.random.fold_in(key, 0), (rows, d),
                              jnp.float32) * EMBED_STD).astype(dtype)


def _final_norm(key, d: int, dtype):
    import jax
    return _leaf(jax.random.fold_in(key, 1), "norm", (d,), dtype)


def init_params(cfg: dict, key, embed_rows: int) -> dict:
    """Every weight in one jitted call, in the program's layout.  The
    embedding's ``embed_rows`` rows are the vocabulary's, then zero rows
    of padding that no token addresses."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(cfg["dtype"])
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if not isinstance(v, list)))

    @jax.jit
    def make(key):
        layers = jax.vmap(lambda l: _layer(items, key, l, dtype))(
            jnp.arange(cfg["num_layers"]))
        emb = _embed(key, cfg["vocab_size"], cfg["d_model"], dtype)
        pad = jnp.zeros((embed_rows - cfg["vocab_size"], cfg["d_model"]),
                        dtype)
        return {"embed": jnp.concatenate([emb, pad]),
                "final_norm": _final_norm(key, cfg["d_model"], dtype),
                "scan": (_program_layout(layers),), "rest": ()}
    return make(key)


@functools.lru_cache(maxsize=None)
def _layer_fn(items: tuple, quant):
    import jax
    import jax.numpy as jnp
    cfg = dict(items)
    dtype = jnp.dtype(cfg["dtype"])

    @jax.jit
    def f(key, layer):
        p = _layer(items, key, layer, dtype)
        return {k: _dequant(v, quant) for k, v in p.items()}
    return f


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


def _rms(x, g, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, cfg):
    """x (S, T, H, hd); rotate pairs (2i, 2i+1) of the first rotary dims."""
    import jax.numpy as jnp
    hd = x.shape[-1]
    rot = int(hd * cfg["rotary_pct"]) // 2 * 2
    inv = 1.0 / (cfg["rope_theta"] **
                 (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos[:, None].astype(jnp.float32) * inv        # (T, rot/2)
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    out = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1)
    out = out.reshape(x.shape[:-1] + (rot,))
    return jnp.concatenate([out, x[..., rot:]], -1)


@functools.lru_cache(maxsize=None)
def _block_fn(items: tuple):
    import jax
    import jax.numpy as jnp
    cfg = dict(items)
    d, h, hkv, hd, _ = _dims(cfg)
    eps = cfg["norm_eps"]

    @jax.jit
    def f(x, p):
        with jax.default_matmul_precision("highest"):
            s_, t_ = x.shape[:2]
            pos = jnp.arange(t_)
            hn = _rms(x, p["norm1"], eps)
            q = _rope((hn @ p["wq"]).reshape(s_, t_, h, hd), pos, cfg)
            k = _rope((hn @ p["wk"]).reshape(s_, t_, hkv, hd), pos, cfg)
            v = (hn @ p["wv"]).reshape(s_, t_, hkv, hd)
            k = jnp.repeat(k, h // hkv, axis=2)
            v = jnp.repeat(v, h // hkv, axis=2)
            sc = jnp.einsum("sqhd,skhd->shqk", q, k) * hd ** -0.5
            causal = pos[:, None] >= pos[None, :]
            sc = jnp.where(causal, sc, -jnp.inf)
            o = jnp.einsum("shqk,skhd->sqhd", jax.nn.softmax(sc, -1), v)
            x = x + o.reshape(s_, t_, h * hd) @ p["wo"]
            hn = _rms(x, p["norm2"], eps)
            mlp = jax.nn.silu(hn @ p["wi_gate"]) * (hn @ p["wi_up"])
            return x + mlp @ p["wo_mlp"]
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
        argmax token at every position."""
        with jax.default_matmul_precision("highest"):
            emb = _dequant(_embed(key, vocab, d, dtype), quant)
            g = _final_norm(key, d, dtype).astype(jnp.float32)
            logits = _rms(x, g, eps) @ emb.T
            best = logits.max(-1)
            at = jnp.take_along_axis(logits, tok[..., None], -1)[..., 0]
            return jnp.where(ask, best - at, 0.0), logits.argmax(-1)
    return f


def _items(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if not isinstance(v, list)))


def _run(cfg: dict, key, tokens: np.ndarray, tok: np.ndarray,
         ask: np.ndarray, quant):
    import jax
    import jax.numpy as jnp
    items = _items(cfg)
    dtype = jnp.dtype(cfg["dtype"])
    rows = jnp.asarray(tokens)
    emb = _embed(key, cfg["vocab_size"], cfg["d_model"], dtype)
    x = _dequant(emb, quant)[rows]
    del emb
    layer = _layer_fn(items, quant)
    block = _block_fn(items)
    for l in range(cfg["num_layers"]):
        x = block(x, layer(key, l))
    gap, top = _head_fn(items, quant)(key, x, jnp.asarray(tok),
                                      jnp.asarray(ask))
    return np.asarray(gap), np.asarray(top)


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
