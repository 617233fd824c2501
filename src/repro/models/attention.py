"""Attention layers: blockwise (memory-O(S·chunk)) GQA with full/sliding
window, decode-with-cache, and DeepSeek MLA.

Blockwise attention is the jnp fallback of the Pallas flash kernel
(`repro.kernels.flash_attention`) — the dry-run and CPU tests lower this
path; on a TPU runtime the kernel is selected instead.  The online-softmax
scan over KV chunks keeps live memory at O(S·chunk) per head, which is what
makes the 32k-prefill and 500k shapes compile inside HBM.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from .common import (ModelConfig, apply_rope, dense_init, init_rms,
                     rms_norm, rope_freqs, yarn_mscale, yarn_rope)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Blockwise multi-query/grouped attention (training & prefill)
# ---------------------------------------------------------------------------

def dense_attention(q, k, v, *, causal=True, window=None, scale=None):
    """Plain O(S²)-memory attention. COST-MODE / small-shape path: flop-
    identical to the blockwise path but scan-free, so XLA cost analysis
    counts every block (scan bodies are counted once, see roofline docs)."""
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * (scale or d ** -0.5)
    qp = jnp.arange(sq)[:, None]
    kp = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= qp >= kp
    if window is not None:
        mask &= qp - kp < window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p_ = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p_.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, sq, h, dv).astype(q.dtype)


def blockwise_attention(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None, chunk: int = 512,
                        banded: bool = True, dense: bool = False,
                        scale: Optional[float] = None):
    """q (B,Sq,H,D); k,v (B,Sk,Hkv,D); GQA via head grouping. -> (B,Sq,H,D)

    ``banded=True`` with a window slides a static band of KV chunks along
    the diagonal (computes only ceil(window/chunk)+1 chunks per q chunk)
    instead of masking the full row — the O(S·w) sliding-window path.
    ``dense=True`` switches to the scan-free cost-mode path.  ``scale``
    defaults to ``D^-0.5``.
    """
    if dense:
        return dense_attention(q, k, v, causal=causal, window=window,
                               scale=scale)
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]                        # MLA: value dim ≠ qk dim
    g = h // hkv
    assert sq % chunk == 0 and sk % chunk == 0, (sq, sk, chunk)
    nq, nk = sq // chunk, sk // chunk
    scale = scale or d ** -0.5

    qc = q.reshape(b, nq, chunk, hkv, g, d)
    kc = k.reshape(b, nk, chunk, hkv, d)
    vc = v.reshape(b, nk, chunk, hkv, dv)

    use_band = banded and window is not None and window < sk
    if use_band:
        band = -(-window // chunk) + 1          # kv chunks per q chunk
        band = min(band, nk)

    def q_step(_, qi):
        qblk = qc[:, qi]                        # (b, C, hkv, g, d)
        q_pos = qi * chunk + jnp.arange(chunk)

        def kv_step(carry, kj):
            m, l, acc = carry
            kblk = jax.lax.dynamic_index_in_dim(kc, kj, 1, keepdims=False)
            vblk = jax.lax.dynamic_index_in_dim(vc, kj, 1, keepdims=False)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            k_pos = kj * chunk + jnp.arange(chunk)
            mask = jnp.ones((chunk, chunk), bool)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                mask &= q_pos[:, None] - k_pos[None, :] < window
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, chunk), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, chunk, dv), jnp.float32)
        if use_band:
            start = jnp.maximum(qi - (band - 1), 0)
            kjs = start + jnp.arange(band)
        elif causal:
            # static full scan; masked chunks above the diagonal contribute
            # nothing (hillclimb note: ~2× FLOP waste vs triangular skip)
            kjs = jnp.arange(nk)
        else:
            kjs = jnp.arange(nk)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), kjs)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)        # (b, hkv, g, C, d)

    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq))
    # outs: (nq, b, hkv, g, C, dv) -> (b, S, h, dv)
    out = jnp.moveaxis(outs, 0, 1).transpose(0, 1, 4, 2, 3, 5)
    return out.reshape(b, sq, h, dv)


def decode_attention(q, k_cache, v_cache, cache_len, *,
                     window: Optional[int] = None):
    """q (B,C,H,D); caches (B,Smax,Hkv,D); cache_len (B,C) the valid length
    each query sees, incl. its own token: query t of slot b attends over
    positions ``< cache_len[b, t]`` (and the last ``window`` of them).

    Each query's scores, softmax and weighted sum run over the whole cache,
    and a query's result does not depend on C: the G·C queries of a kv
    head are the rows of one matmul against its cache, and a lone row is
    padded to two, since a one-row matmul may lower as a matrix-vector
    product that sums in another order (on the CPU and on a TPU alike).
    So C tokens in one pass give the bits of C single-token steps."""
    b, c, h, d = q.shape
    smax, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    m = g * c
    rows = q.reshape(b, c, hkv, g, d).transpose(0, 2, 3, 1, 4) \
        .reshape(b, hkv, m, d)
    if m == 1:
        rows = jnp.concatenate([rows, rows], axis=2)
    s = jnp.einsum("bhmd,bkhd->bhmk", rows, k_cache,
                   preferred_element_type=jnp.float32) * d ** -0.5
    pos = jnp.arange(smax)
    mask = pos[None, None, :] < cache_len[:, :, None]
    if window is not None:
        mask &= pos[None, None, :] >= cache_len[:, :, None] - window
    # row g·C + t of a kv head is query t of head group g
    mask = jnp.broadcast_to(mask[:, None, None], (b, 1, g, c, smax)) \
        .reshape(b, 1, m, smax)
    s = jnp.where(mask, s[:, :, :m], NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    if m == 1:
        p = jnp.concatenate([p, p], axis=2)
    out = jnp.einsum("bhmk,bkhd->bhmd", p, v_cache,
                     preferred_element_type=jnp.float32)[:, :, :m]
    return out.reshape(b, hkv, g, c, d).transpose(0, 3, 1, 2, 4) \
        .reshape(b, c, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer (params, fwd, decode)
# ---------------------------------------------------------------------------

def init_attn(key, cfg: ModelConfig, dtype):
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, h * hd), dtype),
        "wk": dense_init(ks[1], (d, hkv * hd), dtype),
        "wv": dense_init(ks[2], (d, hkv * hd), dtype),
        "wo": dense_init(ks[3], (h * hd, d), dtype),
    }


def attn_forward(p, x, cfg: ModelConfig, *, positions, causal=True,
                 window=None, kv=None, dense=False):
    """x (B,S,D). ``kv`` overrides K/V source (cross-attention)."""
    b, s, _ = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    src = kv if kv is not None else x
    sk = src.shape[1]
    q = (x @ p["wq"]).reshape(b, s, h, hd)
    k = (src @ p["wk"]).reshape(b, sk, hkv, hd)
    v = (src @ p["wv"]).reshape(b, sk, hkv, hd)
    if kv is None:  # self-attention: rotary
        cos, sin = rope_freqs(positions, hd, cfg.rope_theta, cfg.rotary_pct)
        q = apply_rope(q, cos, sin, cfg.rotary_pct)
        k = apply_rope(k, cos, sin, cfg.rotary_pct)
    import math
    from .common import pick_chunk
    chunk = pick_chunk(math.gcd(s, sk), min(cfg.attn_chunk, s))
    o = blockwise_attention(q, k, v, causal=causal and kv is None,
                            window=window, chunk=chunk, dense=dense)
    return o.reshape(b, s, h * hd) @ p["wo"]


def _default_device():
    return jax.devices()[0]


def _keep_layout(leaf):
    """``leaf``, held in the layout the default device gives its shape.

    Inside the serving wave's nested scans a row written at a dynamic
    position would lead the compiler to lay the whole carried cache out for
    the row and to relayout it at the wave's entry and exit: a TPU keeps a
    stablelm-3b K/V leaf, head dim 80, with its sequence axis minor."""
    device = _default_device()
    try:
        layout = device.client.get_default_layout(
            jnp.dtype(leaf.dtype), tuple(leaf.shape), device)
    except jax.errors.JaxRuntimeError:
        return leaf
    return with_layout_constraint(leaf, Layout.from_pjrt_layout(layout))


@dataclasses.dataclass(frozen=True)
class LayerCache:
    """One layer's decode cache, as a view into the cache tree it lives in.

    With ``layer`` set the leaves of ``tree`` are layer-stacked (leading
    axis = layer, as a scanned stack carries them) and this view is row
    ``layer`` of each; with ``layer=None`` they are the layer's own.
    ``active`` (B,) bool, or None for every slot, marks the slots this
    micro-step feeds: an inactive slot's cache stays bit-identical.  A
    (B,C) ``active`` marks a *block* of C tokens per slot instead, slot b's
    valid tokens being a prefix of its row.

    :meth:`write` picks how to write by the cache's structure: a positional
    cache (one with a per-slot ``len`` counter over a sequence axis — K/V,
    int8 K/V with scales, the MLA latent) takes each slot's rows at its own
    position by one ``dynamic_update_slice`` per slot (:func:`_write_block`),
    in place, and keeps its device's layout (:func:`_keep_layout`); a
    fixed-size recurrent state is written whole."""
    tree: dict
    layer: Optional[jax.Array] = None
    active: Optional[jax.Array] = None

    def __getitem__(self, name):
        leaf = self.tree[name]
        if self.layer is None:
            return leaf
        return jax.lax.dynamic_index_in_dim(leaf, self.layer, 0,
                                            keepdims=False)

    def __contains__(self, name):
        return name in self.tree

    def read(self) -> dict:
        return {name: self[name] for name in self.tree}

    def child(self, name) -> "LayerCache":
        return dataclasses.replace(self, tree=self.tree[name])

    def with_child(self, name, child: "LayerCache") -> "LayerCache":
        return dataclasses.replace(self, tree={**self.tree,
                                               name: child.tree})

    def write(self, values: dict) -> "LayerCache":
        """Positional cache: ``values[name]`` (B,C,...) holds C rows per
        slot, slot b's valid rows landing at ``len[b] + t``
        (:func:`_write_block`), and ``len`` advances by their count; a
        (B,) or None ``active`` feeds one row per slot (C=1).
        Recurrent state: ``values`` is the whole new state."""
        tree = dict(self.tree)
        lead = () if self.layer is None else (self.layer,)
        active = self.active
        if "len" in self.tree:
            length = self["len"]
            if active is None:
                active = jnp.ones(next(iter(values.values())).shape[:2], bool)
            elif active.ndim == 1:
                active = active[:, None]
            for name, rows in values.items():
                tree[name] = _keep_layout(_write_block(tree[name], rows,
                                                       length, active, lead))
            values = {"len": length + active.sum(-1, dtype=length.dtype)}
            active = None
        for name, new in values.items():
            if active is not None:
                new = jnp.where(active.reshape(
                    active.shape + (1,) * (new.ndim - 1)), new, self[name])
            tree[name] = new if self.layer is None else \
                jax.lax.dynamic_update_index_in_dim(tree[name], new,
                                                    self.layer, 0)
        return dataclasses.replace(self, tree=tree)


def _write_block(leaf, rows, length, active, lead):
    """``rows`` (B,C,...) into ``leaf`` by one C-row ``dynamic_update_slice``
    per slot; ``active`` (B,C) marks each slot's valid rows, a prefix.  Slot
    b's block starts at ``min(len[b], Smax - C)``: a block that would run
    past the cache's end starts early, so the compiler never shifts it onto
    valid rows, and its valid rows still land at ``len[b] + t``.  A block
    row that takes no valid token writes back the row it holds."""
    b, c = rows.shape[:2]
    smax = leaf.shape[len(lead) + 1]
    w = min(c, smax)
    for slot in range(b):
        first = jnp.minimum(length[slot], smax - w)
        start = lead + (slot, first) + (0,) * (rows.ndim - 2)
        shape = (1,) * len(lead) + (1, w) + rows.shape[2:]
        held = jax.lax.dynamic_slice(leaf, start, shape)
        # block row j holds position first + j, which is token t
        t = jnp.arange(w) + first - length[slot]
        tc = jnp.maximum(t, 0)
        take = (t >= 0) & active[slot][tc]
        new = rows[slot][tc].reshape(shape)
        take = take.reshape((1,) * (len(lead) + 1) + (w,) +
                            (1,) * (rows.ndim - 2))
        leaf = jax.lax.dynamic_update_slice(
            leaf, jnp.where(take, new, held), start)
    return leaf


def attn_decode(p, x, cfg: ModelConfig, cache: LayerCache, *, window=None):
    """x (B,C,D), C tokens per slot at positions ``len + t``; ``cache`` a
    :class:`LayerCache` over {k,v:(B,Smax,Hkv,hd), len:(B,) per-slot
    position counters} (self-attn).  The new K/V rows are written first,
    then each token attends over the layer's cache up to its own position:
    C=1 is a decode micro-step, C>1 a block of a prompt in one pass."""
    b, c = x.shape[:2]
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    pos = cache["len"][:, None] + jnp.arange(c)
    q = (x @ p["wq"]).reshape(b, c, h, hd)
    k = (x @ p["wk"]).reshape(b, c, hkv, hd)
    v = (x @ p["wv"]).reshape(b, c, hkv, hd)
    cos, sin = rope_freqs(pos.astype(jnp.float32), hd,
                          cfg.rope_theta, cfg.rotary_pct)
    q = apply_rope(q, cos, sin, cfg.rotary_pct)
    k = apply_rope(k, cos, sin, cfg.rotary_pct)
    if "k_scale" in cache:   # int8 quantized cache
        kq, ks = _quant_kv(k)
        vq, vs = _quant_kv(v)
        cache = cache.write({"k": kq, "v": vq, "k_scale": ks,
                             "v_scale": vs})
        kd = _dequant_kv(cache["k"], cache["k_scale"], x.dtype)
        vd = _dequant_kv(cache["v"], cache["v_scale"], x.dtype)
    else:
        cache = cache.write({"k": k, "v": v})
        kd, vd = cache["k"], cache["v"]
    o = decode_attention(q, kd, vd, pos + 1, window=window)
    return o.reshape(b, c, h * hd) @ p["wo"], cache


def init_kv_cache(cfg: ModelConfig, batch, max_len, dtype):
    hkv, hd = cfg.num_kv_heads, cfg.hd
    # ``len`` is per-slot: each batch slot carries its own position counter
    # so the serving loop can admit/retire requests slot-by-slot (true
    # continuous batching) instead of draining whole waves
    if cfg.kv_cache_dtype == "int8":
        # beyond-paper serving optimization: per-(token, head) block-scaled
        # int8 KV — halves-to-quarters the decode memory term (§Perf)
        return {"k": jnp.zeros((batch, max_len, hkv, hd), jnp.int8),
                "v": jnp.zeros((batch, max_len, hkv, hd), jnp.int8),
                "k_scale": jnp.zeros((batch, max_len, hkv), jnp.float32),
                "v_scale": jnp.zeros((batch, max_len, hkv), jnp.float32),
                "len": jnp.zeros((batch,), jnp.int32)}
    return {"k": jnp.zeros((batch, max_len, hkv, hd), dtype),
            "v": jnp.zeros((batch, max_len, hkv, hd), dtype),
            "len": jnp.zeros((batch,), jnp.int32)}


def _quant_kv(x):
    """x (b,c,h,d) -> int8 values + per-(token,head) scale."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _dequant_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# ---------------------------------------------------------------------------
# DeepSeek MLA (multi-head latent attention)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, dtype):
    d, h, hd, r = cfg.d_model, cfg.num_heads, cfg.hd, cfg.kv_lora_rank
    rd = cfg.rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq": dense_init(ks[0], (d, h * (hd + rd)), dtype),
        "w_dkv": dense_init(ks[1], (d, r), dtype),
        "kv_norm": init_rms(None, r, dtype),
        "w_uk": dense_init(ks[2], (r, h * hd), dtype),
        "w_uv": dense_init(ks[3], (r, h * hd), dtype),
        "w_kr": dense_init(ks[4], (d, rd), dtype),
        "wo": dense_init(ks[5], (h * hd, d), dtype),
    }


def mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale: ``(hd + rd)^-0.5`` times YaRN's
    ``mscale(factor, mscale_all_dim)²``."""
    return (cfg.hd + cfg.rope_head_dim) ** -0.5 * \
        yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2


def _mla_project(p, x, cfg: ModelConfig, positions):
    """x (B,S,D) -> q_nope (B,S,H,hd), roped q_pe (B,S,H,rd), the normed
    latent c (B,S,r) and the roped shared k_pe (B,S,rd).

    Rope rotates interleaved pairs ``(2i, 2i+1)``; DeepSeek permutes each
    rope head to ``(0, 2, 4, …, 1, 3, 5, …)`` and rotates halves, which is
    the same rotation with the dims permuted alike in q and k, so every
    score ``q_pe · k_pe`` is the same."""
    b, s, _ = x.shape
    h, hd, rd = cfg.num_heads, cfg.hd, cfg.rope_head_dim
    q = (x @ p["wq"]).reshape(b, s, h, hd + rd)
    c = rms_norm(x @ p["w_dkv"], p["kv_norm"], cfg.norm_eps)
    kr = (x @ p["w_kr"]).reshape(b, s, 1, rd)
    cos, sin = yarn_rope(positions, cfg, rd)
    qr = apply_rope(q[..., hd:], cos, sin)
    kr = apply_rope(kr, cos, sin)
    return q[..., :hd], qr, c, kr.reshape(b, s, rd)


def mla_forward(p, x, cfg: ModelConfig, *, positions, dense=False):
    """Prefill and training: the latent is up-projected to per-head K and V
    and attention runs in the heads' own dims."""
    b, s, _ = x.shape
    h, hd, rd = cfg.num_heads, cfg.hd, cfg.rope_head_dim
    qn, qr, c, kr = _mla_project(p, x, cfg, positions)
    kn = (c @ p["w_uk"]).reshape(b, s, h, hd)
    v = (c @ p["w_uv"]).reshape(b, s, h, hd)
    qf = jnp.concatenate([qn, qr], axis=-1)
    kf = jnp.concatenate(
        [kn, jnp.broadcast_to(kr[:, :, None], (b, s, h, rd))], axis=-1)
    from .common import pick_chunk
    chunk = pick_chunk(s, min(cfg.attn_chunk, s))
    o = blockwise_attention(qf, kf, v, causal=True, chunk=chunk, dense=dense,
                            scale=mla_scale(cfg))
    return o.reshape(b, s, h * hd) @ p["wo"]


def mla_decode(p, x, cfg: ModelConfig, cache: LayerCache):
    """One decode micro-step of MLA in latent space.  The cache holds only
    the latent ``c`` (B,Smax,r) and the shared ``k_pe`` (B,Smax,rd), one
    ``r + rd``-wide row per position, written through ``cache``.  W_UK is
    absorbed into the query and W_UV into the output, so attention reads
    each cached row once and never expands it into per-head K or V:

        q_lat = q_nope · W_UKᵀ            (B,H,r)
        score = q_lat · c + q_pe · k_pe
        o     = (softmax(score) · c) · W_UV
    """
    b = x.shape[0]
    h, hd, r = cfg.num_heads, cfg.hd, cfg.kv_lora_rank
    pos = jnp.broadcast_to(cache["len"], (b,))
    qn, qr, c, kr = _mla_project(p, x, cfg, pos[:, None])
    cache = cache.write({"c": c, "kr": kr})
    c_cache, kr_cache = cache["c"], cache["kr"]
    f32 = jnp.float32
    q_lat = jnp.einsum("bhd,rhd->bhr", qn[:, 0],
                       p["w_uk"].reshape(r, h, hd), preferred_element_type=f32)
    sc = jnp.einsum("bhr,bsr->bhs", q_lat.astype(c_cache.dtype), c_cache,
                    preferred_element_type=f32) + \
        jnp.einsum("bhd,bsd->bhs", qr[:, 0], kr_cache,
                   preferred_element_type=f32)
    sc = sc * mla_scale(cfg)
    mask = jnp.arange(c_cache.shape[1])[None, :] <= pos[:, None]
    sc = jnp.where(mask[:, None, :], sc, NEG_INF)
    pr = jax.nn.softmax(sc, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", pr.astype(c_cache.dtype), c_cache,
                       preferred_element_type=f32)
    o = jnp.einsum("bhr,rhd->bhd", o_lat.astype(x.dtype),
                   p["w_uv"].reshape(r, h, hd))
    return o.reshape(b, 1, h * hd) @ p["wo"], cache


def init_mla_cache(cfg: ModelConfig, batch, max_len, dtype):
    return {"c": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "kr": jnp.zeros((batch, max_len, cfg.rope_head_dim), dtype),
            "len": jnp.zeros((batch,), jnp.int32)}
