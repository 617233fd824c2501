"""Mixture-of-Experts layer: the expert share, and expert-parallel (EP)
dispatch.

The router is a softmax over all ``num_experts`` and keeps the top
``experts_per_tok`` (renormalised only under ``norm_topk_prob``).  A device
holds experts ``first .. first + held - 1`` (``ModelConfig.experts_held``,
all by default): :func:`moe_share` computes those experts' part of the
layer for every token, dropless, as a dense pass over the held experts
weighted by each token's gate (0 for an expert it did not pick).  On one
device that share is the layer's routed part; under a mesh whose tokens are
replicated (decode) each rank computes its share and one ``psum`` adds them
(:func:`_replicated_token_ep`).  Shared experts are added once, outside the
share.

MoE dispatch *is* an embedding operation in the paper's taxonomy: tokens are
gathered into per-expert capacity buffers by irregular indices (an SLS-class
scatter/gather, DESIGN.md §4).  :func:`moe_ffn_local` keeps that form for
the one path that splits tokens over the EP axis — training and prefill
under a mesh whose EP axis divides the sequence: local sort-based slotting
(access), all-to-all over the expert/model axis (the queue), expert FFN
(execute), reverse all-to-all and weighted combine.  Its capacity buffers
keep every shape static, and drop the tokens past an expert's capacity;
it is the only path that drops.  The aux load-balance loss keeps the
router from collapsing.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..core.ops import EmbeddingOp
from .common import ModelConfig, dense_init, _ACTS


def dispatch_op(cfg: ModelConfig, tokens: int) -> EmbeddingOp:
    """The EP dispatch as a characterized embedding operation.

    Un-dispatch (``out_buf[slot]`` below) is a plain irregular gather over
    the (E·C, D) capacity buffer — the op the Ember program compiler
    co-schedules with the step's other lookups (paper Table 1 taxonomy).
    """
    e, k = cfg.num_experts, max(cfg.experts_per_tok, 1)
    capacity = int(tokens * k / e * cfg.capacity_factor) + 1
    return EmbeddingOp("gather", num_segments=tokens * k,
                       num_embeddings=e * capacity, emb_len=cfg.d_model)


def init_moe(key, cfg: ModelConfig, dtype):
    """The router over all ``num_experts``, the ``held_experts`` experts
    this device holds, and the shared experts."""
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.held_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, cfg.num_experts), jnp.float32),
        "wi_gate": dense_init(ks[1], (e, d, f), dtype),
        "wi_up": dense_init(ks[2], (e, d, f), dtype),
        "wo": dense_init(ks[3], (e, f, d), dtype),
    }
    if cfg.num_shared_experts:
        fs = f * cfg.num_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wi_gate": dense_init(k1, (d, fs), dtype),
            "wi_up": dense_init(k2, (d, fs), dtype),
            "wo": dense_init(k3, (fs, d), dtype),
        }
    return p


def route(p, x2d, cfg: ModelConfig):
    """Softmax over all ``num_experts`` in float32 (the router's matmul at
    full precision, as the published gate computes it), then the top
    ``experts_per_tok``: ``(probs (T,E), weights (T,k), experts (T,k))``.
    The weights are renormalised to sum to 1 only under
    ``cfg.norm_topk_prob``."""
    logits = jnp.dot(x2d.astype(jnp.float32), p["router"],
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, tope = jax.lax.top_k(probs, cfg.experts_per_tok)
    if cfg.norm_topk_prob:
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    return probs, topw, tope


def _aux_loss(probs, tope, e):
    """Load-balance loss: E · Σ_e frac_e · mean prob_e (≈ 1 when even)."""
    frac = jnp.mean(jax.nn.one_hot(tope, e, dtype=jnp.float32), axis=(0, 1))
    return e * jnp.sum(frac * jnp.mean(probs, axis=0))


def moe_share(p, x2d, cfg: ModelConfig, first=0, active=None):
    """The routed part that experts ``first .. first + held - 1`` (``held``
    = the experts ``p`` holds) give every token of x2d (T, D), with no
    capacity and nothing dropped.  Each held expert runs densely over all
    tokens, and its output is weighted by the token's gate: the router
    weight where the token picked it, else 0.  Returns ``(part (T, D), aux
    loss, counts)``; ``counts`` (2,) int32 are, over the tokens ``active``
    (T,) marks (all by default), the token-expert assignments that fall on
    held experts and the held experts that received at least one."""
    held = p["wi_gate"].shape[0]
    act = _ACTS[cfg.act]
    probs, topw, tope = route(p, x2d, cfg)
    picked = jax.nn.one_hot(tope - first, held, dtype=jnp.float32)  # T,k,e
    gate = (topw[..., None] * picked).sum(1)                   # (T, held)
    h = act(jnp.einsum("td,edf->etf", x2d, p["wi_gate"])) * \
        jnp.einsum("td,edf->etf", x2d, p["wi_up"])
    y = jnp.einsum("etf,efd->etd", h, p["wo"])
    out = jnp.einsum("etd,te->td", y.astype(jnp.float32), gate,
                     precision=jax.lax.Precision.HIGHEST)
    hits = picked.sum(1)                                       # (T, held)
    if active is not None:
        hits = hits * active[:, None]
    per_expert = hits.sum(0)
    counts = jnp.stack([per_expert.sum(), (per_expert > 0).sum()]
                       ).astype(jnp.int32)
    return out.astype(x2d.dtype), _aux_loss(probs, tope, cfg.num_experts), \
        counts


def shared_experts(p, x2d, cfg: ModelConfig):
    """The shared experts' output (0 without them)."""
    if "shared" not in p:
        return jnp.zeros_like(x2d)
    sp = p["shared"]
    act = _ACTS[cfg.act]
    return (act(x2d @ sp["wi_gate"]) * (x2d @ sp["wi_up"])) @ sp["wo"]


def _slot_assignments(expert_ids, num_experts, capacity):
    """Sort-based capacity slotting (the SLS 'segment traversal' on device).

    expert_ids (N,) -> (slot (N,), keep (N,)) where slot ∈ [0, E*C).
    """
    n = expert_ids.shape[0]
    order = jnp.argsort(expert_ids)              # stable
    sorted_e = expert_ids[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(num_experts))
    pos_in_expert = jnp.arange(n) - starts[sorted_e]
    keep_sorted = pos_in_expert < capacity
    slot_sorted = sorted_e * capacity + jnp.minimum(pos_in_expert,
                                                    capacity - 1)
    # un-sort back to assignment order
    inv = jnp.argsort(order)
    return slot_sorted[inv], keep_sorted[inv]


def moe_ffn_local(p, x2d, cfg: ModelConfig, ep_axis=None):
    """x2d (T, D) -> (T, D). When ``ep_axis`` is given we are inside a
    shard_map and experts are sharded over it (EP all-to-all dispatch)."""
    t, d = x2d.shape
    e, k = cfg.num_experts, cfg.experts_per_tok
    assert cfg.held_experts == e, "the all-to-all path holds every expert"
    act = _ACTS[cfg.act]
    probs, topw, tope = route(p, x2d, cfg)
    aux = _aux_loss(probs, tope, e)

    flat_e = tope.reshape(-1)                              # (T*k,)
    capacity = int(t * k / e * cfg.capacity_factor) + 1
    slot, keep = _slot_assignments(flat_e, e, capacity)

    src = jnp.repeat(x2d, k, axis=0)                       # (T*k, D)
    buf = jnp.zeros((e * capacity, d), x2d.dtype)
    buf = buf.at[jnp.where(keep, slot, e * capacity)].set(src,
                                                          mode="drop")

    if ep_axis is not None:
        n = jax.lax.axis_size(ep_axis)
        e_loc = e // n
        # tiled all-to-all: (E=n·E_loc, C, D) -> (E_loc, n·C, D)
        buf = buf.reshape(e, capacity, d)
        buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                                 tiled=True)
        wg, wu, wo = p["wi_gate"], p["wi_up"], p["wo"]     # local (E_loc,…)
    else:
        e_loc = e
        buf = buf.reshape(e, capacity, d)
        wg, wu, wo = p["wi_gate"], p["wi_up"], p["wo"]

    h = act(jnp.einsum("ecd,edf->ecf", buf, wg)) * \
        jnp.einsum("ecd,edf->ecf", buf, wu)
    out_buf = jnp.einsum("ecf,efd->ecd", h, wo)

    if ep_axis is not None:
        # reverse tiled all-to-all: (E_loc, n·C, D) -> (E, C, D)
        out_buf = jax.lax.all_to_all(out_buf, ep_axis, split_axis=1,
                                     concat_axis=0, tiled=True)
    out_buf = out_buf.reshape(e * capacity, d)

    gathered = jnp.where(keep[:, None], out_buf[slot], 0.0)  # (T*k, D)
    out = jnp.sum(gathered.reshape(t, k, d) *
                  topw[..., None].astype(x2d.dtype), axis=1)
    return out + shared_experts(p, x2d, cfg), aux


def _replicated_token_ep(p, x2d, cfg: ModelConfig, ep_axis, active=None):
    """Decode-path EP: tokens too few to split over the EP axis — every rank
    routes the (replicated) tokens, computes its own experts' share, and
    the shares combine with one psum.  No all-to-all; collective bytes are
    O(tokens·D), ideal for serve steps."""
    first = jax.lax.axis_index(ep_axis) * p["wi_gate"].shape[0]
    out, aux, counts = moe_share(p, x2d, cfg, first, active)
    out = jax.lax.psum(out, ep_axis) + shared_experts(p, x2d, cfg)
    return out, aux, jax.lax.psum(counts, ep_axis)


def moe_ffn(p, x, cfg: ModelConfig, mesh=None, ep_axis="model",
            data_axes=("data",), active=None):
    """x (B,S,D) -> ``(out (B,S,D), aux loss, counts)``; ``counts`` as
    :func:`moe_share` gives them, over the batch rows ``active`` (B,)
    marks.  Without a mesh: this device's share plus the shared experts.
    With a mesh: shard_map EP dispatch."""
    b, s, d = x.shape
    mask = jnp.broadcast_to((jnp.ones((b,), bool) if active is None
                             else active)[:, None], (b, s))
    if mesh is None or ep_axis is None:
        out, aux, counts = moe_share(p, x.reshape(-1, d), cfg,
                                     active=mask.reshape(-1))
        out = out + shared_experts(p, x.reshape(-1, d), cfg)
        return out.reshape(b, s, d), aux, counts

    n_ep = mesh.shape[ep_axis]
    seq_split = s % n_ep == 0 and s >= n_ep   # decode (s==1): can't split

    def body(p_, x_, m_):
        t = x_.shape[0] * x_.shape[1]
        if seq_split:
            # training and prefill count nothing
            out, aux = moe_ffn_local(p_, x_.reshape(t, d), cfg,
                                     ep_axis=ep_axis)
            counts = jnp.zeros((2,), jnp.int32)
        else:
            out, aux, counts = _replicated_token_ep(
                p_, x_.reshape(t, d), cfg, ep_axis, m_.reshape(t))
        aux = jax.lax.pmean(aux, ep_axis)
        for ax in data_axes:
            aux = jax.lax.pmean(aux, ax)
            counts = jax.lax.psum(counts, ax)
        return out.reshape(x_.shape), aux, counts

    dp = tuple(data_axes) if data_axes else None
    p_specs = jax.tree.map(lambda _: P("model", None, None), p)
    p_specs["router"] = P(None, None)
    if "shared" in p:
        p_specs["shared"] = jax.tree.map(lambda _: P(None, None), p["shared"])
    # tokens split over data axes on batch and (train/prefill) over the EP
    # axis on sequence
    x_spec = P(dp, ep_axis, None) if seq_split else P(dp, None, None)
    return shard_map(
        body, mesh=mesh, in_specs=(p_specs, x_spec, P(*x_spec[:2])),
        out_specs=(x_spec, P(), P()), check_vma=False)(p, x, mask)
