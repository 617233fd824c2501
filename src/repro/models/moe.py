"""Mixture-of-Experts layer with expert-parallel (EP) dispatch.

MoE dispatch *is* an embedding operation in the paper's taxonomy: tokens are
gathered into per-expert capacity buffers by irregular indices (an SLS-class
scatter/gather, DESIGN.md §4), so the dispatch path is built on the same
sort-and-slot structure emberc generates for SLS — realized here at cluster
scale with a shard_map: local sort-based slotting (access), all-to-all over
the expert/model axis (the queue), expert FFN (execute), reverse all-to-all
and weighted combine.

Capacity-based dropping keeps every shape static (required for pjit); the
aux load-balance loss keeps the router from collapsing.
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


def undispatch_program(cfg: ModelConfig, tokens: int, name=None):
    """The MoE un-dispatch as a standalone one-op
    :class:`~repro.core.ops.EmbeddingProgram` — the second member of the
    serving :func:`~repro.core.executor.pipeline_group`: wave W's expert
    outputs gather back to token order while wave W+1's decode embed
    marshals against the shared staging pool."""
    from ..core.ops import EmbeddingProgram
    return EmbeddingProgram(name or f"{cfg.name}-moe-undispatch",
                            (("moe_undispatch", dispatch_op(cfg, tokens)),))


def init_moe(key, cfg: ModelConfig, dtype):
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e), jnp.float32),
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
    act = _ACTS[cfg.act]

    logits = (x2d.astype(jnp.float32) @ p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    topw, tope = jax.lax.top_k(probs, k)                   # (T,k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # aux load-balance loss (replicated; mean of frac_e * prob_e * E)
    frac = jnp.mean(jax.nn.one_hot(tope, e, dtype=jnp.float32), axis=(0, 1))
    aux = e * jnp.sum(frac * jnp.mean(probs, axis=0))

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

    if "shared" in p:
        sp = p["shared"]
        out = out + (act(x2d @ sp["wi_gate"]) * (x2d @ sp["wi_up"])) @ sp["wo"]
    return out, aux


def _replicated_token_ep(p, x2d, cfg: ModelConfig, ep_axis):
    """Decode-path EP: tokens too few to split over the EP axis — every rank
    routes the (replicated) tokens, processes only its local experts, and the
    outputs combine with one psum.  No all-to-all; collective bytes are
    O(tokens·D), ideal for serve steps."""
    t, d = x2d.shape
    e, k = cfg.num_experts, cfg.experts_per_tok
    act = _ACTS[cfg.act]
    n = jax.lax.axis_size(ep_axis)
    rank = jax.lax.axis_index(ep_axis)
    e_loc = e // n

    logits = x2d.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    topw, tope = jax.lax.top_k(probs, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    frac = jnp.mean(jax.nn.one_hot(tope, e, dtype=jnp.float32), axis=(0, 1))
    aux = e * jnp.sum(frac * jnp.mean(probs, axis=0))

    flat_e = tope.reshape(-1)
    capacity = int(t * k / e * cfg.capacity_factor) + 1
    slot, keep = _slot_assignments(flat_e, e, capacity)
    src = jnp.repeat(x2d, k, axis=0)
    buf = jnp.zeros((e * capacity, d), x2d.dtype)
    buf = buf.at[jnp.where(keep, slot, e * capacity)].set(src, mode="drop")

    my = jax.lax.dynamic_slice_in_dim(buf, rank * e_loc * capacity,
                                      e_loc * capacity).reshape(
                                          e_loc, capacity, d)
    h = act(jnp.einsum("ecd,edf->ecf", my, p["wi_gate"])) * \
        jnp.einsum("ecd,edf->ecf", my, p["wi_up"])
    out_loc = jnp.einsum("ecf,efd->ecd", h, p["wo"]).reshape(-1, d)
    out_buf = jnp.zeros((e * capacity, d), x2d.dtype)
    out_buf = jax.lax.dynamic_update_slice_in_dim(
        out_buf, out_loc, rank * e_loc * capacity, axis=0)
    out_buf = jax.lax.psum(out_buf, ep_axis)

    gathered = jnp.where(keep[:, None], out_buf[slot], 0.0)
    out = jnp.sum(gathered.reshape(t, k, d) *
                  topw[..., None].astype(x2d.dtype), axis=1)
    if "shared" in p:
        sp = p["shared"]
        out = out + (act(x2d @ sp["wi_gate"]) * (x2d @ sp["wi_up"])) @ sp["wo"]
    return out, aux


def moe_ffn(p, x, cfg: ModelConfig, mesh=None, ep_axis="model",
            data_axes=("data",)):
    """x (B,S,D) -> (B,S,D). With a mesh: shard_map EP dispatch."""
    b, s, d = x.shape
    if mesh is None or ep_axis is None:
        out, aux = moe_ffn_local(p, x.reshape(-1, d), cfg)
        return out.reshape(b, s, d), aux

    n_ep = mesh.shape[ep_axis]
    seq_split = s % n_ep == 0 and s >= n_ep   # decode (s==1): can't split

    def body(p_, x_):
        t = x_.shape[0] * x_.shape[1]
        if seq_split:
            out, aux = moe_ffn_local(p_, x_.reshape(t, d), cfg,
                                     ep_axis=ep_axis)
        else:
            out, aux = _replicated_token_ep(p_, x_.reshape(t, d), cfg,
                                            ep_axis)
        aux = jax.lax.pmean(aux, ep_axis)
        for ax in data_axes:
            aux = jax.lax.pmean(aux, ax)
        return out.reshape(x_.shape), aux

    dp = tuple(data_axes) if data_axes else None
    p_specs = jax.tree.map(lambda _: P("model", None, None), p)
    p_specs["router"] = P(None, None)
    if "shared" in p:
        p_specs["shared"] = jax.tree.map(lambda _: P(None, None), p["shared"])
    # tokens split over data axes on batch and (train/prefill) over the EP
    # axis on sequence
    x_spec = P(dp, ep_axis, None) if seq_split else P(dp, None, None)
    out, aux = shard_map(
        body, mesh=mesh, in_specs=(p_specs, x_spec),
        out_specs=(x_spec, P()), check_vma=False)(p, x)
    return out, aux
