"""Sharded embedding engine — Ember's technique as a first-class framework
feature.

Every assigned architecture funnels its irregular-lookup work through this
module: token embedding (vocab-sharded tables = the paper's embedding
tables), the unembedding/logits projection, and the vocab-parallel cross
entropy that never materializes unsharded logits.

Strategy selection mirrors emberc's job (pick the best lookup schedule for
the target):

``take``          plain ``jnp.take`` — small/replicated tables;
``one_hot``       MXU-friendly one-hot matmul — tiny vocabularies only;
``masked_psum``   shard_map: mask ids to the local vocab shard, local take,
                  ``psum`` over the vocab axis — the production path for
                  model-sharded tables (the DAE decomposition at cluster
                  scale: local gather = access, psum = combine);
``masked_psum_scatter``  same but reduce-scatters the result over the
                  sequence axis (sequence parallelism) — halves the
                  collective bytes when the consumer is seq-sharded;
``pallas``        the emberc-compiled DAE gather kernel (single-device TPU
                  runtime path) — compiled through the *program-level*
                  pipeline, so repeated lookups of the same shape are
                  compile-cache hits.

The engine also builds the :class:`~repro.core.ops.EmbeddingProgram` that
describes ALL of a model step's irregular lookups (token embedding + the
vocab-parallel label gather + optional MoE dispatch), which the runtimes
compile once and reuse across steps (:func:`model_embedding_program`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .ops import EmbeddingOp, EmbeddingProgram, single_op_program


def choose_strategy(vocab_size: int, sharded: bool) -> str:
    if not sharded:
        return "take"
    if vocab_size <= 1024:
        return "one_hot"
    return "masked_psum"


# ---------------------------------------------------------------------------
# Lookup
# ---------------------------------------------------------------------------

def lookup(table: jax.Array, ids: jax.Array, *, mesh=None,
           vocab_axis: Optional[str] = None, strategy: str = "take",
           data_axes: tuple = (), seq_scatter: bool = False) -> jax.Array:
    """Embed ``ids (..., S)`` from ``table (V, D)`` → ``(..., S, D)``.

    ``data_axes`` are the mesh axes the leading (batch) dim of ``ids`` is
    sharded over.  With ``seq_scatter`` the result comes back sharded over
    the vocab axis along S (sequence parallelism via reduce-scatter).
    """
    if strategy == "take":
        return jnp.take(table, ids, axis=0)
    if strategy == "one_hot":
        oh = jax.nn.one_hot(ids, table.shape[0], dtype=table.dtype)
        return oh @ table
    if strategy in ("masked_psum", "masked_psum_scatter"):
        assert mesh is not None and vocab_axis is not None
        return _masked_lookup(table, ids, mesh, vocab_axis, data_axes,
                              seq_scatter or strategy.endswith("scatter"))
    if strategy == "pallas":
        return _pallas_lookup(table, ids)
    raise ValueError(strategy)


def _pallas_lookup(table, ids):
    """Single-device DAE path: compile (cached) + run the gather kernel."""
    from . import backend_pallas as bp
    from .pipeline import compile_program
    from ..kernels.ops import default_interpret
    n_tok = int(np.prod(ids.shape))
    op = EmbeddingOp("gather", num_segments=n_tok,
                     num_embeddings=int(table.shape[0]),
                     emb_len=int(table.shape[1]))
    pres = compile_program(single_op_program(op, "lookup"), "O3")
    out = bp.execute(pres.units[0].result,
                     {"table": table, "idxs": ids.reshape(-1)},
                     interpret=default_interpret())
    return out.reshape(*ids.shape, table.shape[1])


def model_embedding_program(*, vocab_size: int, d_model: int, tokens: int,
                            extra_ops: tuple = (),
                            name: str = "model-step") -> EmbeddingProgram:
    """The irregular-lookup program of one model step.

    Token embedding and the label-logit gather of the vocab-parallel cross
    entropy both read the embed table — annotated as a shared table so the
    fusion pass stacks it once; ``extra_ops`` appends model-specific lookups
    (e.g. :func:`repro.models.moe.dispatch_op`).  The result is what
    runtimes hand to :func:`repro.core.pipeline.compile_program`, whose
    cache makes per-step recompiles free.
    """
    ops = (("tok_embed",
            EmbeddingOp("gather", num_segments=tokens,
                        num_embeddings=vocab_size, emb_len=d_model)),
           ("label_gather",
            EmbeddingOp("gather", num_segments=tokens,
                        num_embeddings=vocab_size, emb_len=d_model)))
    return EmbeddingProgram(name, ops + tuple(extra_ops),
                            shared_tables=(("tok_embed", "label_gather"),))


def _masked_lookup(table, ids, mesh, vocab_axis, data_axes, seq_scatter):
    def body(tbl, ids_):
        # tbl is the local vocab shard (V/n, D); ids_ the local data shard
        shard = jax.lax.axis_index(vocab_axis)
        v_local = tbl.shape[0]
        lo = shard * v_local
        local = ids_ - lo
        in_range = (local >= 0) & (local < v_local)
        local = jnp.clip(local, 0, v_local - 1)
        emb = jnp.take(tbl, local, axis=0)          # access: local gather
        emb = jnp.where(in_range[..., None], emb, 0.0)
        if seq_scatter:                              # combine: reduce-scatter
            return jax.lax.psum_scatter(emb, vocab_axis,
                                        scatter_dimension=emb.ndim - 2,
                                        tiled=True)
        return jax.lax.psum(emb, vocab_axis)         # combine: all-reduce

    # batch dim sharded over ALL data axes jointly (one dim, axis tuple)
    dp = tuple(data_axes) if data_axes else None
    ids_spec = P(dp, *(None,) * (ids.ndim - 1))
    out_tail = (vocab_axis, None) if seq_scatter else (None, None)
    out_spec = P(dp, *(None,) * (ids.ndim - 2), *out_tail)
    return shard_map(body, mesh=mesh,
                     in_specs=(P(vocab_axis, None), ids_spec),
                     out_specs=out_spec, check_vma=False)(table, ids)


# ---------------------------------------------------------------------------
# Unembedding + vocab-parallel cross entropy (Megatron-style)
# ---------------------------------------------------------------------------

def logits(x: jax.Array, table: jax.Array) -> jax.Array:
    """x (..., D) @ table.T (D, V) → (..., V); vocab-sharded under GSPMD."""
    return jax.lax.dot_general(
        x, table, (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def xent_vocab_parallel(x: jax.Array, table: jax.Array, labels: jax.Array, *,
                        mesh, vocab_axis: str,
                        data_axes: tuple = ()) -> jax.Array:
    """Fused unembed + softmax cross entropy over a vocab-sharded table.

    Never materializes an unsharded (tokens, V) logits tensor: each shard
    computes local logits, the log-sum-exp reduces with ``pmax``/``psum``
    over the vocab axis, and the label logit is fetched from whichever shard
    owns it.  Returns the mean loss (replicated).
    """
    def body(x_, tbl, labels_):
        shard = jax.lax.axis_index(vocab_axis)
        v_local = tbl.shape[0]
        lo = shard * v_local
        lg = jax.lax.dot_general(
            x_, tbl, (((x_.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (..., V/n)
        # the max is a constant stability shift — stop_gradient *before*
        # pmax (which has no JVP rule) so no tangent ever reaches it
        m = jax.lax.pmax(jax.lax.stop_gradient(jnp.max(lg, axis=-1)),
                         vocab_axis)
        se = jax.lax.psum(jnp.sum(jnp.exp(lg - m[..., None]), axis=-1),
                          vocab_axis)
        lse = m + jnp.log(se)
        local_label = labels_ - lo
        in_range = (local_label >= 0) & (local_label < v_local)
        local_label = jnp.clip(local_label, 0, v_local - 1)
        picked = jnp.take_along_axis(lg, local_label[..., None],
                                     axis=-1)[..., 0]
        label_logit = jax.lax.psum(jnp.where(in_range, picked, 0.0),
                                   vocab_axis)
        loss = jnp.mean(lse - label_logit)
        for ax in data_axes:
            loss = jax.lax.pmean(loss, ax)   # mean over all tokens
        return loss

    dp = tuple(data_axes) if data_axes else None
    x_spec = P(dp, *(None,) * (x.ndim - 1))
    lbl_spec = P(dp, *(None,) * (labels.ndim - 1))
    loss = shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(vocab_axis, None), lbl_spec),
        out_specs=P(),
        check_vma=False)(x, table, labels)
    return loss
