"""Sharding rules: params, optimizer state (ZeRO-1), KV caches, batches.

Conventions (DESIGN.md §5):
  * embedding / unembedding tables: vocab → ``model``
  * attention projections: heads (fused head·dim columns) → ``model``
  * MLP: hidden → ``model`` (column then row parallel)
  * MoE: experts → ``model`` (EP == TP axis)
  * SSM/xLSTM inner dims → ``model``
  * batch dims → (``pod``, ``data``)
  * optimizer moments: params' spec, plus ZeRO-1 sharding of replicated
    leaves over ``data``
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.tree_util import DictKey, SequenceKey

COL = {"wq", "wk", "wv", "wi_gate", "wi_up", "w_in", "w_gate", "w_if"}
ROW = {"wo", "w_out"}
REPL = {"router", "A_log", "D", "dt_bias", "b_i", "b_f", "b", "conv_w",
        "norm1", "norm2", "norm_x", "norm_z", "final_norm", "enc_norm",
        "frontend_proj", "w_kr", "r"}


def _path_names(path):
    out = []
    for k in path:
        if isinstance(k, DictKey):
            out.append(str(k.key))
        elif isinstance(k, SequenceKey):
            out.append(f"[{k.idx}]")
        else:
            out.append(str(k))
    return out


def param_spec(path, leaf) -> P:
    names = _path_names(path)
    name = names[-1]
    stacked = names[0] in ("scan", "enc_scan")
    in_moe = "moe" in names and "shared" not in names

    if name in ("embed", "lm_head"):
        base = ("model", None)
    elif name in REPL or leaf.ndim <= 1:
        base = (None,) * (leaf.ndim - (1 if stacked else 0))
    elif in_moe and name in ("wi_gate", "wi_up", "wo"):
        base = ("model", None, None)          # experts → model (EP)
    elif name in ("w_dkv", "w_uk", "w_uv"):
        base = (None, "model")
    elif name in COL:
        base = (None, "model")
    elif name in ROW:
        base = ("model", None)
    else:
        base = (None,) * (leaf.ndim - (1 if stacked else 0))
    if stacked:
        base = (None,) + tuple(base)
    assert len(base) == leaf.ndim, (names, leaf.ndim, base)
    return P(*base)


def param_specs(params):
    return jax.tree_util.tree_map_with_path(param_spec, params)


def zero1_specs(params, specs, data_axes: tuple, mesh):
    """ZeRO-1: optimizer moments of *replicated* leaves shard their leading
    dim over the data axes when divisible (param itself stays replicated)."""
    dsize = int(np.prod([mesh.shape[a] for a in data_axes])) if data_axes \
        else 1

    def one(leaf, spec):
        if dsize <= 1 or leaf.ndim == 0:
            return spec
        if all(s is None for s in spec) and leaf.shape[0] % dsize == 0 \
                and leaf.shape[0] >= dsize:
            return P(tuple(data_axes), *((None,) * (leaf.ndim - 1)))
        return spec

    return jax.tree.map(one, params, specs)


# ---------------------------------------------------------------------------
# Caches & batches
# ---------------------------------------------------------------------------

def cache_spec(path, leaf, batch_axes, msize: int = 1) -> P:
    names = _path_names(path)
    name = names[-1]
    stacked = names[0] == "scan"
    nd = leaf.ndim - (1 if stacked else 0)
    ba = batch_axes if batch_axes else None

    if name == "len" or nd == 0:
        base = (None,) * nd
    elif name in ("k", "v"):            # (B, S, Hkv, hd)
        hkv = leaf.shape[-2]
        # few-KV-head archs (gemma3 kv=4 < model=16): shard head_dim instead
        base = (ba, None, "model", None) if hkv % msize == 0 \
            else (ba, None, None, "model")
    elif name == "c" and nd == 3:       # mla latent (B, S, r)
        base = (ba, None, "model")
    elif name == "kr":                  # (B, S, rd)
        base = (ba, None, None)
    elif name == "state":               # mamba (B, H, P, N)
        base = (ba, "model", None, None)
    elif name == "conv":                # (B, 3, d_inner)
        base = (ba, None, "model")
    elif name == "C":                   # mlstm (B, H, hd, hd)
        base = (ba, None, "model", None)
    elif name == "n" and nd == 4:       # mlstm normalizer (B, H, 1, hd)
        base = (ba, None, None, None)
    elif nd == 2:                       # slstm scalars (B, d)
        base = (ba, "model")
    else:
        base = (ba,) + (None,) * (nd - 1)
    if stacked:
        base = (None,) + tuple(base)
    base = tuple(base)[:leaf.ndim]
    base = base + (None,) * (leaf.ndim - len(base))
    return P(*base)


def cache_specs(caches, batch: int, mesh, data_axes: tuple):
    dsize = int(np.prod([mesh.shape[a] for a in data_axes])) if data_axes \
        else 1
    ba = tuple(data_axes) if batch % max(dsize, 1) == 0 and batch >= dsize \
        else ()
    msize = mesh.shape["model"]
    specs = jax.tree_util.tree_map_with_path(
        lambda p, l: cache_spec(p, l, ba, msize), caches)
    return sanitize_specs(specs, caches, mesh)


def batch_specs(batch_struct: dict, batch: int, mesh, data_axes: tuple):
    dsize = int(np.prod([mesh.shape[a] for a in data_axes])) if data_axes \
        else 1
    ba = tuple(data_axes) if batch % max(dsize, 1) == 0 and batch >= dsize \
        else None

    def one(leaf):
        return P(ba, *((None,) * (leaf.ndim - 1)))
    return jax.tree.map(one, batch_struct)


def sanitize_specs(specs, tree, mesh):
    """Drop any per-dim axis assignment that does not divide the dim —
    e.g. 4 KV heads cannot shard over model=16, so the spec falls back to
    the head_dim (caller's alternate) or replication for that dim."""
    def one(spec, leaf):
        dims = []
        for i in range(leaf.ndim):
            ax = spec[i] if i < len(spec) else None
            if ax is None:
                dims.append(None)
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = int(np.prod([mesh.shape[a] for a in axes]))
            dims.append(ax if leaf.shape[i] % size == 0 and
                        leaf.shape[i] >= size else None)
        return P(*dims)
    return jax.tree.map(one, specs, tree,
                        is_leaf=lambda x: isinstance(x, P))


def to_shardings(mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Executor table shardings (vocab-partitioned stacked tables)
# ---------------------------------------------------------------------------

def table_row_sharding(mesh, axis: str = "model") -> NamedSharding:
    """Row (vocab) sharding of a stacked embedding table — the placement the
    sharded :class:`~repro.core.executor.ProgramExecutor` gives its fused
    stacked buffers and routed ``(S, …)`` offset-stream buckets (leading dim
    = shard)."""
    return leading_axis_sharding(mesh, axis, 2)


def leading_axis_sharding(mesh, axis: str = "model",
                          ndim: int = 2) -> NamedSharding:
    """Shard only the leading dim over ``axis`` — stacked tables and routed
    2-D buckets (``ndim=2``), and the collective exchange's ``(S_src, …)``
    send buffers (``ndim`` 3/4: dim 0 = source shard)."""
    return NamedSharding(mesh, P(axis, *((None,) * (ndim - 1))))


def replicated_sharding(mesh, ndim: int = 1) -> NamedSharding:
    """Fully-replicated placement (the executor's ``roff`` streams and
    pooled outputs)."""
    return NamedSharding(mesh, P(*(None,) * ndim))
