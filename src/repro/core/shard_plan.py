"""Vocab-sharded fused programs — the device half of the sharded executor.

At serving scale one device cannot hold the fused stacked tables, so the
steady-state executor shards them along the vocab (row) dimension over the
``model`` axis of the production mesh, FlexEMR-style: the *indices* move to
the data, the data never moves to the compute.

All layout and routing decisions — the interleaved cold split, the
replicated hot slabs, per-lookup owner/local-address resolution, the
capacity buckets of the exchange — live in the compiled
:class:`~repro.core.access_plan.AccessPlan` (the ``plan-access`` pass).
This module only *realizes* a plan on a mesh:

* :func:`shard_stack_tables` materializes the plan's per-shard local tables
  (cold slices + replicated hot slabs) as one row-sharded global array;
* :func:`put_sharded` / :func:`put_replicated` place the per-step operand
  buffers: the host-exchange ``(S_dst, …)`` routed buckets, or the
  collective path's ``(S_src, …)`` resident send lattice;
* ``make_csr_body`` / ``make_gather_body`` (host exchange) and
  ``make_csr_collective_body`` / ``make_gather_collective_body``
  (device-collective exchange) + :func:`sharded_call` build the
  ``jit(shard_map(...))`` execute bodies: optional on-device
  ``all_to_all`` index exchange, local pool, then pooled-rows-back combine
  — fully replicated (``psum``/``pmax``/``pmin``) or **reduce-scattered**
  so each shard keeps only its contiguous segment slice — with
  ⊕-identity-exact empty-segment handling throughout.

Exchange protocol (per step, the access side doing the all-to-all on the
offset stream):

    1. **indices out** — the host interprets the AccessPlan: every lookup
       resolves to ``(owner shard, fully-rebased local address)``; hot rows
       are replicated so their lookups are *local* (round-robin on the host
       exchange; served at the *source* shard — zero wire traffic — on the
       collective), cold rows route to ``cold_rank // C_t``.  Buckets are
       padded to the plan's capacity lattice, so the exchange is
       retrace-free across ragged steps.  ``exchange="host"`` realizes the
       move as a single-controller sharded ``device_put`` of per-owner
       buckets; ``exchange="collective"`` device_puts ONE ``(S_src, S_dst,
       …)`` send lattice and runs ``jax.lax.all_to_all`` *inside* the
       shard_map body (each lookup travels with its fused segment id, so
       the receiver rebuilds a canonical sub-CSR without host help).
    2. **local pool** — each shard runs the batched SLS kernel (or the XLA
       reference body) over its local sub-CSR; since routed indices arrive
       fully rebased, the kernel's ``seg_base`` stream is all-zero here.
    3. **pooled rows back** — the partial pools combine across shards with
       ``psum`` (⊕=add) / ``pmax`` / ``pmin`` when replicated, or
       reduce-scatter (``psum_scatter``; the all_to_all transpose for
       max/min) when each shard owns a segment slice; locally-empty
       segments contribute the ⊕-identity, and globally-empty segments are
       fixed to 0 afterwards (the SLS convention), so a shard receiving
       zero indices for a step is a no-op, not a hazard.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..kernels import ops as kops
from ..launch.sharding import (leading_axis_sharding, replicated_sharding,
                               table_row_sharding)
from .access_plan import AccessPlan

_ADD_IDENT = {"add": 0.0, "max": -np.inf, "min": np.inf}


def shard_count(mesh, axis: str = "model") -> int:
    """Size of ``axis`` in ``mesh`` (1 when mesh is None / axis absent) —
    the executor's single switch between the replicated and sharded paths."""
    if mesh is None:
        return 1
    shape = dict(mesh.shape)
    return int(shape.get(axis, 1))


# ---------------------------------------------------------------------------
# Layout realization: the plan's per-shard tables on a mesh
# ---------------------------------------------------------------------------

def shard_stack_tables(parts: list, plan: AccessPlan, mesh,
                       axis: str) -> jax.Array:
    """Device-side sharded stacking of one fused unit per its AccessPlan:
    each slot's cold tail is striped over the shards (ceil-split, padded),
    its hot slab is replicated into every shard's local table, and the
    ``(S·L·blk, E)`` result is placed row-sharded over ``axis``.  Shard
    ``k``'s local table is built and placed one shard at a time, so no
    device ever holds the whole stack (a table set sized to fill the
    mesh's memory does not fit one device)."""
    parts = [jnp.asarray(p) for p in parts]
    local = plan.local_rows * plan.blk
    shape = (plan.shards * local, parts[0].shape[1])
    sharding = table_row_sharding(mesh, axis)
    owners = {}                     # shard -> devices holding its rows
    for d, idx in sharding.addressable_devices_indices_map(shape).items():
        owners.setdefault((idx[0].start or 0) // local, []).append(d)
    arrays = []
    for k, devices in sorted(owners.items()):
        block = _local_table(parts, plan, k)
        arrays += [jax.device_put(block, d) for d in devices]
    return jax.make_array_from_single_device_arrays(shape, sharding, arrays)


def _local_table(parts: list, plan: AccessPlan, k: int) -> jax.Array:
    """Shard ``k``'s ``(L·blk, E)`` table: every slot's ``k``-th cold
    stripe (padded to the slot's capacity), then every hot slab."""
    cold, hot = [], []
    for slot, p in zip(plan.slots, parts):
        rows = slot.cap * plan.blk
        if slot.hot_rows:
            ids = slot.cold_ids[k * slot.cap:(k + 1) * slot.cap]
            stripe = jnp.take(p, plan.phys_rows(ids), axis=0)
            hot.append(jnp.take(p, plan.phys_rows(slot.hot_ids), axis=0))
        else:
            stripe = p[k * rows:(k + 1) * rows]
        if stripe.shape[0] < rows:
            stripe = jnp.pad(stripe, ((0, rows - stripe.shape[0]), (0, 0)))
        cold.append(stripe)
    return jnp.concatenate(cold + hot)


def compute_spill(pair_counts: np.ndarray, max_fraction: float,
                  overload_ratio: float) -> dict:
    """Hot-spill table from one step's ``(S_src, S_dst)`` pair counts.

    The lattice diagonal is the hot (source-served) traffic; when a source
    shard's diagonal exceeds ``overload_ratio ×`` the mean diagonal load,
    a bounded ``max_fraction`` of its hot lookups should spill to its
    least-loaded peer (by total routed column load).  Returns the
    ``{src: (dst, fraction)}`` mapping
    :meth:`~repro.core.access_plan.AccessPlan.route_csr_collective`
    applies on the *next* step — the feedback edge of the executor's
    spill-aware lattice fill."""
    pair = np.asarray(pair_counts, np.int64)
    s = pair.shape[0]
    if s < 2 or max_fraction <= 0.0:
        return {}
    diag = np.diag(pair).astype(np.float64)
    mean = diag.mean()
    if mean <= 0:
        return {}
    load = pair.sum(axis=0).astype(np.float64)   # per-dst routed work
    spill: dict = {}
    for src in np.flatnonzero(diag > overload_ratio * mean):
        peers = np.array([d for d in range(s) if d != src])
        dst = int(peers[np.argmin(load[peers])])
        spill[int(src)] = (dst, float(max_fraction))
    return spill


def put_sharded(arr: np.ndarray, mesh, axis: str) -> jax.Array:
    """Place a host ``(S, …)`` bucket array so shard ``s`` holds block ``s``
    of the leading dim: the host-exchange scatter (dim 0 = *destination*
    shard) and the collective path's resident send buffer (dim 0 = *source*
    shard — the ``all_to_all`` moves the indices from there)."""
    assert arr.ndim >= 2, arr.shape
    return jax.device_put(arr, leading_axis_sharding(mesh, axis, arr.ndim))


def put_replicated(arr, mesh) -> jax.Array:
    a = jnp.asarray(arr)
    return jax.device_put(a, replicated_sharding(mesh, a.ndim))


# ---------------------------------------------------------------------------
# Device-side execute bodies (steps 2+3: local pool + pooled rows back)
# ---------------------------------------------------------------------------

def _combine(out, axis: str, add_op: str):
    if add_op == "add":
        return jax.lax.psum(out, axis)
    return (jax.lax.pmax if add_op == "max" else jax.lax.pmin)(out, axis)


def _reduce_scatter(x, axis: str, add_op: str, shards: int, seg_cap: int):
    """⊕-reduce-scatter of per-shard partial pools along dim 0: pad the
    segment dim to the ``shards·seg_cap`` grid and leave each shard holding
    the combined rows of its own contiguous segment slice (rows past the
    true segment count are padding and never read).  ``psum_scatter`` is
    the ⊕=add primitive; max/min reduce-scatter via the all_to_all
    transpose (each shard collects every peer's partials for its slice)."""
    pad = shards * seg_cap - x.shape[0]
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                    constant_values=_ADD_IDENT[add_op])
    if add_op == "add":
        return jax.lax.psum_scatter(x, axis, scatter_dimension=0,
                                    tiled=True)
    r = jax.lax.all_to_all(x.reshape((shards, seg_cap) + x.shape[1:]),
                           axis, 0, 0)
    return (jnp.max if add_op == "max" else jnp.min)(r, axis=0)


def _finish_csr(out, counts, *, axis: str, add_op: str, replicate: bool,
                shards: int, seg_cap: int):
    """Cross-shard combine + SLS zero-fix of one CSR unit's partial pools.
    ``counts`` are the shard's per-segment lookup counts (locally-empty
    segments hold the ⊕-identity in ``out``); globally-empty segments are
    fixed to 0 after the merge — the SLS convention — using the summed
    counts, reduce-scattered alongside the rows when outputs are owned."""
    if replicate:
        merged = _combine(out, axis, add_op)
        if add_op == "add":
            return merged
        total = jax.lax.psum(counts, axis)
        return jnp.where((total > 0)[:, None], merged, 0.0)
    merged = _reduce_scatter(out, axis, add_op, shards, seg_cap)
    if add_op == "add":
        return merged
    pad = shards * seg_cap - counts.shape[0]
    if pad:
        counts = jnp.pad(counts, (0, pad))
    total = jax.lax.psum_scatter(counts, axis, scatter_dimension=0,
                                 tiled=True)
    return jnp.where((total > 0)[:, None], merged, 0.0)


def jnp_sls_local(table, ptrs, idxs, vals, roff, *, num_segments: int,
                  add_op: str, mul_op: str):
    """Traceable XLA reference of the local-shard SLS pool (the ``jax``
    backend's execute unit under shard_map).  Locally-empty segments yield
    the ⊕-identity (NOT the SLS zero) so cross-shard merging stays exact;
    the caller zero-fixes globally-empty segments after the combine."""
    cap = idxs.shape[0]
    pos = jnp.arange(cap, dtype=jnp.int32)
    p32 = ptrs.astype(jnp.int32)
    seg = jnp.searchsorted(p32[1:], pos, side="right")
    valid = pos < p32[-1]
    segc = jnp.minimum(seg, num_segments - 1)
    rows = jnp.take(table, idxs + jnp.take(roff, segc), axis=0)
    if vals is not None:
        w = vals[:, None].astype(rows.dtype)
        rows = rows * w if mul_op == "mul" else rows + w
    ident = jnp.asarray(_ADD_IDENT[add_op], rows.dtype)
    rows = jnp.where(valid[:, None], rows, ident)
    reduce = {"add": jax.ops.segment_sum, "max": jax.ops.segment_max,
              "min": jax.ops.segment_min}[add_op]
    out = reduce(rows, segc, num_segments=num_segments)
    if add_op != "add":
        counts = p32[1:] - p32[:-1]
        out = jnp.where((counts > 0)[:, None], out, ident)
    return out


def _local_pool_csr(table, roff, ptrs, idxs, vals, *, backend: str,
                    add_op: str, mul_op: str, nseg: int, max_lookups: int,
                    col_tile: int, interpret: bool):
    """One shard's partial pool over a local sub-CSR, with locally-empty
    segments holding the ⊕-identity (merge-ready).  Returns
    ``(out, counts)`` — counts feed the globally-empty zero-fix."""
    counts = ptrs[1:] - ptrs[:-1]
    if backend == "pallas":
        out = kops.sls(table, ptrs, idxs, vals, num_segments=nseg,
                       max_lookups=max_lookups, add_op=add_op,
                       mul_op=mul_op, col_tile=col_tile,
                       interpret=interpret, seg_base=roff)
        if add_op != "add":
            # the kernel zeroed locally-empty segments (SLS convention);
            # restore the ⊕-identity before merging across shards
            out = jnp.where((counts > 0)[:, None], out,
                            jnp.asarray(_ADD_IDENT[add_op], out.dtype))
    else:
        out = jnp_sls_local(table, ptrs, idxs, vals, roff,
                            num_segments=nseg, add_op=add_op,
                            mul_op=mul_op)
    return out, counts


def make_csr_body(op, *, axis: str, backend: str, max_lookups: int,
                  need_vals: bool, interpret: bool, col_tile: int,
                  replicate: bool = True, shards: int = 1,
                  seg_cap: int = 0):
    """shard_map body of one fused CSR unit under the *host* exchange: the
    bucketed operands arrive pre-routed with a leading length-1 shard dim
    (in_specs P(axis, …)); the table arrives as the local (L·blk, E) slice;
    ``roff`` replicated (all-zero — routed indices arrive fully rebased).
    Local pool, then pooled rows back — replicated (``psum``/``pmax``) or
    reduce-scattered to each shard's segment slice."""
    add_op, mul_op = op.semiring.add, op.semiring.mul
    nseg = op.num_segments

    def body(table, roff, ptrs, idxs, *maybe_vals):
        out, counts = _local_pool_csr(
            table, roff, ptrs[0], idxs[0],
            maybe_vals[0][0] if need_vals else None,
            backend=backend, add_op=add_op, mul_op=mul_op, nseg=nseg,
            max_lookups=max_lookups, col_tile=col_tile,
            interpret=interpret)
        return _finish_csr(out, counts, axis=axis, add_op=add_op,
                           replicate=replicate, shards=shards,
                           seg_cap=seg_cap)

    return body


def make_csr_collective_body(op, *, axis: str, backend: str,
                             max_lookups: int, need_vals: bool,
                             interpret: bool, col_tile: int,
                             replicate: bool, shards: int, seg_cap: int):
    """shard_map body of one fused CSR unit under the *collective* exchange.

    The operands arrive as the resident send buffer — per shard a
    ``(S, 2, cap)`` lattice of (segment id, local index) pairs keyed by
    destination (plus a ``(S, cap)`` vals lattice) — and the index exchange
    itself runs on device: ``all_to_all`` transposes the lattice so dim 0
    becomes *received-from*.  Pad slots carry the segment sentinel
    ``num_segments``.  The received streams rebuild a canonical local
    sub-CSR (pallas: stable sort by segment + ``searchsorted`` offsets; the
    kernel then runs exactly as on the host-exchange path) or feed the
    segment-reduce directly (jax backend), and the pooled rows combine
    replicated or reduce-scattered."""
    add_op, mul_op = op.semiring.add, op.semiring.mul
    nseg = op.num_segments

    def body(table, roff, ints, *maybe_vals):
        recv = jax.lax.all_to_all(ints[0], axis, 0, 0)   # dim 0: src shard
        segs = recv[:, 0, :].reshape(-1)
        idxs = recv[:, 1, :].reshape(-1)
        vals = (jax.lax.all_to_all(maybe_vals[0][0], axis, 0, 0).reshape(-1)
                if need_vals else None)
        valid = segs < nseg
        if backend == "pallas":
            order = jnp.argsort(segs)          # stable; sentinels sort last
            ptrs = jnp.searchsorted(
                jnp.take(segs, order),
                jnp.arange(nseg + 1, dtype=segs.dtype)).astype(jnp.int32)
            out, counts = _local_pool_csr(
                table, roff, ptrs, jnp.take(idxs, order),
                jnp.take(vals, order) if need_vals else None,
                backend=backend, add_op=add_op, mul_op=mul_op, nseg=nseg,
                max_lookups=max_lookups, col_tile=col_tile,
                interpret=interpret)
        else:
            segc = jnp.minimum(segs, nseg - 1).astype(jnp.int32)
            rows = jnp.take(table, idxs, axis=0)
            if need_vals:
                w = vals[:, None].astype(rows.dtype)
                rows = rows * w if mul_op == "mul" else rows + w
            ident = jnp.asarray(_ADD_IDENT[add_op], rows.dtype)
            rows = jnp.where(valid[:, None], rows, ident)
            reduce = {"add": jax.ops.segment_sum,
                      "max": jax.ops.segment_max,
                      "min": jax.ops.segment_min}[add_op]
            out = reduce(rows, segc, num_segments=nseg)
            counts = jax.ops.segment_sum(valid.astype(jnp.int32), segc,
                                         num_segments=nseg)
            if add_op != "add":
                out = jnp.where((counts > 0)[:, None], out, ident)
        return _finish_csr(out, counts, axis=axis, add_op=add_op,
                           replicate=replicate, shards=shards,
                           seg_cap=seg_cap)

    return body


def make_gather_body(op, *, axis: str, backend: str, interpret: bool,
                     replicate: bool = True, shards: int = 1,
                     seg_cap: int = 0):
    """shard_map body of one fused gather unit under the host exchange:
    masked local block-gather; partial rows back via psum (exactly one
    shard owns each segment) or reduce-scattered to the owner slices."""
    blk = op.block_rows

    def body(table, roff, idxs, mask):
        i = idxs[0] + roff
        rows = _local_block_gather(table, i, blk, backend, interpret)
        rows = rows * mask[0][:, None, None].astype(rows.dtype)
        if replicate:
            return jax.lax.psum(rows, axis)
        return _reduce_scatter(rows, axis, "add", shards, seg_cap)

    return body


def _local_block_gather(table, i, blk: int, backend: str, interpret: bool):
    if backend == "pallas":
        return kops.block_gather(table, i, block_rows=blk,
                                 interpret=interpret)
    r = i[:, None] * blk + jnp.arange(blk, dtype=i.dtype)[None, :]
    return jnp.take(table, r.reshape(-1), axis=0).reshape(
        i.shape[0], blk, table.shape[-1])


def make_gather_collective_body(op, *, axis: str, backend: str,
                                interpret: bool, replicate: bool,
                                shards: int, seg_cap: int):
    """Collective-exchange gather body: all_to_all the (segment, block id)
    send lattice, block-gather the received local blocks, scatter them to
    their segments (each segment globally owned by exactly one lookup), and
    sum-combine — replicated or reduce-scattered."""
    blk = op.block_rows
    nseg = op.num_segments

    def body(table, roff, ints):
        recv = jax.lax.all_to_all(ints[0], axis, 0, 0)
        segs = recv[:, 0, :].reshape(-1)
        idxs = recv[:, 1, :].reshape(-1)
        valid = segs < nseg
        rows = _local_block_gather(table, idxs, blk, backend, interpret)
        rows = rows * valid[:, None, None].astype(rows.dtype)
        segc = jnp.minimum(segs, nseg - 1).astype(jnp.int32)
        out = jax.ops.segment_sum(rows, segc, num_segments=nseg)
        if replicate:
            return jax.lax.psum(out, axis)
        return _reduce_scatter(out, axis, "add", shards, seg_cap)

    return body


def sharded_call(body, mesh, axis: str, in_specs, out_specs):
    """jit(shard_map(body)) with the caller's explicit operand/output
    PartitionSpecs (the table is always ``P(axis, None)``, ``roff``
    replicated, buckets/send buffers leading-dim sharded; outputs
    replicated or — reduce-scattered — leading-dim sharded).  jit makes the
    per-capacity-bucket trace the retrace unit, mirroring the single-device
    executor."""
    return jax.jit(shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                             out_specs=out_specs, check_vma=False))


def csr_in_specs(axis: str, *, collective: bool, need_vals: bool) -> tuple:
    """(table, roff, …operands) specs of a CSR unit's shard_map call."""
    if collective:
        ops_ = (P(axis, None, None, None),)          # ints (S, S, 2, cap)
        if need_vals:
            ops_ += (P(axis, None, None),)           # vals (S, S, cap)
    else:
        ops_ = (P(axis, None), P(axis, None))        # ptrs, idxs
        if need_vals:
            ops_ += (P(axis, None),)
    return (P(axis, None), P(None)) + ops_


def gather_in_specs(axis: str, *, collective: bool) -> tuple:
    if collective:
        return (P(axis, None), P(None), P(axis, None, None, None))
    return (P(axis, None), P(None), P(axis, None), P(axis, None))


def pooled_out_specs(axis: str, ndim: int, *, replicate: bool):
    """Replicated pooled output, or the reduce-scattered layout where each
    shard holds its contiguous segment slice (leading dim sharded)."""
    if replicate:
        return P(*(None,) * ndim)
    return P(axis, *(None,) * (ndim - 1))
