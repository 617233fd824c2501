"""DAE-style SLS / EmbeddingBag Pallas TPU kernel.

TPU-native realization of the Ember-compiled DLC program (DESIGN.md §2):

* **access unit** ≙ the scalar core: the CSR ``ptrs``/``idxs`` arrays are
  scalar-prefetched into SMEM, and the kernel computes *which table row to
  DMA next* and issues that copy ``DEPTH`` lookups ahead of compute —
  running ahead exactly like the TMU traversal engine;
* **queues** ≙ the :class:`~repro.kernels.rowdma.RowRing` of VMEM row
  buffers: while the VPU reduces lookup ``q``, the copies of the next
  lookups are in flight;
* **execute unit** ≙ the kernel body (vector ⊕/⊗ on 8×128 vregs).

The table stays in HBM and only the rows a bag touches are copied.  The
grid is ``(segment tiles, column tiles)``: one step owns ``seg_tile``
consecutive segments — a sublane-aligned output block — whose lookups are
contiguous in CSR order, and streams them through the ring with no padded
grid slots.  The compiler's KernelPlan chooses the column tile (``vlen`` →
lane tile; without bufferization the kernel walks 128-lane column tiles,
one DMA descriptor per tile ≙ more queue traffic).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .rowdma import (DEPTH, RowRing, csr_chunks, local_segment, pad_ptrs,
                     place_row, ring_scratch, row_granule, sublane_rows)

_INIT = {"add": 0.0, "max": -jnp.inf, "min": jnp.inf}
_COMBINE = {"add": jnp.add, "max": jnp.maximum, "min": jnp.minimum}


def _sls_kernel(ptrs, idxs, seg_base, table, weights, out, buf, sems, *,
                add_op, mul_op, weighted, seg_tile, col_tile, granule):
    """One grid step = segments ``[s0, s0+seg_tile)`` × one column tile."""
    s0 = pl.program_id(0) * seg_tile
    ring = RowRing(table, buf, sems, granule=granule, col_tile=col_tile,
                   col=pl.multiple_of(pl.program_id(1) * col_tile, col_tile))
    p0 = ptrs[s0]
    total = ptrs[s0 + seg_tile] - p0
    last_base = seg_base.shape[0] - 1

    def row_in(seg, q):
        # fused multi-table rebase onto the stacked table (§ program fusion)
        return idxs[p0 + q] + seg_base[jnp.minimum(seg, last_base)]

    def row_of(q):                  # a lookup of a segment not yet reached
        return row_in(s0 + local_segment(ptrs, s0, p0 + q, seg_tile), q)

    def prime(q, carry):                        # fill the queue
        @pl.when(q < total)
        def _start():
            ring.start(q, row_of(q))
        return carry

    jax.lax.fori_loop(0, DEPTH, prime, 0)

    def segment(k, tile):
        def lookup(q, acc):
            slot = q % DEPTH
            ring.wait(slot)
            row = ring.read(slot, row_in(s0 + k, q))
            if weighted:
                w = weights[p0 + q]
                row = row * w if mul_op == "mul" else row + w
            acc = _COMBINE[add_op](acc, row)

            @pl.when(q + DEPTH < total)         # refill the freed slot
            def _next():
                ring.start(slot, row_of(q + DEPTH))
            return acc

        beg = ptrs[s0 + k] - p0
        end = ptrs[s0 + k + 1] - p0
        acc = jax.lax.fori_loop(
            beg, end, lookup,
            jnp.full((1, col_tile), _INIT[add_op], jnp.float32))
        if add_op != "add":
            # SLS convention: empty segments produce 0 even for max/min
            acc = jnp.where(end > beg, acc, 0.0)
        return place_row(tile, k, acc)

    out[...] = jax.lax.fori_loop(
        0, seg_tile, segment,
        jnp.zeros((seg_tile, col_tile), jnp.float32)).astype(out.dtype)


def _sls_launch(table, ptrs, idxs, seg_base, weights, *, num_segments,
                add_op, mul_op, weighted, col_tile, granule, interpret):
    seg_tile = sublane_rows(table.dtype)
    ptrs, padded = pad_ptrs(ptrs, num_segments, seg_tile)
    kernel = functools.partial(
        _sls_kernel, add_op=add_op, mul_op=mul_op, weighted=weighted,
        seg_tile=seg_tile, col_tile=col_tile, granule=granule)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(padded // seg_tile, table.shape[1] // col_tile),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),       # table stays in HBM
                pl.BlockSpec(memory_space=pltpu.SMEM),   # weights (scalar)
            ],
            out_specs=pl.BlockSpec((seg_tile, col_tile),
                                   lambda s, c, *_: (s, c)),
            scratch_shapes=ring_scratch(granule, col_tile, table.dtype),
        ),
        out_shape=jax.ShapeDtypeStruct((padded, table.shape[1]),
                                       table.dtype),
        interpret=interpret,
    )(ptrs, idxs, seg_base, table, weights)
    return out[:num_segments]


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "max_lookups", "add_op", "mul_op",
                     "col_tile", "interpret"))
def sls_pallas(table, ptrs, idxs, weights=None, *, num_segments: int,
               max_lookups: int, add_op: str = "add", mul_op: str = "mul",
               col_tile: int = 128, interpret: bool = False, seg_base=None):
    """Compiler entry point (see `repro.core.backend_pallas.KernelPlan`).

    table     (N, E)   embedding table (HBM resident, never copied)
    ptrs      (B+1,)   CSR segment offsets  — scalar-prefetched
    idxs      (nnz,)   row indices          — scalar-prefetched
    weights   (nnz,)   optional per-lookup scale (GNN edge values)
    seg_base  (B,)     optional per-segment table-row base — the fused
                       multi-table program's table-offset stream, applied
                       on the scalar core before each row DMA
    max_lookups        the bucketed densest bag: sizes the SMEM window of a
                       step split into chunks (``rowdma.csr_chunks``)
    """
    emb_len = table.shape[1]
    if emb_len % col_tile:          # column tiles must divide the row
        col_tile = emb_len
    weighted = weights is not None
    weights = (weights.astype(jnp.float32) if weighted
               else jnp.zeros((1,), jnp.float32))
    if idxs.shape[0] == 0:        # degenerate all-empty batch
        idxs = jnp.zeros((1,), jnp.int32)
    seg_base = (jnp.zeros((1,), jnp.int32) if seg_base is None
                else jnp.asarray(seg_base, jnp.int32))
    launch = functools.partial(
        _sls_launch, table, add_op=add_op, mul_op=mul_op, weighted=weighted,
        col_tile=col_tile, interpret=interpret,
        granule=row_granule(table.dtype, emb_len, interpret))
    outs = [launch(p, i, b, w[0] if weighted else weights, num_segments=n)
            for _, n, p, i, w, (b,) in csr_chunks(
                jnp.asarray(ptrs, jnp.int32), idxs,
                (weights,) if weighted else (), (seg_base,),
                num_segments=num_segments, max_lookups=max_lookups,
                seg_tile=sublane_rows(table.dtype))]
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


def max_lookups_of(ptrs: np.ndarray) -> int:
    return int(np.diff(ptrs).max(initial=0)) or 1


# The shape-bucketing policy (pow-2 nnz, quarter-octave max_lookups, joint
# exchange buckets) lives in ONE canonical module — repro.core.capacity —
# carried by every compiled AccessPlan; re-exported here so kernel callers
# keep their historical import path.
from repro.core.capacity import (lookup_capacity, grid_capacity,  # noqa: E402
                                 exchange_capacity)
