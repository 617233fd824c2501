"""DAE-style SLS / EmbeddingBag Pallas TPU kernel.

TPU-native realization of the Ember-compiled DLC program (DESIGN.md §2):

* **access unit** ≙ the scalar core: the CSR ``ptrs``/``idxs`` arrays are
  scalar-prefetched into SMEM, and the kernel computes *which table row to
  DMA next* and issues that copy a queue's depth of lookups ahead of
  compute — running ahead exactly like the TMU traversal engine.  An issue
  cursor carries the segment of the next row to issue, and finds the next
  one by stepping over the segments the row stream has passed;
* **queues** ≙ the :class:`~repro.kernels.rowdma.RowRing` of VMEM row
  buffers: while the VPU reduces lookup ``p``, the copies of the next
  lookups are in flight.  The queue is as deep as the slots that fit the
  ring's bytes (``rowdma.stream_depth``): 64 of a 512-byte float32 row, 8
  of a 16-row bf16 group (the gather and FusedMM kernels keep their
  8-deep ring, ``rowdma.DEPTH``);
* **execute unit** ≙ the kernel body (vector ⊕/⊗ on 8×128 vregs).

The table stays in HBM and only the rows a bag touches are copied.  The
grid walks column tiles; one step streams every lookup of the launch in
CSR order through the ring, which stays full from the first lookup to the
last: an output tile of ``seg_tile`` segments, once pooled, is copied out
to HBM from a two-slot staging buffer while the stream runs on.  Each
segment's rows are added in CSR order into a float32 accumulator.  The
compiler's KernelPlan chooses the column tile (``vlen`` → lane tile;
without bufferization the kernel walks 128-lane column tiles, one DMA
descriptor per tile ≙ more queue traffic).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .rowdma import (RowRing, csr_chunks, pad_ptrs, place_row, ring_scratch,
                     row_granule, stream_depth, sublane_rows)

_INIT = {"add": 0.0, "max": -jnp.inf, "min": jnp.inf}
_COMBINE = {"add": jnp.add, "max": jnp.maximum, "min": jnp.minimum}


def _sls_kernel(ptrs, idxs, seg_base, table, weights, out, buf, sems, stage,
                flushes, *, add_op, mul_op, weighted, seg_tile, col_tile,
                granule, depth, num_tiles):
    """One grid step = every segment of the launch × one column tile."""
    col = pl.multiple_of(pl.program_id(0) * col_tile, col_tile)
    ring = RowRing(table, buf, sems, granule=granule, col=col,
                   col_tile=col_tile)
    total = ptrs[num_tiles * seg_tile]
    last_base = seg_base.shape[0] - 1

    def row_at(seg, p):
        # fused multi-table rebase onto the stacked table (§ program fusion)
        return idxs[p] + seg_base[jnp.minimum(seg, last_base)]

    def issue(p, seg):
        """Start the copy of lookup ``p``, if there is one, and return the
        segment holding it (the last lookup's, past the end), walked to
        from ``seg``, the segment of an earlier lookup."""
        at = jnp.minimum(p, total - 1)
        seg = jax.lax.while_loop(lambda s: ptrs[s + 1] <= at,
                                 lambda s: s + 1, seg)

        @pl.when(p < total)
        def _start():
            ring.start(p & (depth - 1), row_at(seg, p))
        return seg

    def flush(slot, s0):
        return pltpu.make_async_copy(
            stage.at[slot], out.at[pl.ds(s0, seg_tile), pl.ds(col, col_tile)],
            flushes.at[slot])

    def tile(t, seg):
        s0 = pl.multiple_of(t * seg_tile, seg_tile)

        def segment(k, carry):
            rows, seg = carry

            def lookup(p, carry):
                acc, seg = carry
                slot = p & (depth - 1)
                ring.wait(slot)
                row = ring.read(slot, row_at(s0 + k, p) if granule > 1
                                else None)
                if weighted:
                    w = weights[p]
                    row = row * w if mul_op == "mul" else row + w
                return _COMBINE[add_op](acc, row), issue(p + depth, seg)

            beg, end = ptrs[s0 + k], ptrs[s0 + k + 1]
            acc, seg = jax.lax.fori_loop(
                beg, end, lookup,
                (jnp.full((1, col_tile), _INIT[add_op], jnp.float32), seg))
            if add_op != "add":
                # SLS convention: empty segments produce 0 even for max/min
                acc = jnp.where(end > beg, acc, 0.0)
            return place_row(rows, k, acc), seg

        rows, seg = jax.lax.fori_loop(
            0, seg_tile, segment,
            (jnp.zeros((seg_tile, col_tile), jnp.float32), seg))
        slot = t % 2

        @pl.when(t >= 2)                # the slot's last tile has landed
        def _free():
            flush(slot, 0).wait()
        stage[slot] = rows.astype(stage.dtype)
        flush(slot, s0).start()
        return seg

    # fill the queue, then stream the launch's lookups through it
    seg = jax.lax.fori_loop(0, jnp.minimum(depth, total), issue, 0)
    jax.lax.fori_loop(0, num_tiles, tile, seg)
    for t in range(max(num_tiles - 2, 0), num_tiles):
        flush(t % 2, 0).wait()


def _sls_launch(table, ptrs, idxs, seg_base, weights, *, num_segments,
                add_op, mul_op, weighted, col_tile, granule, interpret):
    seg_tile = sublane_rows(table.dtype)
    ptrs, padded = pad_ptrs(ptrs, num_segments, seg_tile)
    depth = stream_depth(granule, col_tile, table.dtype)
    kernel = functools.partial(
        _sls_kernel, add_op=add_op, mul_op=mul_op, weighted=weighted,
        seg_tile=seg_tile, col_tile=col_tile, granule=granule, depth=depth,
        num_tiles=padded // seg_tile)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(table.shape[1] // col_tile,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),       # table stays in HBM
                pl.BlockSpec(memory_space=pltpu.SMEM),   # weights (scalar)
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),  # tiles copied out
            scratch_shapes=ring_scratch(granule, col_tile, table.dtype,
                                        depth) + [
                pltpu.VMEM((2, seg_tile, col_tile), table.dtype),
                pltpu.SemaphoreType.DMA((2,))],
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
