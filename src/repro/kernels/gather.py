"""Block-sparse attention gather kernel (SpAttn, paper §2.2.2 / §7.4).

The emb-opt3 form of this operation has *zero* queue traffic: Ember's
store-stream optimization lets the access unit copy blocks straight from the
table to the output.  The TPU analogue is a pure DMA-copy kernel: the scalar
core (reading scalar-prefetched ``idxs``) drives row DMAs from the
HBM-resident table into the output block, and the body only places each
landed row — the VPU does no arithmetic, mirroring "bypass the core"
(DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import rowdma
from .rowdma import DEPTH, RowRing, ring_scratch, row_granule

#: gather slots (output blocks) per grid step
GATHER_TILE = 64


def _gather_kernel(idxs, table, out, buf, sems, *, block_rows, granule,
                   num_blocks, gtile):
    g0 = pl.program_id(0) * gtile
    count = jnp.minimum(gtile, num_blocks - g0) * block_rows
    ring = RowRing(table, buf, sems, granule=granule, col=0,
                   col_tile=table.shape[1])

    def row_of(q):
        return idxs[g0 + q // block_rows] * block_rows + q % block_rows

    for q in range(DEPTH):                      # fill the queue
        @pl.when(q < count)
        def _prime():
            ring.start(q, row_of(q))

    def copy(g, carry):
        for r in range(block_rows):
            q = g * block_rows + r
            slot = q % DEPTH
            ring.wait(slot)
            out[g, r:r + 1, :] = ring.read(slot, row_of(q)).astype(out.dtype)

            @pl.when(q + DEPTH < count)
            def _next():
                ring.start(slot, row_of(q + DEPTH))
        return carry

    jax.lax.fori_loop(0, count // block_rows, copy, 0)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def block_gather_pallas(table, idxs, *, block_rows: int = 1,
                        interpret: bool = False):
    """out[g, r, :] = table[idxs[g] * block_rows + r, :]

    table (N*block_rows, E) — HBM resident, never copied;
    idxs (G,) int32 — scalar-prefetched.
    """
    if idxs.shape[0] * 4 > rowdma.SMEM_BUDGET:     # split to fit SMEM
        step = rowdma.SMEM_BUDGET // 4
        return jnp.concatenate([
            block_gather_pallas(table, idxs[lo:lo + step],
                                block_rows=block_rows, interpret=interpret)
            for lo in range(0, idxs.shape[0], step)])
    emb_len = table.shape[1]
    num_blocks = idxs.shape[0]
    if num_blocks == 0:
        return jnp.zeros((0, block_rows, emb_len), table.dtype)
    gtile = min(GATHER_TILE, num_blocks)
    granule = row_granule(table.dtype, emb_len, interpret)
    return pl.pallas_call(
        functools.partial(_gather_kernel, block_rows=block_rows,
                          granule=granule, num_blocks=num_blocks,
                          gtile=gtile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(num_blocks, gtile),),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((gtile, block_rows, emb_len),
                                   lambda g, *_: (g, 0, 0)),
            scratch_shapes=ring_scratch(granule, emb_len, table.dtype),
        ),
        out_shape=jax.ShapeDtypeStruct((num_blocks, block_rows, emb_len),
                                       table.dtype),
        interpret=interpret,
    )(idxs, table)
