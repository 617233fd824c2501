"""FusedMM (SDDMM+SpMM) Pallas kernel — message-passing models (§2.2.3).

The bufferized DLC program for MP keeps *two* buffer streams (x[i,:] and
x[j,:]), computes the SDDMM dot on the execute unit, and reuses the buffered
x[j,:] for the SpMM accumulate — the workspace loop's second memory pass
disappears.  Here x[i,:] arrives as the step's VMEM block of destination
rows and x[j,:] through the :class:`~repro.kernels.rowdma.RowRing` from the
HBM-resident features; the body does the dot (VPU reduce) and scaled
accumulate without re-touching HBM, which is exactly the paper's
hand-optimized MP structure.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .rowdma import (DEPTH, RowRing, csr_chunks, pad_ptrs, place_row,
                     ring_scratch, row_granule, sublane_rows, take_row)


def _fusedmm_kernel(ptrs, idxs, xi, x, out, buf, sems, *, fn, seg_tile,
                    granule):
    s0 = pl.program_id(0) * seg_tile
    ring = RowRing(x, buf, sems, granule=granule, col=0,
                   col_tile=x.shape[1])
    p0 = ptrs[s0]
    total = ptrs[s0 + seg_tile] - p0

    for q in range(DEPTH):                      # fill the queue
        @pl.when(q < total)
        def _prime():
            ring.start(q, idxs[p0 + q])

    rows = xi[...].astype(jnp.float32)

    def segment(k, tile):
        a = take_row(rows, k)

        def edge(q, acc):
            slot = q % DEPTH
            ring.wait(slot)
            c = ring.read(slot, idxs[p0 + q])
            s = jnp.sum(a * c)              # SDDMM (buffered dot)
            if fn == "relu":
                s = jnp.maximum(s, 0.0)

            @pl.when(q + DEPTH < total)
            def _next():
                ring.start(slot, idxs[p0 + q + DEPTH])
            return acc + s * c              # SpMM from the same buffer

        acc = jax.lax.fori_loop(ptrs[s0 + k] - p0, ptrs[s0 + k + 1] - p0,
                                edge, jnp.zeros(a.shape, jnp.float32))
        return place_row(tile, k, acc)

    out[...] = jax.lax.fori_loop(0, seg_tile, segment,
                                 jnp.zeros_like(rows)).astype(out.dtype)


def _fusedmm_launch(x, ptrs, idxs, *, seg_lo, num_segments, fn, interpret):
    seg_tile = sublane_rows(x.dtype)
    assert seg_lo % seg_tile == 0, (seg_lo, seg_tile)
    ptrs, padded = pad_ptrs(ptrs, num_segments, seg_tile)
    granule = row_granule(x.dtype, x.shape[1], interpret)
    first = seg_lo // seg_tile
    out = pl.pallas_call(
        functools.partial(_fusedmm_kernel, fn=fn, seg_tile=seg_tile,
                          granule=granule),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(padded // seg_tile,),
            in_specs=[pl.BlockSpec((seg_tile, x.shape[1]),
                                   lambda s, *_: (first + s, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((seg_tile, x.shape[1]),
                                   lambda s, *_: (s, 0)),
            scratch_shapes=ring_scratch(granule, x.shape[1], x.dtype),
        ),
        out_shape=jax.ShapeDtypeStruct((padded, x.shape[1]), x.dtype),
        interpret=interpret,
    )(ptrs, idxs, x, x)
    return out[:num_segments]


@functools.partial(jax.jit, static_argnames=("num_segments", "max_lookups",
                                             "fn", "interpret"))
def fusedmm_pallas(x, ptrs, idxs, *, num_segments: int, max_lookups: int,
                   fn: str = "identity", interpret: bool = False):
    """out[i] = Σ_{p in ptrs[i]..ptrs[i+1]} f(<x[i], x[idxs[p]]>) · x[idxs[p]]

    x (N, E) node features (HBM resident), N >= num_segments."""
    if idxs.shape[0] == 0:
        idxs = jnp.zeros((1,), jnp.int32)
    outs = [_fusedmm_launch(x, p, i, seg_lo=lo, num_segments=n, fn=fn,
                            interpret=interpret)
            for lo, n, p, i, _, _ in csr_chunks(
                jnp.asarray(ptrs, jnp.int32), idxs, (), (),
                num_segments=num_segments, max_lookups=max_lookups,
                seg_tile=sublane_rows(x.dtype))]
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)
