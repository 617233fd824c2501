"""Row DMAs from an HBM-resident table — the access unit shared by the
SLS, gather and FusedMM kernels.

A table stays where it is (``memory_space=pl.ANY``); the scalar core copies
the rows a step touches into a ring of VMEM buffers, each guarded by its
own DMA semaphore, and the vector unit reads them as they land.  Nothing
copies or relays out the whole table per call.

How deep the ring is depends on the kernel.  The SLS row stream sizes its
queue from the ring's bytes (:func:`stream_depth`): as many slots as fit
``RING_BYTES``, so a 512-byte float32 row gets a deep queue and a 16-row
bf16 sublane group a shallow one; it never drains between output tiles
(``kernels/sls.py``).  The gather and FusedMM kernels keep a queue of
``DEPTH`` rows per grid step.

Two layout facts of the TPU shape this module:

* A DMA source must be whole tiles of the table's HBM layout.  A 32-bit
  table exactly one lane tile (128) wide is laid out one row per tile, so a
  single row is one DMA.  Every other table is tiled in sublane groups
  (8 rows of 32-bit, 16 of 16-bit), so the ring copies the aligned group
  holding the row and the execute side selects the row from it.
* The scalar-prefetched CSR streams live in SMEM, which holds 1 MiB.  A step
  whose streams do not fit is split into consecutive segment chunks, each
  one kernel launch whose ``idxs`` window is sized by the chunk's lookup
  capacity (segments × bucketed ``max_lookups``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: row copies in flight in the gather and FusedMM rings, and the fewest
#: the SLS stream keeps
DEPTH = 8
#: VMEM bytes of the SLS stream's ring; its depth is the slots that fit
RING_BYTES = 1 << 15
#: SMEM bytes one launch may spend on scalar-prefetched streams (of 1 MiB;
#: the rest is the compiler's own)
SMEM_BUDGET = 3 << 18


def sublane_rows(dtype) -> int:
    """Rows of one sublane tile: 8 of 32-bit, 16 of 16-bit, 32 of 8-bit."""
    return 8 * max(1, 4 // np.dtype(dtype).itemsize)


def row_granule(dtype, width: int, interpret: bool) -> int:
    """Rows per DMA to fetch one table row (see the module docstring).
    The interpreter has no tiled layout and copies single rows."""
    if interpret or (np.dtype(dtype).itemsize == 4 and width == 128):
        return 1
    return sublane_rows(dtype)


def slot_bytes(granule: int, col_tile: int, dtype) -> int:
    """VMEM bytes of one ring slot: ``granule`` rows of ``col_tile`` lanes,
    padded to whole 128-lane tiles as VMEM lays them out."""
    return granule * -(-col_tile // 128) * 128 * np.dtype(dtype).itemsize


def stream_depth(granule: int, col_tile: int, dtype) -> int:
    """Slots of the SLS ring: the largest power of two whose slots fit
    ``RING_BYTES`` (a power of two, so a slot is a mask of the lookup's
    position), and never fewer than ``DEPTH``."""
    fit = RING_BYTES // slot_bytes(granule, col_tile, dtype)
    return max(DEPTH, 1 << max(fit.bit_length() - 1, 0))


def ring_scratch(granule: int, col_tile: int, dtype, depth: int = DEPTH):
    return [pltpu.VMEM((depth, granule, col_tile), dtype),
            pltpu.SemaphoreType.DMA((depth,))]


class RowRing:
    """VMEM slots, each filled by one row DMA from ``table``."""

    def __init__(self, table, buf, sems, *, granule: int, col, col_tile: int):
        self.table, self.buf, self.sems = table, buf, sems
        self.granule, self.col, self.col_tile = granule, col, col_tile

    def _copy(self, slot, row):
        g = self.granule
        if g > 1:
            row = pl.multiple_of((row // g) * g, g)
        return pltpu.make_async_copy(
            self.table.at[pl.ds(row, g), pl.ds(self.col, self.col_tile)],
            self.buf.at[slot], self.sems.at[slot])

    def start(self, slot, row) -> None:
        self._copy(slot, row).start()

    def wait(self, slot) -> None:
        self._copy(slot, 0).wait()

    def read(self, slot, row):
        """The landed row as ``(1, col_tile)`` float32."""
        tile = self.buf[slot].astype(jnp.float32)
        if self.granule == 1:
            return tile
        return take_row(tile, row % self.granule)


def place_row(tile, k, row):
    """``tile`` with its row ``k`` (dynamic) replaced by ``row`` — a select,
    not a store at a dynamic sublane offset."""
    hit = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0) == k
    return jnp.where(hit, row, tile)


def take_row(tile, k):
    """Row ``k`` (dynamic) of a ``(rows, lanes)`` tile as ``(1, lanes)``."""
    hit = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0) == k
    return jnp.sum(jnp.where(hit, tile, 0.0), axis=0, keepdims=True)


def pad_ptrs(ptrs, num_segments: int, seg_tile: int):
    """CSR offsets extended to a whole number of segment tiles; the extra
    segments are empty (their offsets repeat the last one)."""
    padded = -(-num_segments // seg_tile) * seg_tile
    ptrs = ptrs[:num_segments + 1]
    if padded == num_segments:
        return ptrs, padded
    tail = jnp.broadcast_to(ptrs[num_segments], (padded - num_segments,))
    return jnp.concatenate([ptrs, tail]), padded


def csr_chunks(ptrs, idxs, per_lookup: tuple, per_segment: tuple, *,
               num_segments: int, max_lookups: int, seg_tile: int):
    """Split one CSR step into launches whose SMEM streams fit.

    Yields ``(seg_lo, nseg, ptrs, idxs, per_lookup, per_segment)`` per
    chunk, rebased so each chunk's offsets start at 0.  ``per_lookup``
    arrays run parallel to ``idxs``, ``per_segment`` arrays to the
    segments (a length-1 array is a broadcast scalar and passes whole).
    One chunk — the inputs untouched — whenever the whole step fits.
    """
    def smem(nseg, nnz):
        return 4 * (nseg + 1 + nnz * (1 + len(per_lookup))
                    + sum(nseg if a.shape[0] > 1 else 1
                          for a in per_segment))

    nnz = idxs.shape[0]
    if smem(num_segments, nnz) <= SMEM_BUDGET:
        yield 0, num_segments, ptrs, idxs, per_lookup, per_segment
        return
    # a chunk's window never needs more than the whole step's lookups
    cap_of = lambda nseg: min(nseg * max_lookups, nnz)
    chunk = seg_tile
    while chunk < num_segments and \
            smem(2 * chunk, cap_of(2 * chunk)) <= SMEM_BUDGET:
        chunk *= 2
    cap = cap_of(chunk)
    if smem(chunk, cap) > SMEM_BUDGET:
        raise ValueError(
            f"{seg_tile} segments of up to {max_lookups} lookups do not fit "
            f"the {SMEM_BUDGET}-byte SMEM budget of one launch")
    pad = lambda a: jnp.concatenate([a, jnp.zeros((cap,), a.dtype)])
    idxs = pad(idxs)
    per_lookup = tuple(pad(a) for a in per_lookup)
    for lo in range(0, num_segments, chunk):
        nseg = min(chunk, num_segments - lo)
        start = ptrs[lo]
        window = lambda a: jax.lax.dynamic_slice(a, (start,), (cap,))
        # a bag longer than max_lookups is cut at the chunk's capacity
        local = jnp.minimum(ptrs[lo:lo + nseg + 1] - start, cap)
        yield (lo, nseg, local, window(idxs),
               tuple(window(a) for a in per_lookup),
               tuple(a[lo:lo + nseg] if a.shape[0] > 1 else a
                     for a in per_segment))
