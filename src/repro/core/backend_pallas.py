"""DLC → Pallas code generation (the paper's `tmu` dialect stage, for TPU).

The optimized DLC program is erased into a :class:`KernelPlan` — the queue
machinery becomes a DMA schedule (DESIGN.md §2) — and the plan parameterizes
the generic DAE kernel templates in :mod:`repro.kernels`:

=====================  =====================================================
DLC/opt property        KernelPlan effect
=====================  =====================================================
vectorized (vlen)       column tile = round_up(vlen, 128) lanes
bufferized              whole-row DMA per lookup (one block per table row);
                        without it the kernel walks column tiles (more grid
                        steps → more DMA descriptors ≙ queue traffic)
queue_aligned           rows padded to the lane tile; output addressed from
                        scalar-prefetched ptrs, no row-id marshaling
store_streams           pure-copy kernel (block_gather) — VPU bypassed
=====================  =====================================================

Un-vectorized (O0) programs have no sensible TPU realization — a 1-lane VPU
op does not exist — so O0/O1 differences below the lane width are modeled by
the cost model, and the Pallas backend refuses plans narrower than a lane.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from .cost_model import lane_tile
from .ops import EmbeddingOp
from .pipeline import (CompileResult, ProgramCompileResult, opt_level_index)
from .passes import fuse_inputs, split_outputs


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    kind: str
    col_tile: int           # lane-tile of each DMA (queue "chunk")
    whole_row_dma: bool     # bufferization: one DMA per embedding row
    aligned: bool           # queue alignment: padded rows, no id marshaling
    store_stream: bool      # §7.4 pure-copy path
    num_buffers: int = 2    # DMA pipeline depth (the queue depth)
    num_tables: int = 1     # >1: batched multi-table plan (stacked table +
                            # scalar-prefetched per-segment base stream)

    @property
    def vmem_bytes_per_buffer(self) -> int:
        return self.col_tile * 4 * self.num_buffers

    @property
    def batched(self) -> bool:
        return self.num_tables > 1


def make_plan(res: CompileResult) -> KernelPlan:
    opt = res.opt
    vlen = opt.get("vlen") or 0
    if vlen and vlen < 128:
        vlen = 128  # TPU lane width floor (see module docstring)
    col_tile = lane_tile(res.op.emb_len, vlen)
    return KernelPlan(
        kind=res.op.kind,
        col_tile=col_tile,
        whole_row_dma=bool(opt.get("bufferized")),
        aligned=bool(opt.get("queue_aligned")),
        store_stream=bool(opt.get("store_streams")),
        num_tables=res.op.num_tables,
    )


def _run(aot, name, fn, static: dict, *args, **kw):
    """Dispatch one kernel launch: the plain jit wrapper, or — when the
    caller holds an :class:`~repro.core.artifact.AotCache` — the
    AOT-compiled executable (deserialized from the serving artifact or
    lowered once).  ``fn`` must be the underlying jit object (the public
    :mod:`repro.kernels.ops` wrappers are plain functions, no ``lower``)."""
    if aot is None:
        return fn(*args, **kw, **static)
    return aot.call(name, fn, static, *args, **kw)


def execute(res: CompileResult, inputs: dict,
            max_lookups: Optional[int] = None, aot=None):
    """Run the compiled op through the Pallas DAE kernels.

    ``max_lookups`` (the kernel's static lookup-slot grid extent) is derived
    from ``ptrs`` when absent — a host read of the offsets.  Steady-state
    callers (:mod:`repro.core.executor`) pass a precomputed *bucketed* value
    so device-resident ``ptrs`` are never synced back to the host and ragged
    batches reuse one jit specialization per bucket.
    """
    op = res.op
    plan = make_plan(res)
    interp = kops.default_interpret()
    if op.kind == "gather":
        assert plan.store_stream or opt_level_index(res.opt_level) < 3
        idxs = jnp.asarray(inputs["idxs"])
        if plan.batched and "roff" in inputs:
            # table-offset stream: rebase is scalar index math ahead of DMA
            idxs = idxs + jnp.asarray(inputs["roff"], jnp.int32)
        return _run(aot, "block_gather_pallas", kops.block_gather_pallas,
                    {"block_rows": op.block_rows, "interpret": interp},
                    jnp.asarray(inputs["table"]), idxs)
    if op.kind == "fusedmm":
        ptrs = _ptrs_of(op, inputs)
        if max_lookups is None:
            max_lookups = kops.max_lookups_of(np.asarray(ptrs))
        return _run(aot, "fusedmm_pallas", kops.fusedmm_pallas,
                    {"num_segments": op.num_segments,
                     "max_lookups": max_lookups, "interpret": interp},
                    jnp.asarray(inputs["x"]), jnp.asarray(ptrs),
                    jnp.asarray(inputs["idxs"]))
    if op.kind == "kg":
        ptrs = np.arange(op.num_segments + 1, dtype=np.int32)
        w = inputs["vals"]
        max_lookups = 1
    else:
        ptrs = _ptrs_of(op, inputs)
        w = inputs.get("vals")
    if max_lookups is None:
        max_lookups = kops.max_lookups_of(np.asarray(ptrs))
    col_tile = plan.col_tile if plan.whole_row_dma else 128
    seg_base = None
    if plan.batched and "roff" in inputs:
        seg_base = jnp.asarray(inputs["roff"], jnp.int32)
    return _run(aot, "sls_pallas", kops.sls_pallas,
                {"num_segments": op.num_segments,
                 "max_lookups": max_lookups,
                 "add_op": op.semiring.add, "mul_op": op.semiring.mul,
                 "col_tile": col_tile, "interpret": interp},
                jnp.asarray(inputs["table"]), jnp.asarray(ptrs),
                jnp.asarray(inputs["idxs"]),
                None if w is None else jnp.asarray(w),
                seg_base=seg_base)


def execute_program(pres: ProgramCompileResult, inputs: dict) -> dict:
    """Run a compiled program on the Pallas backend.

    ``inputs`` maps op name -> concrete inputs.  Fused units execute ONE
    batched kernel launch over the stacked table (one scalar-prefetch access
    stream instead of per-table dispatches) and split the output rows back
    per member op.
    """
    outs: dict = {}
    for unit in pres.units:
        if unit.group is None:
            outs[unit.names[0]] = execute(unit.result,
                                          inputs[unit.names[0]])
        else:
            fused = execute(unit.result, fuse_inputs(unit.group, inputs))
            outs.update(split_outputs(unit.group, fused))
    return outs


def _ptrs_of(op: EmbeddingOp, inputs: dict):
    """CSR offsets from either index format (lengths → cumulative sum).
    Already-device arrays pass through untouched (no host round trip)."""
    if op.index_format == "lengths" and "ptrs" not in inputs:
        ptrs = np.zeros(op.num_segments + 1, np.int32)
        np.cumsum(inputs["lens"], out=ptrs[1:])
        return ptrs
    ptrs = inputs["ptrs"]
    return ptrs if isinstance(ptrs, jnp.ndarray) else np.asarray(ptrs)
