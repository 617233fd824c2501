"""Public jit'd wrappers for the Pallas kernel layer.

Kernels run compiled for the chip when JAX's backend is a TPU and under the
Pallas interpreter everywhere else (``default_interpret``); an explicit
``interpret`` is for tests that pin the interpreter.
"""
from __future__ import annotations

import jax

from .sls import (sls_pallas, max_lookups_of, lookup_capacity, grid_capacity,
                  exchange_capacity)
from .gather import block_gather_pallas
from .fusedmm import fusedmm_pallas
from .flash_attention import flash_attention
from . import ref


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def sls(table, ptrs, idxs, weights=None, *, num_segments, max_lookups,
        add_op="add", mul_op="mul", col_tile=128, interpret=None,
        seg_base=None):
    return sls_pallas(table, ptrs, idxs, weights,
                      num_segments=num_segments, max_lookups=max_lookups,
                      add_op=add_op, mul_op=mul_op, col_tile=col_tile,
                      seg_base=seg_base,
                      interpret=default_interpret() if interpret is None
                      else interpret)


def block_gather(table, idxs, *, block_rows=1, interpret=None):
    return block_gather_pallas(
        table, idxs, block_rows=block_rows,
        interpret=default_interpret() if interpret is None else interpret)


def fusedmm(x, ptrs, idxs, *, num_segments, max_lookups, fn="identity",
            interpret=None):
    return fusedmm_pallas(
        x, ptrs, idxs, num_segments=num_segments, max_lookups=max_lookups,
        fn=fn,
        interpret=default_interpret() if interpret is None else interpret)


def attention(q, k, v, *, causal=True, block_q=128, block_k=128,
              interpret=None):
    return flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=default_interpret() if interpret is None else interpret)


__all__ = ["sls", "block_gather", "fusedmm", "attention", "ref",
           "max_lookups_of", "lookup_capacity", "grid_capacity",
           "exchange_capacity", "default_interpret"]
