"""Plain reference of sum-pooled lookups (SLS): numpy, float32.

``pool(rows, ptrs, idxs)`` adds, for each segment ``s``, the rows
``idxs[ptrs[s]:ptrs[s+1]]`` of ``rows`` in order, in float32; an empty
segment pools to zeros.  ``rows`` holds only the table rows a batch
touches, and ``idxs`` index into it.

``round_rows(rows, "bfloat16")`` is the control: the same pooling over rows
stored one precision below the configuration's float32.
"""
from __future__ import annotations

import numpy as np


def pool(rows: np.ndarray, ptrs: np.ndarray, idxs: np.ndarray
         ) -> np.ndarray:
    rows = np.asarray(rows, np.float32)
    ptrs = np.asarray(ptrs, np.int64)
    segs = len(ptrs) - 1
    out = np.zeros((segs, rows.shape[1]), np.float32)
    if len(idxs) == 0:
        return out
    gathered = rows[np.asarray(idxs, np.int64)]
    nonempty = ptrs[1:] > ptrs[:-1]
    starts = ptrs[:-1][nonempty]
    out[nonempty] = np.add.reduceat(gathered, starts, axis=0,
                                    dtype=np.float32)
    return out


def round_rows(rows: np.ndarray, dtype: str) -> np.ndarray:
    """Rows rounded to ``dtype`` and back to float32 (the control)."""
    import ml_dtypes
    return np.asarray(rows, np.float32).astype(
        getattr(ml_dtypes, dtype)).astype(np.float32)


def max_rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest ``|got - want| / (1 + |want|)`` (inf if got is not finite
    or has another shape)."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float((np.abs(got - want) / (1.0 + np.abs(want))).max())
