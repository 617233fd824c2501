"""The one traffic generator: it reads a mix's data file
(``chipbench/traffic/<mix>.json``) and makes the cell's work from the seed.

``kind`` in the file picks what is made:

- ``pooled_lookups``: a pool of multi-hot batches, one bag of the table's
  fixed size per sample and table.  Keys follow the paper's locality
  classes (section 8.1): Zipf with exponent ``alpha`` over a table's rows
  (0 is uniform), ranks mapped to rows by a random permutation per table.
- ``lm_requests``: a pool of requests with log-normal prompt and output
  lengths.  The lengths and their order are the same for every seed, and
  the order is a low-discrepancy one, so that any run of consecutive
  requests, such as those one window serves, covers both distributions
  evenly.  The token ids are uniform over the vocabulary, from the seed.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *map(int, tags)])


class ZipfKeys:
    """Keys of one table: rank ``r`` (1-based) drawn with weight
    ``r ** -alpha``, mapped to a row by a fixed permutation."""

    def __init__(self, rows: int, alpha: float, rng: np.random.Generator):
        self.rows = rows
        self.rng = rng
        self.cdf = None
        if alpha > 0:
            w = np.arange(1, rows + 1, dtype=np.float64) ** -alpha
            self.cdf = np.cumsum(w)
            self.cdf /= self.cdf[-1]
        self.perm = rng.permutation(rows).astype(np.int32)

    def draw(self, n: int) -> np.ndarray:
        if self.cdf is None:
            return self.rng.integers(0, self.rows, n, dtype=np.int32)
        ranks = np.searchsorted(self.cdf, self.rng.random(n), side="right")
        return self.perm[np.minimum(ranks, self.rows - 1)]


def pooled_batches(rows: list, bags: list, batch: int, traffic: dict,
                   seed: int) -> list:
    """``traffic["pool"]`` batches, each ``{table index: (ptrs, idxs)}``."""
    assert traffic["kind"] == "pooled_lookups", traffic["kind"]
    pool = int(traffic["pool"])
    out = [dict() for _ in range(pool)]
    for t, (n, bag) in enumerate(zip(rows, bags)):
        keys = ZipfKeys(n, float(traffic["alpha"]), _rng(seed, 1, t))
        ptrs = (np.arange(batch + 1) * bag).astype(np.int32)
        idxs = keys.draw(pool * batch * bag).reshape(pool, batch * bag)
        for b in range(pool):
            out[b][t] = (ptrs, np.ascontiguousarray(idxs[b]))
    return out


def radical_inverse(k: int, base: int) -> float:
    """Point ``k`` (from 1) of the van der Corput sequence in ``base``."""
    f, r = 1.0, 0.0
    while k:
        f /= base
        r += f * (k % base)
        k //= base
    return r


def lognormal_lengths(spec: dict, base: int, n: int) -> np.ndarray:
    """``n`` lengths at the log-normal quantiles (median and sigma of
    ``spec``) of the van der Corput points in ``base``, clipped to
    [min, max]."""
    nd = NormalDist(math.log(spec["median"]), spec["sigma"])
    q = [math.exp(nd.inv_cdf(radical_inverse(k + 1, base)))
         for k in range(n)]
    return np.clip(np.rint(q), spec["min"], spec["max"]).astype(np.int64)


def lm_requests(traffic: dict, vocab: int, seed: int) -> list:
    """``traffic["pool"]`` requests ``(prompt int32 array, max_new)``.

    Prompt and output quantiles are the Halton points in bases 2 and 3,
    so the lengths pair independently and every stretch of the pool is
    spread over both distributions.  The seed draws only the token ids:
    in the closed loop, the order of lengths sets which prefills stall
    which decodes, and a seed-drawn order moves a window's tokens/s by
    5-9% (PERF.md, section 6)."""
    assert traffic["kind"] == "lm_requests", traffic["kind"]
    n = int(traffic["pool"])
    prompts = lognormal_lengths(traffic["prompt"], 2, n)
    outputs = lognormal_lengths(traffic["output"], 3, n)
    rng = _rng(seed, 2)
    return [(rng.integers(0, vocab, int(p), dtype=np.int32), int(o))
            for p, o in zip(prompts, outputs)]
