"""Pooled multi-hot lookups (DLRM-style SLS) through the Ember executor.

The timed path is ``executor_for(program)`` and its
``ProgramExecutor.submit`` / ``StepHandle.result`` on one chip: the access
plan and marshaling on the host and the fused ``sls_pallas`` kernel over
the stacked tables.

Set-up makes the tables on the device in one jitted call from the seed,
draws a pool of batches from the seed, and runs every pool batch once so
that each shape the window meets is compiled and warm.  The window is a
closed loop that keeps the executor's own in-flight depth: before each
submit, the oldest batch in flight is waited on once ``depth`` are out.

``correct`` compares the pooled outputs of a sample of the window's
batches, drawn from the seed, with ``references/sls_numpy.py`` over rows
that are made again from the seed once the executor and its tables are
gone.
"""
from __future__ import annotations

import collections
import gc
import time
from pathlib import Path

import numpy as np

from chipbench import counts
from chipbench.generate import pooled_batches
from chipbench.harness import Check, load_module, memory_peak
from chipbench.peaks import peaks

#: batches of the window whose outputs are compared (positions drawn from
#: the seed among the first ``SAMPLE_FROM``)
SAMPLE, SAMPLE_FROM = 6, 48
#: largest |got - want| / (1 + |want|) of a pooled element; see PERF.md
#: for the readings it was set from
POOL_ERR_LIMIT = 1e-4
#: rows the reference fetches from a table come in multiples of this
GATHER_BUCKET = 1 << 16


def table_names(n: int) -> list:
    return [f"t{i:02d}" for i in range(n)]


def program_for(rows: list, bags: list, width: int, batch: int):
    from repro.core.ops import EmbeddingOp, EmbeddingProgram
    return EmbeddingProgram("dlrm-v2-sls", tuple(
        (name, EmbeddingOp("sls", num_segments=batch, num_embeddings=n,
                           emb_len=width, avg_lookups=bag))
        for name, n, bag in zip(table_names(len(rows)), rows, bags)))


def table_maker(rows: list, width: int):
    """One jitted call that makes every table from a key; table ``i`` is
    ``normal(fold_in(key, i), (rows[i], width))`` in float32, so one table
    can be made again alone (:func:`one_table`)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        return tuple(jax.random.normal(jax.random.fold_in(key, i),
                                       (n, width), jnp.float32)
                     for i, n in enumerate(rows))
    return make


def one_table(key, i: int, rows: int, width: int):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda k: jax.random.normal(jax.random.fold_in(k, i),
                                            (rows, width), jnp.float32))
    return f(key)


def shape_of(config: dict):
    rows = list(config["num_embeddings_per_feature"])
    bags = list(config["multi_hot_sizes"])
    assert len(rows) == len(bags)
    return rows, bags, int(config["embedding_dim"]), int(config["batch_size"])


def reference_outputs(key, rows, bags, width, batches: dict,
                      precision: str = "float32") -> dict:
    """``{position: {table: pooled (B, width)}}`` of the sampled batches
    from rows made again from the key, table by table."""
    ref = load_module(_REF)
    out = {j: {} for j in batches}
    for t, (n, name) in enumerate(zip(rows, table_names(len(rows)))):
        touched = np.unique(np.concatenate(
            [b[t][1] for b in batches.values()]))
        table = one_table(key, t, n, width)
        # fetch a bucketed count of rows, so that every seed gathers with
        # the same few shapes and finds them compiled
        rows_out = min(n, -(-len(touched) // GATHER_BUCKET) * GATHER_BUCKET)
        padded = np.zeros(rows_out, np.int32)
        padded[:len(touched)] = touched
        part = np.asarray(table[padded])[:len(touched)]
        del table
        if precision != "float32":
            part = ref.round_rows(part, precision)
        for j, b in batches.items():
            ptrs, idxs = b[t]
            out[j][name] = ref.pool(part, ptrs,
                                    np.searchsorted(touched, idxs))
    return out


_REF = Path(__file__).resolve().parent.parent / "references" / \
    "sls_numpy.py"


def build(run):
    """Set-up: program, tables, executor and the pool of batches."""
    from repro.core.executor import executor_for
    rows, bags, width, batch = shape_of(run.config)
    names = table_names(len(rows))
    program = program_for(rows, bags, width, batch)
    pool = pooled_batches(rows, bags, batch, run.traffic, run.seed)
    tables = dict(zip(names, table_maker(rows, width)(run.key())))
    ex = executor_for(program)
    inputs = [{name: {"table": tables[name], "ptrs": b[t][0],
                      "idxs": b[t][1]} for t, name in enumerate(names)}
              for b in pool]
    return ex, tables, pool, inputs


def window(run, ex, inputs: list, sample: set):
    """The closed loop.  Returns the batches' ``(position, pool index,
    t_submit, t_ready)``, the kept outputs and the failures."""
    spans = run.spans
    depth = ex.depth
    flight = collections.deque()
    done, kept, failed = [], {}, 0
    t_end = run.open_window() + run.seconds

    def finish(i, b, ts, h):
        nonlocal failed
        try:
            with spans("result"):
                outs = h.result()
        except Exception as e:          # a batch that fails is counted
            failed += 1
            print(f"batch {i} failed: {type(e).__name__}: {e}")
            return
        done.append((i, b, ts, time.perf_counter()))
        if i in sample:
            kept[i] = (b, outs)

    i = 0
    while True:
        now = time.perf_counter()
        run.poll(now)
        if now >= t_end:
            break
        if len(flight) >= depth:
            finish(*flight.popleft())
        b = i % len(inputs)
        with spans("submit"):
            ts = time.perf_counter()
            h = ex.submit(inputs[b])
        flight.append((i, b, ts, h))
        i += 1
    t_close = run.close_window()
    while flight:                       # late, not counted in the window
        finish(*flight.popleft())
    return done, kept, failed, i, t_close


def run(run) -> dict:
    from repro.core.executor import clear_executor_cache
    from repro.core.pipeline import clear_compile_cache
    import jax
    rows, bags, width, batch = shape_of(run.config)
    ex, tables, pool, inputs = build(run)
    with run.spans("warm"):
        for ins in inputs:              # every shape the window meets
            ex.step(ins)
    rng = run.rng(3)
    sample = set(rng.choice(SAMPLE_FROM, SAMPLE, replace=False).tolist())
    done, kept, failed, attempted, t_close = window(run, ex, inputs,
                                                    sample)
    peak = memory_peak(run.devices)
    got = {i: (b, {n: np.asarray(v) for n, v in outs.items()})
           for i, (b, outs) in kept.items()}
    del ex, tables, inputs, kept
    clear_executor_cache()
    clear_compile_cache()
    jax.clear_caches()
    gc.collect()

    # --- what the readers need, all counted from the pool's shapes
    row_bytes = width * 4
    per_batch = []
    for b in pool:
        least = sum(counts.sls_least_bytes(p, x, row_bytes, row_bytes)
                    for p, x in b.values())
        lookups = sum(len(x) for _, x in b.values())
        per_batch.append((lookups, least, counts.sls_flops(lookups, width)))
    pk = peaks(run.devices[0].device_kind)
    f = run.facts
    f["batches"] = [(ts, tr, *per_batch[b]) for _, b, ts, tr in done
                    if tr <= t_close]
    f["least_time_s"] = sum(
        max(fl / (run.chips * pk.flops), by / (run.chips * pk.hbm_bytes))
        for _, _, _, by, fl in f["batches"])
    f["submit_s"] = run.spans.durations("submit", f["t_open"], t_close)
    f["peaks"] = pk

    # --- correct: the sampled outputs against the reference
    by_pos = {i: pool[b] for i, (b, _) in got.items()}
    want = reference_outputs(run.key(), rows, bags, width, by_pos)
    err = worst_err(got, want)
    checks = [Check("pool_err", err, POOL_ERR_LIMIT),
              Check("batches_missing", float(SAMPLE - len(got)), 0)]
    out = {"attempted": attempted, "failed": failed, "checks": checks,
           "memory_peak_bytes": peak}
    if run.control:
        # the control: the reference over rows one precision lower, put in
        # the program's place
        low = reference_outputs(run.key(), rows, bags, width, by_pos,
                                precision="bfloat16")
        low_err = worst_err({i: (b, low[i]) for i, (b, _) in got.items()},
                            want)
        out["control_checks"] = [Check("pool_err", low_err, POOL_ERR_LIMIT),
                                 checks[1]]
    return out


def worst_err(got: dict, want: dict) -> float:
    """Largest relative error over the sampled batches' pooled outputs."""
    ref = load_module(_REF)
    return max((ref.max_rel_err(outs[n], want[i][n])
                for i, (_, outs) in got.items() for n in want[i]),
               default=float("inf"))
