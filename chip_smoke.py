"""Bring-up check: drive the Ember system's main paths once on a TPU.

    python chip_smoke.py             # one chip: pooled lookups, LM serving
    python chip_smoke.py --chips 4   # vocab-sharded executor on four chips

Phases, in one process (a TPU belongs to one process):

1. **Pooled lookups** — a DLRM-v2-width multi-table SLS program (8 tables
   of 2^20 x 128 float32 rows, 4 GiB) through ``executor_for`` on the
   Pallas backend: a few steps of batch 2048 with multi-hot Zipf bags,
   each compared in float32 with ``core.ops.reference``; the compiled
   kernel of a step must be a ``tpu_custom_call`` (no interpreter).
2. **LM serving** — stablelm-3b at full width (bf16, 32 layers, seeded
   random weights) behind ``DecodeServer`` as ``launch/serve.py`` builds
   it: 4 requests of 8 prompt tokens and 16 new tokens must all finish
   ``ok``; prefill + cached decode logits are compared with the uncached
   forward; the server's own embedding executor gathers token rows with
   the Pallas kernel, compared with the table.

``--chips 4`` runs only the vocab-sharded executor on a 4-wide ``model``
mesh against the replicated single-device executor on the same inputs.

Every phase prints its sizes, compile seconds, device memory and largest
error; the last line is ``{"ok": true, "device": {...}}``.  With no TPU
the script exits non-zero and prints no result: there is no CPU fallback.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

GiB = 1 << 30

# MLPerf Inference DLRM-v2: 128-wide rows, multi-hot bags of a fixed size
# per table (these eight are sizes of its 26 tables)
SLS_ROWS = 1 << 20
SLS_WIDTH = 128
SLS_BAGS = (3, 2, 6, 7, 8, 12, 27, 100)
SLS_BATCH = 2048
SLS_STEPS = 3
# the kernel pools each bag in f32 in CSR order, as the reference does
SLS_RTOL, SLS_ATOL = 1e-5, 1e-4

LM_ARCH = "stablelm-3b"
LM_REQUESTS, LM_PROMPT, LM_NEW, LM_SLOTS, LM_MAX_LEN = 4, 8, 16, 4, 128
# Cached decode and the uncached forward run the same bf16 weights through
# different reduction orders (per-token KV-cache attention vs one causal
# pass), so their bf16 hidden states differ by rounding that the residual
# stream carries (1.0-1.4% of the largest logit at 2-8 layers of this
# width on the CPU).  A wrong cache position, mask or rotary offset moves
# the logits by O(1), far outside this bound on the largest logit
# difference relative to the largest reference logit.
LM_REL_TOL = 0.1


def say(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling while active."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self._EVENTS:
            self.seconds += duration

    def take(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return round(s, 2)


def memory(devices=None) -> dict:
    """Per-device ``bytes_in_use`` and the process's ``peak_bytes_in_use``.
    The peak is never reset: a phase reports it beside the peak it found
    at its start, and only a rise is the phase's own."""
    devices = devices or jax.devices()[:1]
    stats = [d.memory_stats() or {} for d in devices]
    return {"bytes_in_use": [s.get("bytes_in_use", 0) for s in stats],
            "peak_bytes_in_use": [s.get("peak_bytes_in_use", 0)
                                  for s in stats]}


def release() -> None:
    """Drop executors and compiled programs once a phase has returned (and
    its arrays with it), so the next phase's device memory is its own."""
    from repro.core.executor import clear_executor_cache
    from repro.core.pipeline import clear_compile_cache
    clear_executor_cache()
    clear_compile_cache()
    jax.clear_caches()
    gc.collect()


# ---------------------------------------------------------------------------
# pooled lookups (DLRM-v2 width)
# ---------------------------------------------------------------------------

def sls_program(rows: int, width: int, bags: tuple, batch: int):
    from repro.core.ops import EmbeddingOp, EmbeddingProgram
    return EmbeddingProgram("dlrm-v2-sls", tuple(
        (f"t{i}", EmbeddingOp("sls", num_segments=batch, num_embeddings=rows,
                              emb_len=width, avg_lookups=bag))
        for i, bag in enumerate(bags)))


def sls_tables(program, seed: int) -> dict:
    """Seeded float32 tables, made on the device."""
    key = jax.random.PRNGKey(seed)
    return {name: jax.random.normal(jax.random.fold_in(key, i),
                                    (op.num_embeddings, op.emb_len),
                                    jnp.float32)
            for i, (name, op) in enumerate(program.ops)}


def sls_traffic(program, step: int) -> dict:
    """One step of multi-hot bags with Zipf keys (``data/locality.py``)."""
    from repro.data.locality import make_trace
    out = {}
    for i, (name, op) in enumerate(program.ops):
        bag = op.avg_lookups
        ptrs = (np.arange(op.num_segments + 1) * bag).astype(np.int32)
        idxs = make_trace(op.num_embeddings, op.num_segments * bag, "L1",
                          seed=1000 * step + i).astype(np.int32)
        out[name] = {"ptrs": ptrs, "idxs": idxs}
    return out


def sls_reference(program, tables: dict, traffic: dict) -> dict:
    """``core.ops.reference`` in float32 over the rows the step touches
    (fetched from the device; the reference indexes a compacted table)."""
    from repro.core.ops import reference
    out = {}
    for name, op in program.ops:
        idxs = traffic[name]["idxs"]
        rows, local = np.unique(idxs, return_inverse=True)
        table = np.asarray(tables[name][jnp.asarray(rows)])
        out[name] = reference(op, {"table": table,
                                   "ptrs": traffic[name]["ptrs"],
                                   "idxs": local.astype(np.int32)})
    return out


def step_inputs(tables: dict, traffic: dict) -> dict:
    return {n: {"table": tables[n], **traffic[n]} for n in tables}


def max_violation(got: dict, want: dict, rtol: float, atol: float):
    """Largest |got - want| and the largest ratio of it to the allowed
    ``atol + rtol * |want|`` (<= 1 passes)."""
    err = ratio = 0.0
    for n in want:
        g = np.asarray(got[n], np.float32)
        w = np.asarray(want[n], np.float32)
        assert g.shape == w.shape, (n, g.shape, w.shape)
        assert np.isfinite(g).all(), f"{n}: non-finite output"
        d = np.abs(g - w)
        err = max(err, float(d.max()))
        ratio = max(ratio, float((d / (atol + rtol * np.abs(w))).max()))
    return err, ratio


class KernelRecorder:
    """Stands in for the executor's AOT cache for one step: each kernel
    launch is lowered and compiled explicitly so its compiled text can be
    inspected, then run."""

    def __init__(self):
        self.texts = {}

    def call(self, name, fn, static, *args, **kw):
        exe = fn.lower(*args, **kw, **static).compile()
        self.texts[name] = exe.as_text()
        return exe(*args, **kw)


def phase_sls(clock: CompileClock, *, rows=SLS_ROWS, width=SLS_WIDTH,
              bags=SLS_BAGS, batch=SLS_BATCH, steps=SLS_STEPS, seed=0):
    from repro.core.executor import executor_for
    peak0 = memory()["peak_bytes_in_use"]
    program = sls_program(rows, width, bags, batch)
    tables = sls_tables(program, seed)
    table_bytes = sum(t.nbytes for t in tables.values())
    ex = executor_for(program)
    assert ex.backend == "pallas" and not ex.interpret, \
        "the executor must run compiled Pallas kernels on a TPU"
    worst_err = worst_ratio = 0.0
    step_s = []
    for step in range(steps):
        traffic = sls_traffic(program, step)
        ins = step_inputs(tables, traffic)
        if step == steps - 1:
            # the last step dispatches through a recorder that keeps the
            # compiled text of every kernel it launched
            ex.aot = rec = KernelRecorder()
        t0 = time.perf_counter()
        got = ex.step(ins)
        jax.block_until_ready(got)
        step_s.append(time.perf_counter() - t0)
        ex.aot = None
        err, ratio = max_violation(
            got, sls_reference(program, tables, traffic), SLS_RTOL, SLS_ATOL)
        worst_err, worst_ratio = max(worst_err, err), max(worst_ratio, ratio)
    assert rec.texts, "no kernel launch was recorded"
    for name, text in rec.texts.items():
        assert "tpu_custom_call" in text, \
            f"{name}: compiled step holds no tpu_custom_call"
    say("sls", tables=f"{len(bags)}x{rows}x{width}xf32",
        table_gib=round(table_bytes / GiB, 3), batch=batch,
        bags=list(bags), lookups_per_step=batch * sum(bags), steps=steps,
        units=len(ex.compiled.units), kernels=sorted(rec.texts),
        compile_s=clock.take(), first_step_s=round(step_s[0], 3),
        last_step_s=round(step_s[-1], 3), **memory(),
        peak_before_phase=peak0,
        max_abs_err=worst_err, tol=f"rtol={SLS_RTOL},atol={SLS_ATOL}")
    assert worst_ratio <= 1.0, \
        f"pooled lookups differ from the reference by {worst_err}"


# ---------------------------------------------------------------------------
# LM serving at full width
# ---------------------------------------------------------------------------

def lm_cached_vs_uncached(lm, params, prompt: np.ndarray, new: int,
                          max_len: int):
    """Logits of prefill + greedy cached decode, and the same positions
    from ``LM.forward`` (no cache) projected through the output head in
    float32 at the highest matmul precision."""
    cfg = lm.cfg
    wave = jax.jit(lm.wave_step, donate_argnums=(3,))
    caches = lm.init_caches(1, max_len)
    logits, caches = wave(params, jnp.asarray(prompt[None]),
                          jnp.asarray([prompt.size], jnp.int32), caches)
    cached, seq = [logits[0, 0]], list(prompt)
    for _ in range(new - 1):
        tok = int(jnp.argmax(logits[0, 0]))
        seq.append(tok)
        logits, caches = wave(params, jnp.asarray([[tok]], jnp.int32),
                              jnp.asarray([1], jnp.int32), caches)
        cached.append(logits[0, 0])
    cached = np.stack([np.asarray(c, np.float32) for c in cached])
    del caches

    @jax.jit
    def uncached(params, tokens):
        with jax.default_matmul_precision("highest"):
            h, _ = lm.forward(params, {"tokens": tokens})
            head = params["embed"].astype(jnp.float32)
            return (h.astype(jnp.float32) @ head.T)[..., :cfg.vocab_size]

    ref = np.asarray(uncached(params, jnp.asarray([seq], jnp.int32))[0])
    return cached, ref[prompt.size - 1:]


def phase_lm(clock: CompileClock, *, cfg=None, seed=0):
    from repro.configs import get_config
    from repro.models import LM
    from repro.runtime.server import DecodeServer, Request
    peak0 = memory()["peak_bytes_in_use"]
    cfg = cfg or get_config(LM_ARCH)
    lm = LM(cfg)
    params = jax.jit(lm.init)(jax.random.PRNGKey(seed))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    srv = DecodeServer(lm, params, batch_slots=LM_SLOTS, max_len=LM_MAX_LEN,
                       prefill_chunk=LM_PROMPT)
    rng = np.random.default_rng(seed)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, LM_PROMPT)
                    .astype(np.int32), max_new_tokens=LM_NEW)
            for _ in range(LM_REQUESTS)]
    t0 = time.perf_counter()
    for r in reqs:
        srv.submit(r)
    iters = srv.run_until_drained()
    serve_s = time.perf_counter() - t0
    statuses = [r.status for r in reqs]
    assert all(s == "ok" for s in statuses), statuses
    assert all(len(r.out) == LM_NEW for r in reqs), [len(r.out) for r in reqs]
    serve_compile_s = clock.take()

    # the model's Ember embedding program: token rows through the Pallas
    # gather (the server itself embeds inside its jitted wave)
    ex = lm.embedding_executor(LM_SLOTS, 1)
    assert ex.backend == "pallas" and not ex.interpret
    ids = np.array([r.prompt[0] for r in reqs], np.int32)
    gathered = ex.step({n: {"table": params["embed"], "idxs": ids}
                        for n, _ in ex.compiled.program.ops})
    want = np.asarray(params["embed"][jnp.asarray(ids)], np.float32)
    gather_err = max(float(np.abs(np.asarray(g, np.float32)[:, 0] - want)
                           .max()) for g in gathered.values())
    assert gather_err == 0.0, f"embedding gather differs by {gather_err}"

    cached, ref = lm_cached_vs_uncached(lm, params, reqs[0].prompt, LM_NEW,
                                        LM_MAX_LEN)
    assert np.isfinite(cached).all() and np.isfinite(ref).all()
    err = float(np.abs(cached - ref).max())
    rel = err / float(np.abs(ref).max())
    argmax_agree = float((cached.argmax(-1) == ref.argmax(-1)).mean())
    say("lm", arch=cfg.name, dtype=cfg.dtype, layers=cfg.num_layers,
        d_model=cfg.d_model, vocab=cfg.vocab_size, params=n_params,
        param_gib=round(param_bytes / GiB, 3), requests=LM_REQUESTS,
        prompt=LM_PROMPT, new_tokens=LM_NEW, statuses=statuses,
        serving_iterations=iters, serve_s=round(serve_s, 3),
        serve_compile_s=serve_compile_s, check_compile_s=clock.take(),
        **memory(), peak_before_phase=peak0, gather_max_err=gather_err, logits_max_abs_err=err,
        logits_rel_err=rel, argmax_agree=argmax_agree,
        tol=f"rel<={LM_REL_TOL}")
    assert rel <= LM_REL_TOL, \
        f"cached logits differ from the uncached forward by {rel:.4f}"


# ---------------------------------------------------------------------------
# --chips 4: vocab-sharded executor against the replicated one
# ---------------------------------------------------------------------------

def phase_sharded(clock: CompileClock, *, chips: int, rows=SLS_ROWS,
                  width=SLS_WIDTH, bags=SLS_BAGS, batch=SLS_BATCH,
                  steps=2, seed=0):
    from repro.core.executor import executor_for
    from repro.launch.mesh import make_host_mesh
    devices = jax.devices()
    assert len(devices) == chips, f"{len(devices)} devices, need {chips}"
    mesh = make_host_mesh(model_parallel=chips)
    program = sls_program(rows, width, bags, batch)
    tables = sls_tables(program, seed)
    before = memory(devices)["bytes_in_use"]
    shrd = executor_for(program, mesh=mesh)
    repl = executor_for(program)
    assert shrd.shards == chips and repl.shards == 1
    worst_diff = worst_ratio = 0.0
    for step in range(steps):
        ins = step_inputs(tables, sls_traffic(program, step))
        a, b = shrd.step(ins), repl.step(ins)
        err, ratio = max_violation(a, {n: np.asarray(v) for n, v in b.items()},
                                   SLS_RTOL, SLS_ATOL)
        worst_diff, worst_ratio = max(worst_diff, err), max(worst_ratio, ratio)
        if step == 0:
            held = [x - y for x, y in zip(
                memory(devices)["bytes_in_use"], before)]
    fused_s = [u.table for u in shrd._units if u.group is not None]
    fused_r = [u.table for u in repl._units if u.group is not None]
    stacked = sum(t.nbytes for t in fused_r)
    per_dev = {}
    for t in fused_s:
        for s in t.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) + \
                s.data.nbytes
    say("sharded", chips=chips, kind=devices[0].device_kind,
        tables=f"{len(bags)}x{rows}x{width}xf32", batch=batch, steps=steps,
        stacked_table_bytes=stacked, per_device_table_bytes=per_dev,
        bytes_held_after_first_step=held, compile_s=clock.take(),
        max_abs_diff_sharded_vs_replicated=worst_diff,
        tol=f"rtol={SLS_RTOL},atol={SLS_ATOL}")
    assert worst_ratio <= 1.0, \
        f"sharded and replicated outputs differ by {worst_diff}"
    assert sorted(per_dev) == sorted(d.id for d in devices), \
        f"stacked tables sit on devices {sorted(per_dev)}"
    assert all(v * chips == stacked for v in per_dev.values()), \
        f"per-device stacked bytes {per_dev} are not 1/{chips} of {stacked}"
    # every device holds its quarter (device 0 also the source tables
    # and the replicated executor's stack)
    assert all(h >= stacked // chips for h in held), held


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the vocab-sharded executor on a "
                         "4-wide model mesh against the replicated one")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); this check runs on the chip only",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    say("device", platform=dev.platform, kind=dev.device_kind,
        count=len(jax.devices()), jax=jax.__version__,
        compile_cache=enable_compile_cache())
    clock = CompileClock()
    phases = ([functools.partial(phase_sharded, chips=4)]
              if args.chips == 4 else [phase_sls, phase_lm])
    for phase in phases:
        phase(clock)
        release()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
