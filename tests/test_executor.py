"""Steady-state ProgramExecutor: numerics vs the reference interpreters at
O0–O3, marshaling-cache reuse (zero re-stacking in steady state),
double-buffer correctness across ragged batch sequences, cost-model fusion
partitioning (budget + balance), extended fusion (kg degenerate CSR, mixed
weighted/unweighted upcast), and the bounded LRU compile cache."""
import numpy as np
import pytest

from repro.core import backend_pallas, cost_model
from repro.core.executor import (ProgramExecutor, clear_executor_cache,
                                 executor_cache_stats, executor_for)
from repro.core.ops import (EmbeddingOp, EmbeddingProgram, Semiring,
                            make_program_inputs, program_reference)
from repro.core.passes import fuse_program, partition_members
from repro.core.pipeline import (OPT_LEVELS, clear_compile_cache,
                                 compile_cache_stats, compile_program,
                                 run_program_interpreted,
                                 set_compile_cache_limit)


def _mixed_program():
    """Fused CSR group (weighted + unweighted + kg upcast), fused gather
    group with a shared table, and an unfusable singleton."""
    return EmbeddingProgram("mixed", (
        ("w", EmbeddingOp("sls", 5, 9, 8, avg_lookups=3, weighted=True)),
        ("u", EmbeddingOp("sls", 4, 7, 8, avg_lookups=2)),
        ("k", EmbeddingOp("kg", 6, 11, 8)),
        ("g1", EmbeddingOp("gather", 6, 20, 8)),
        ("g2", EmbeddingOp("gather", 6, 20, 8)),
        ("solo", EmbeddingOp("spmm", 3, 5, 16, avg_lookups=2)),
    ), shared_tables=(("g1", "g2"),))


def _step_inputs(prog, seed, base):
    """Steady-state step: tables stay those of ``base``; index data fresh."""
    ins = make_program_inputs(prog, seed=seed)
    for n in ins:
        for k in ("table", "x"):
            if k in base[n]:
                ins[n][k] = base[n][k]
    return ins


# ---------------------------------------------------------------------------
# Executor numerics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lvl", OPT_LEVELS)
def test_executor_matches_interpreter_all_levels(lvl):
    prog = _mixed_program()
    pres = compile_program(prog, lvl, vlen=4, use_cache=False)
    ex = ProgramExecutor(pres)
    base = make_program_inputs(prog, seed=0)
    for seed in (0, 1, 2):
        ins = _step_inputs(prog, seed, base)
        want = program_reference(prog, ins)
        interp = run_program_interpreted(pres, ins)
        got = ex.step(ins)
        for n in want:
            np.testing.assert_allclose(np.asarray(got[n]), want[n],
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"{n}@{lvl} vs reference")
            np.testing.assert_allclose(np.asarray(got[n]),
                                       np.asarray(interp[n]),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"{n}@{lvl} vs interpreter")


def test_executor_matches_jax_backend():
    prog = _mixed_program()
    pres = compile_program(prog, "O3", vlen=4, use_cache=False)
    ins = make_program_inputs(prog, seed=3)
    want = backend_pallas.execute_program(pres, ins)
    got = ProgramExecutor(pres).step(ins)
    for n in dict(prog.ops):
        np.testing.assert_allclose(np.asarray(got[n]), np.asarray(want[n]),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("lvl", OPT_LEVELS)
def test_executor_jax_backend_numerics(lvl):
    """backend="jax": same marshaling cache, XLA execute unit."""
    prog = _mixed_program()
    pres = compile_program(prog, lvl, vlen=4, use_cache=False)
    ex = ProgramExecutor(pres, backend="jax")
    base = make_program_inputs(prog, seed=0)
    for seed in (0, 5):
        ins = _step_inputs(prog, seed, base)
        want = program_reference(prog, ins)
        got = ex.step(ins)
        for n in want:
            np.testing.assert_allclose(np.asarray(got[n]), want[n],
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"{n}@{lvl} jax backend")


# ---------------------------------------------------------------------------
# Marshaling cache: steady state does zero re-stacking
# ---------------------------------------------------------------------------

def test_marshaling_cache_reuse_no_restacking():
    prog = _mixed_program()
    ex = ProgramExecutor(compile_program(prog, "O3", vlen=4,
                                         use_cache=False))
    base = make_program_inputs(prog, seed=0)
    ex.step(base)
    stacks_after_first = ex.stats["table_stacks"]
    assert stacks_after_first == len(ex.compiled.units)
    tables = [id(u.table) for u in ex._units]
    misses_after_first = ex.stats["marshal_misses"]
    rng = np.random.default_rng(0)
    for _ in range(4):
        # same shapes, fresh index values: the steady-state decode pattern
        for n in base:
            if "idxs" in base[n]:
                rng.shuffle(base[n]["idxs"])
        ex.step(base)
    # no table was ever re-stacked, and the same-shape steps hit the
    # bucketed scratch instead of allocating new marshal state
    assert ex.stats["table_stacks"] == stacks_after_first
    assert [id(u.table) for u in ex._units] == tables
    assert ex.stats["marshal_misses"] == misses_after_first
    assert ex.stats["marshal_hits"] >= 4 * 3  # ≥ units × later steps


def test_update_tables_in_place_refresh():
    prog = _mixed_program()
    ex = ProgramExecutor(compile_program(prog, "O3", vlen=4,
                                         use_cache=False))
    ex.step(make_program_inputs(prog, seed=0))
    new = make_program_inputs(prog, seed=7)
    ex.update_tables(new)
    got = ex.step(new)
    want = program_reference(prog, new)
    for n in want:
        np.testing.assert_allclose(np.asarray(got[n]), want[n],
                                   rtol=1e-4, atol=1e-4)
    # only the owned multi-slot stack (w,u,k) is a device restack; the
    # single-slot gather group and the singleton alias-rebind for free
    owned = sum(1 for u in ex._units if u.owns_table)
    assert owned == 1
    assert ex.stats["table_restacks"] == owned
    assert ex.stats["table_rebinds"] == len(ex.compiled.units) - owned
    # feeding the SAME arrays again is a no-op (steady-state train feed)
    ex.update_tables(new)
    assert ex.stats["table_restacks"] == owned
    assert ex.stats["table_rebinds"] == len(ex.compiled.units) - owned


def test_update_tables_partial_inputs_skip_missing_units():
    """The trainer feeds only the param-backed tables; units with absent
    member inputs (per-step operand tables) must be left untouched."""
    prog = _mixed_program()
    ex = ProgramExecutor(compile_program(prog, "O3", vlen=4,
                                         use_cache=False))
    base = make_program_inputs(prog, seed=0)
    ex.step(base)
    new = make_program_inputs(prog, seed=11)
    ex.update_tables({"solo": new["solo"]})     # only the singleton present
    assert ex.stats["table_restacks"] == 0
    assert ex.stats["table_rebinds"] == 1
    # the untouched units still serve their previously bound tables
    ins = _step_inputs(prog, 12, base)
    ins["solo"]["table"] = new["solo"]["table"]
    got = ex.step(ins)
    for n, w in program_reference(prog, ins).items():
        np.testing.assert_allclose(np.asarray(got[n]), w,
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Double-buffered overlap across ragged batches
# ---------------------------------------------------------------------------

def test_double_buffer_ragged_sequence():
    """submit/result pipeline over steps whose nnz (and hence capacity
    bucket) varies: every step's async outputs must match its own inputs."""
    prog = EmbeddingProgram("ragged", (
        ("a", EmbeddingOp("sls", 6, 12, 8, avg_lookups=2)),
        ("b", EmbeddingOp("sls", 5, 9, 8, avg_lookups=12)),
    ))
    ex = ProgramExecutor(compile_program(prog, "O3", vlen=4,
                                         use_cache=False), depth=2)
    base = make_program_inputs(prog, seed=0)
    steps, wants = [], []
    for seed in range(6):
        ins = _step_inputs(prog, seed * 31 + 1, base)
        steps.append(ins)
        wants.append(program_reference(prog, ins))
    results = ex.run_steps(steps)
    assert ex.stats["max_inflight"] == 2
    for s, (got, want) in enumerate(zip(results, wants)):
        for n in want:
            np.testing.assert_allclose(np.asarray(got[n]), want[n],
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"step {s} op {n}")
    # ragged nnz produced more than one capacity bucket for the fused unit
    # (pool keys are (unit, bucket))
    assert len({k[1] for k in ex.pool._entries}) >= 2


def test_interleaved_submit_and_step_keep_slots_safe():
    """An un-consumed submit() must survive any number of later step()
    calls rotating through the same scratch bucket: the slot owner is
    drained before reuse, so the old handle's outputs stay its own."""
    prog = EmbeddingProgram("p", (
        ("a", EmbeddingOp("sls", 6, 12, 8, avg_lookups=2)),
        ("b", EmbeddingOp("sls", 5, 9, 8, avg_lookups=2)),
    ))
    ex = ProgramExecutor(compile_program(prog, "O3", vlen=4,
                                         use_cache=False), depth=2)
    base = make_program_inputs(prog, seed=0)
    ins0 = _step_inputs(prog, 100, base)
    want0 = program_reference(prog, ins0)
    h0 = ex.submit(ins0)                  # left in flight, not consumed
    for seed in (101, 102, 103, 104):     # same shapes → same bucket
        ins = _step_inputs(prog, seed, base)
        got = ex.step(ins)
        for n, w in program_reference(prog, ins).items():
            np.testing.assert_allclose(np.asarray(got[n]), w,
                                       rtol=1e-4, atol=1e-4)
    out0 = h0.result()
    for n in want0:
        np.testing.assert_allclose(np.asarray(out0[n]), want0[n],
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"stale submit clobbered {n}")


def test_buffer_pool_grows_instead_of_draining_when_shared():
    """Exhausting every slot of a ring grows it (up to max_slots) rather
    than draining an in-flight owner; only a full ring drains its oldest
    owner."""
    from repro.core.executor import BufferPool

    class _FakeHandle:
        done = False
        drained = 0

        def result(self):
            self.done = True
            _FakeHandle.drained += 1

    pool = BufferPool(n_slots=2, max_slots=3)
    spec = {"idxs": ((8,), np.int32)}
    key = ("owner", ())
    taken = []
    for _ in range(3):
        entry, turn, _ = pool.acquire(key, spec)
        h = _FakeHandle()
        entry["owners"][turn] = h
        taken.append((entry, turn))
    assert pool.stats["grown"] == 1           # 2 slots -> grew to 3
    assert _FakeHandle.drained == 0
    # ring at max_slots and all busy: now the oldest owner is drained
    entry, turn, _ = pool.acquire(key, spec)
    assert pool.stats["forced_drains"] == 1
    assert _FakeHandle.drained == 1


def test_step_handles_are_identity_compared():
    prog = EmbeddingProgram("p1", (("a", EmbeddingOp("sls", 3, 7, 8)),))
    ex = ProgramExecutor(compile_program(prog, "O3", use_cache=False))
    ins = make_program_inputs(prog, seed=0)
    h1, h2 = ex.submit(ins), ex.submit(ins)
    assert h1 is not h2 and h1 != h2
    ex.drain()


# ---------------------------------------------------------------------------
# Cost-model fusion partitioning
# ---------------------------------------------------------------------------

def _giant_program(n_ops=8, segs=2000, avg=16):
    return EmbeddingProgram("giant", tuple(
        (f"t{i}", EmbeddingOp("sls", segs, 64, 16, avg_lookups=avg))
        for i in range(n_ops)))


def test_partitioner_splits_giant_group_within_budget():
    prog = _giant_program()
    budget = cost_model.FusionBudget(vmem_bytes=400_000)
    units, note = fuse_program(prog, vlen=128, budget=budget)
    groups = [u for u in units if not isinstance(u, tuple)]
    assert len(groups) >= 2, note          # the giant group was split
    assert "split by budget" in note
    for g in groups:
        res = cost_model.fused_plan_resources(g.member_ops, vlen=128)
        assert res["vmem_bytes"] <= budget.vmem_bytes, \
            f"group {g.members} overflows the budget: {res}"
    # every member appears exactly once across the partition
    emitted = [n for g in groups for n in g.members] + \
        [u[0] for u in units if isinstance(u, tuple)]
    assert sorted(emitted) == sorted(prog.names)


def test_partitioner_balances_access_load():
    prog = _giant_program(n_ops=9)
    budget = cost_model.FusionBudget(vmem_bytes=500_000)
    parts = partition_members(prog, prog.names, 128, budget)
    assert len(parts) >= 2
    loads = [sum(cost_model.access_weight(prog.op(n)) for n in part)
             for part in parts]
    assert max(loads) <= 2.5 * min(loads), loads   # LPT balance

def test_partitioner_keeps_small_groups_whole():
    prog = EmbeddingProgram("small", (
        ("a", EmbeddingOp("sls", 5, 11, 10, avg_lookups=3)),
        ("b", EmbeddingOp("sls", 7, 6, 10, avg_lookups=2)),
    ))
    units, _ = fuse_program(prog)          # default budget
    assert len(units) == 1 and not isinstance(units[0], tuple)


def test_partitioned_program_still_correct():
    """A split group must stay numerically identical to the reference."""
    prog = EmbeddingProgram("split4", tuple(
        (f"t{i}", EmbeddingOp("sls", 40, 16, 8, avg_lookups=4))
        for i in range(4)))
    budget = cost_model.FusionBudget(vmem_bytes=4096)
    pres = compile_program(prog, "O3", vlen=4, use_cache=False,
                           budget=budget)
    assert len(pres.units) >= 2
    ins = make_program_inputs(prog, seed=5)
    want = program_reference(prog, ins)
    for outs in (run_program_interpreted(pres, ins),
                 ProgramExecutor(pres).step(ins)):
        for n in want:
            np.testing.assert_allclose(np.asarray(outs[n]), want[n],
                                       rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Extended fusion: kg as degenerate CSR, mixed weighted/unweighted upcast
# ---------------------------------------------------------------------------

def test_kg_fuses_as_degenerate_csr():
    prog = EmbeddingProgram("kgmix", (
        ("s", EmbeddingOp("sls", 4, 9, 8, avg_lookups=3)),
        ("k", EmbeddingOp("kg", 6, 11, 8)),
    ))
    units, _ = fuse_program(prog)
    assert len(units) == 1
    group = units[0]
    assert group.op.kind == "sls" and group.op.weighted  # upcast
    ins = make_program_inputs(prog, seed=2)
    want = program_reference(prog, ins)
    for lvl in OPT_LEVELS:
        pres = compile_program(prog, lvl, vlen=4, use_cache=False)
        outs = run_program_interpreted(pres, ins)
        for n in want:
            np.testing.assert_allclose(outs[n], want[n], rtol=1e-4,
                                       atol=1e-5, err_msg=f"{n}@{lvl}")


def test_mixed_weighted_unweighted_upcast():
    prog = EmbeddingProgram("wmix", (
        ("w", EmbeddingOp("sls", 5, 9, 8, avg_lookups=3, weighted=True)),
        ("u", EmbeddingOp("sls", 4, 7, 8, avg_lookups=2)),
    ))
    units, _ = fuse_program(prog)
    assert len(units) == 1 and units[0].op.weighted
    assert units[0].unit_weight == 1.0
    ins = make_program_inputs(prog, seed=4)
    want = program_reference(prog, ins)
    pres = compile_program(prog, "O3", vlen=4, use_cache=False)
    outs = backend_pallas.execute_program(pres, ins)
    for n in want:
        np.testing.assert_allclose(np.asarray(outs[n]), want[n],
                                   rtol=1e-4, atol=1e-4)


def test_semiring_mismatch_still_separate():
    prog = EmbeddingProgram("srmix", (
        ("a", EmbeddingOp("sls", 4, 9, 8)),
        ("m", EmbeddingOp("kg", 4, 9, 8, semiring=Semiring("max"))),
    ))
    units, note = fuse_program(prog)
    assert len(units) == 2 and "0 fused" in note


# ---------------------------------------------------------------------------
# Bounded LRU compile cache
# ---------------------------------------------------------------------------

def _prog_of_width(w):
    return EmbeddingProgram("p", (("a", EmbeddingOp("sls", 4, 9, w)),))


def test_compile_cache_lru_eviction():
    clear_compile_cache()
    prev = set_compile_cache_limit(2)
    try:
        compile_program(_prog_of_width(8), "O1", vlen=4)    # A
        compile_program(_prog_of_width(16), "O1", vlen=4)   # B
        assert compile_program(_prog_of_width(8), "O1", vlen=4).cache_hit
        compile_program(_prog_of_width(24), "O1", vlen=4)   # C evicts B (LRU)
        stats = compile_cache_stats()
        assert stats["entries"] == 2 and stats["capacity"] == 2
        assert stats["evictions"] == 1
        assert compile_program(_prog_of_width(8), "O1", vlen=4).cache_hit
        assert not compile_program(_prog_of_width(16), "O1", vlen=4).cache_hit
    finally:
        set_compile_cache_limit(prev)
        clear_compile_cache()


def test_shrinking_limit_evicts_immediately():
    clear_compile_cache()
    prev = set_compile_cache_limit(8)
    try:
        for w in (8, 16, 24):
            compile_program(_prog_of_width(w), "O1", vlen=4)
        set_compile_cache_limit(1)
        assert compile_cache_stats()["entries"] == 1
        assert compile_cache_stats()["evictions"] == 2
    finally:
        set_compile_cache_limit(prev)
        clear_compile_cache()


# ---------------------------------------------------------------------------
# Executor cache (the runtimes' steady-state entry point)
# ---------------------------------------------------------------------------

def test_executor_for_memoizes_per_signature():
    clear_executor_cache()
    prog = _mixed_program()
    ex1 = executor_for(prog, "O3", vlen=4)
    ex1.step(make_program_inputs(prog, seed=0))
    ex2 = executor_for(_mixed_program(), "O3", vlen=4)  # equal signature
    assert ex2 is ex1                      # same warm marshaling cache back
    stats = executor_cache_stats()
    assert stats["hits"] == 1 and stats["misses"] == 1
    assert executor_for(prog, "O2", vlen=4) is not ex1
    clear_executor_cache()


def test_shared_signature_executor_rebinds_other_models_tables():
    """Two models with equal program signatures share one cached executor;
    the per-step table identity check must rebind instead of silently
    serving model A's tables to model B."""
    clear_executor_cache()
    prog = _mixed_program()
    ex = executor_for(prog, "O3", vlen=4)
    ins_a = make_program_inputs(prog, seed=0)
    ex.step(ins_a)
    ins_b = make_program_inputs(prog, seed=9)   # "another model": new arrays
    ex_b = executor_for(_mixed_program(), "O3", vlen=4)
    assert ex_b is ex
    got = ex_b.step(ins_b)
    want = program_reference(prog, ins_b)
    for n in want:
        np.testing.assert_allclose(np.asarray(got[n]), want[n],
                                   rtol=1e-4, atol=1e-4)
    assert ex.stats["table_rebinds"] == len(ex.compiled.units)
    # back to model A's arrays: rebinds again, still correct
    got = ex.step(ins_a)
    for n, w in program_reference(prog, ins_a).items():
        np.testing.assert_allclose(np.asarray(got[n]), w,
                                   rtol=1e-4, atol=1e-4)
    clear_executor_cache()


def test_trainer_feed_keeps_executor_fresh_no_restacks(tmp_path):
    """The trainer donates every optimizer step's embed table into the
    executor via ``update_tables``; for the LM program (token embed + label
    gather sharing one table) that is an alias rebind, so the train→serve
    handoff never re-stacks: ``table_restacks`` stays 0 across the whole
    cycle and the serve step hits the identity fast path."""
    import jax
    from repro.configs import get_reduced
    from repro.data.pipeline import DataConfig, SyntheticTokens
    from repro.models import LM
    from repro.runtime.trainer import Trainer, TrainerConfig

    cfg = get_reduced("stablelm-3b")
    lm = LM(cfg)
    data = SyntheticTokens(DataConfig(vocab_size=cfg.vocab_size, seq_len=8,
                                      global_batch=4))
    tcfg = TrainerConfig(total_steps=3, ckpt_every=8,
                         ckpt_dir=str(tmp_path / "ckpt"))
    trainer = Trainer(lm, data, tcfg)
    out = trainer.run(jax.random.PRNGKey(0))
    ex = trainer.emb_executor
    n_units = len(ex.compiled.units)
    # training fed 3 param versions: bind once, then alias rebinds only
    assert ex.stats["table_stacks"] == n_units
    assert ex.stats["table_restacks"] == 0
    rebinds_after_train = ex.stats["table_rebinds"]
    assert rebinds_after_train == (tcfg.total_steps - 1) * n_units

    # serve: drive the SAME executor with the final params — identity hit,
    # zero re-stacking, correct lookups
    params = out["state"]["params"]
    embed = np.asarray(params["embed"], np.float32)
    tokens = np.arange(32, dtype=np.int32) % cfg.padded_vocab
    ins = {"tok_embed": {"table": params["embed"], "idxs": tokens},
           "label_gather": {"table": params["embed"], "idxs": tokens}}
    got = ex.step(ins)
    assert ex.stats["table_stacks"] == n_units
    assert ex.stats["table_restacks"] == 0
    assert ex.stats["table_rebinds"] == rebinds_after_train
    np.testing.assert_allclose(
        np.asarray(got["tok_embed"], np.float32).reshape(32, -1),
        embed[tokens], rtol=1e-2, atol=1e-2)


def test_fusedmm_singleton_takes_fresh_x_each_step():
    """fusedmm's dense operand is per-step data, not weights — the executor
    must not freeze the step-1 features."""
    from repro.core.ops import single_op_program
    prog = single_op_program(
        EmbeddingOp("fusedmm", 6, 6, 8, avg_lookups=2), "mp")
    ex = ProgramExecutor(compile_program(prog, "O2", vlen=4,
                                         use_cache=False))
    for seed in (0, 1):
        ins = make_program_inputs(prog, seed=seed)
        got = ex.step(ins)
        want = program_reference(prog, ins)
        np.testing.assert_allclose(np.asarray(got["mp"]), want["mp"],
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"seed {seed}")
