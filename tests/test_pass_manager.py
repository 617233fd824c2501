"""Program-level compilation: PassManager ordering/diagnostics, inter-pass
verification, multi-table fusion correctness at every opt level (interpreted
and Pallas/jnp backends vs. composed numpy references), and compile-cache
hit behaviour (no pass re-runs on a hit)."""
import numpy as np
import pytest

from repro.core import backend_jax, backend_pallas, slc as slc_ir
from repro.core import scf as scf_ir
from repro.core.ops import (EmbeddingOp, EmbeddingProgram,
                            make_program_inputs, program_reference)
from repro.core.pass_manager import Pass, PassManager, verify_ir
from repro.core.passes import fuse_inputs, fuse_program, split_outputs
from repro.core.pipeline import (OPT_LEVELS, clear_compile_cache,
                                 compile_cache_stats, compile_op,
                                 compile_program, opt_level_index,
                                 run_interpreted, run_program_interpreted)

ALL_PASSES = ["build-scf", "decouple", "vectorize", "bufferize",
              "store-streams", "queue-align", "lower-dlc", "plan-access"]


def _two_table_program(kind="sls", emb_len=10):
    return EmbeddingProgram("p2", (
        ("a", EmbeddingOp(kind, num_segments=5, num_embeddings=11,
                          emb_len=emb_len, avg_lookups=3,
                          block_rows=2 if kind == "gather" else 1)),
        ("b", EmbeddingOp(kind, num_segments=7, num_embeddings=6,
                          emb_len=emb_len, avg_lookups=2,
                          block_rows=2 if kind == "gather" else 1)),
    ))


# ---------------------------------------------------------------------------
# PassManager: ordering, gating, diagnostics
# ---------------------------------------------------------------------------

def test_pass_ordering_and_opt_gating():
    op = EmbeddingOp("sls", 4, 9, 8, avg_lookups=2)
    ran_by_lvl = {}
    for lvl in OPT_LEVELS:
        res = compile_op(op, lvl, vlen=4)
        ran = [r.name for r in res.records if r.ran]
        # declared order is preserved and mandatory stages always run
        assert ran == [p for p in ALL_PASSES if p in ran]
        assert ran[0] == "build-scf" and ran[-1] == "plan-access"
        assert "decouple" in ran and "lower-dlc" in ran
        ran_by_lvl[lvl] = set(ran)
    assert "vectorize" not in ran_by_lvl["O0"]
    assert "vectorize" in ran_by_lvl["O1"]
    assert "bufferize" not in ran_by_lvl["O1"]
    assert "bufferize" in ran_by_lvl["O2"]
    assert {"queue-align"} <= ran_by_lvl["O3"]
    # skipped passes are still recorded, with a reason
    rec0 = compile_op(op, "O0").records
    gated = {r.name: r.note for r in rec0 if not r.ran}
    assert "vectorize" in gated and "opt-gated" in gated["vectorize"]
    # per-pass timing is populated for executed passes
    assert all(r.duration_s >= 0 for r in rec0)


def test_pass_records_stage_annotations():
    res = compile_op(EmbeddingOp("sls", 3, 7, 6), "O3", vlen=4)
    stages = {r.name: r.stage for r in res.records if r.ran}
    assert stages["build-scf"] == "scf"
    assert stages["decouple"] == "slc"
    assert stages["vectorize"] == "slcv"
    assert stages["lower-dlc"] == "dlc"
    assert stages["plan-access"] == "access"
    assert res.access_plan is not None


def test_verifier_catches_malformed_slc():
    """A pass that emits an SLC function violating the §6.2 invariant (a
    mem_str over a writable memref) is caught at its own boundary."""
    def corrupt(fn, **_):
        fn.body.insert(0, slc_ir.MemStr("bad", "out",
                                        (scf_ir.Const(0), scf_ir.Const(0))))
        return fn

    pm = PassManager()
    pm.register(Pass("corrupt", ("slc", "slcv"), corrupt), after="decouple")
    with pytest.raises(slc_ir.SlcVerifyError):
        compile_op(EmbeddingOp("sls", 3, 7, 6), "O0", pm=pm)


def test_verifier_catches_wrong_stage_artifact():
    def not_an_ir(fn, **_):
        return {"oops": fn}

    pm = PassManager()
    pm.register(Pass("break-type", ("slc", "slcv"), not_an_ir),
                after="decouple")
    with pytest.raises(slc_ir.SlcVerifyError):
        compile_op(EmbeddingOp("sls", 3, 7, 6), "O0", pm=pm)


def test_verify_ir_rejects_duplicate_dlc_tokens():
    res = compile_op(EmbeddingOp("sls", 3, 7, 6), "O0")
    res.dlc.cases.append(res.dlc.cases[0])
    with pytest.raises(slc_ir.SlcVerifyError):
        verify_ir("dlc", res.dlc)


def test_register_after_unknown_pass_raises():
    from repro.core.pass_manager import PassManagerError
    pm = PassManager()
    with pytest.raises(PassManagerError):
        pm.register(Pass("x", "slc", lambda f, **_: f), after="nope")


def test_opt_level_index_numeric_not_lexical():
    assert [opt_level_index(l) for l in OPT_LEVELS] == [0, 1, 2, 3]
    with pytest.raises(AssertionError):
        opt_level_index("O9")


# ---------------------------------------------------------------------------
# Fusion pass: 2-table programs match composed references at O0–O3
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["sls", "gather", "spmm"])
@pytest.mark.parametrize("lvl", OPT_LEVELS)
def test_fusion_matches_composed_reference(kind, lvl):
    prog = _two_table_program(kind)
    ins = make_program_inputs(prog, seed=3)
    want = program_reference(prog, ins)
    pres = compile_program(prog, lvl, vlen=4, use_cache=False)
    assert len(pres.units) == 1 and pres.units[0].fused
    assert pres.units[0].result.op.num_tables == 2
    for stage in ("slc", "dlc"):
        outs = run_program_interpreted(pres, ins, stage)
        for n in want:
            np.testing.assert_allclose(outs[n], want[n], rtol=1e-4,
                                       atol=1e-5, err_msg=f"{n}@{lvl}")


@pytest.mark.parametrize("lvl", OPT_LEVELS)
def test_fusion_backends_match_reference(lvl):
    prog = _two_table_program("sls", emb_len=12)
    ins = make_program_inputs(prog, seed=5)
    want = program_reference(prog, ins)
    pres = compile_program(prog, lvl, vlen=4, use_cache=False)
    # Pallas backend: one batched kernel launch for the fused unit
    outs = backend_pallas.execute_program(pres, ins)
    for n in want:
        np.testing.assert_allclose(np.asarray(outs[n]), want[n],
                                   rtol=1e-4, atol=1e-4)
    # jnp baseline on the fused unit
    group = pres.units[0].group
    got = backend_jax.execute(group.op, fuse_inputs(group, ins))
    per_op = split_outputs(group, np.asarray(got))
    for n in want:
        np.testing.assert_allclose(per_op[n], want[n], rtol=1e-4, atol=1e-4)


def test_fused_kernel_plan_is_batched():
    pres = compile_program(_two_table_program("sls"), "O3",
                           use_cache=False)
    plan = backend_pallas.make_plan(pres.units[0].result)
    assert plan.batched and plan.num_tables == 2


def test_incompatible_ops_stay_separate():
    from repro.core.ops import Semiring
    prog = EmbeddingProgram("mix", (
        ("s", EmbeddingOp("sls", 4, 9, 8)),
        ("k", EmbeddingOp("kg", 4, 9, 8,
                          semiring=Semiring("max"))),  # semiring mismatch
        ("g", EmbeddingOp("gather", 3, 5, 8, block_rows=2)),
        ("s2", EmbeddingOp("sls", 2, 5, 16)),       # emb_len mismatch
    ))
    units, note = fuse_program(prog)
    assert len(units) == 4 and "0 fused" in note
    ins = make_program_inputs(prog, seed=1)
    outs = run_program_interpreted(
        compile_program(prog, "O3", vlen=4, use_cache=False), ins)
    for n, w in program_reference(prog, ins).items():
        np.testing.assert_allclose(outs[n], w, rtol=1e-4, atol=1e-5)


def test_shared_table_stacked_once():
    prog = EmbeddingProgram("lm", (
        ("tok", EmbeddingOp("gather", 6, 20, 8)),
        ("lab", EmbeddingOp("gather", 6, 20, 8)),
        ("moe", EmbeddingOp("gather", 4, 12, 8)),
    ), shared_tables=(("tok", "lab"),))
    units, _ = fuse_program(prog)
    assert len(units) == 1
    group = units[0]
    # tok and lab share base 0; moe starts right after ONE copy of the table
    assert group.row_offsets == (0, 0, 20)
    assert group.op.num_embeddings == 32
    ins = make_program_inputs(prog, seed=2)
    fused_in = fuse_inputs(group, ins)
    assert fused_in["table"].shape[0] == 32
    pres = compile_program(prog, "O3", vlen=4, use_cache=False)
    outs = run_program_interpreted(pres, ins)
    for n, w in program_reference(prog, ins).items():
        np.testing.assert_allclose(outs[n], w, rtol=1e-4, atol=1e-5)


def test_fused_queue_traffic_not_worse_than_per_op():
    """Fusion must not add queue traffic: the marshaled-data total of the
    fused program equals the sum of the members' (the table-offset stream
    stays on the access unit)."""
    prog = _two_table_program("sls")
    ins = make_program_inputs(prog, seed=7)
    pres = compile_program(prog, "O3", vlen=4, use_cache=False)
    _, fused_stats = run_program_interpreted(pres, ins, "dlc",
                                             return_queues=True)
    per_op = 0
    for name, op in prog.ops:
        res = compile_op(op, "O3", vlen=4)
        _, st = run_interpreted(res, ins[name], "dlc", return_queues=True)
        per_op += st["data_pushed"]
    assert fused_stats["data_pushed"] <= per_op
    assert fused_stats["data_left"] == 0 and fused_stats["ctrl_left"] == 0


# ---------------------------------------------------------------------------
# Compile cache
# ---------------------------------------------------------------------------

def test_compile_cache_hit_runs_no_passes():
    clear_compile_cache()
    prog = _two_table_program("sls")
    pres1 = compile_program(prog, "O3", vlen=4)
    assert not pres1.cache_hit
    before = PassManager.total_executed
    # identical signature (fresh but structurally equal program object)
    pres2 = compile_program(_two_table_program("sls"), "O3", vlen=4)
    assert pres2.cache_hit
    assert PassManager.total_executed == before, \
        "cache hit must not re-run any pass"
    # the diagnostics are the original compile's records, not new ones
    assert pres2.pass_records() == pres1.pass_records()
    stats = compile_cache_stats()
    assert stats["hits"] >= 1 and stats["misses"] >= 1


def test_compile_cache_distinguishes_options():
    clear_compile_cache()
    prog = _two_table_program("sls")
    compile_program(prog, "O3", vlen=4)
    assert not compile_program(prog, "O2", vlen=4).cache_hit
    assert not compile_program(prog, "O3", vlen=8).cache_hit
    assert compile_program(prog, "O3", vlen=4).cache_hit
    assert compile_cache_stats()["entries"] == 3


def test_program_signature_name_independent():
    a = EmbeddingProgram("x", (("a", EmbeddingOp("sls", 4, 9, 8)),))
    b = EmbeddingProgram("y", (("a", EmbeddingOp("sls", 4, 9, 8)),))
    assert a.signature() == b.signature()
