"""Vocab-sharded fused programs: AccessPlan layout/routing math (incl. the
hot/cold split and the collective send lattice), per-shard cost model,
mesh-of-size-1 identity with the single-device executor, and (in a
2-device subprocess via the ``run_on_mesh`` conftest fixture) end-to-end
sharded numerics — mixed weighted/unweighted + kg fusion, max-semiring
merge, empty shards/steps, hot-slab batches, both execute backends, both
exchange modes (host scatter / device all_to_all + reduce-scatter),
footprint halving, sharded ``update_tables`` and the executor-cache
keying."""
import numpy as np
import pytest

from repro.core import access_plan as ap
from repro.core import cost_model, shard_plan as sp
from repro.core.executor import (ProgramExecutor, clear_executor_cache,
                                 executor_cache_stats, executor_for)
from repro.core.ops import (EmbeddingOp, EmbeddingProgram,
                            make_program_inputs, program_reference)
from repro.core.passes import fuse_program
from repro.core.passes.fuse import FusedGroup
from repro.core.pipeline import compile_program
from repro.kernels.sls import exchange_capacity


def _csr_group():
    # 'a' weighted -> the fused group unit-weight-upcasts and marshals a
    # vals stream, so the routing tests cover the vals permutation too
    prog = EmbeddingProgram("g", (
        ("a", EmbeddingOp("sls", 4, 10, 8, avg_lookups=3, weighted=True)),
        ("b", EmbeddingOp("sls", 3, 7, 8, avg_lookups=2)),
    ))
    units, _ = fuse_program(prog)
    assert len(units) == 1 and isinstance(units[0], FusedGroup)
    return units[0]


def _group_inputs(group, seg, idxs, vals=None):
    """Split a fused (seg, idx) stream back into per-member input dicts."""
    inputs = {}
    pos = 0
    for name, mop, off in zip(group.members, group.member_ops,
                              group.seg_offsets):
        mask = (seg >= off) & (seg < off + mop.num_segments)
        counts = np.bincount(seg[mask] - off, minlength=mop.num_segments)
        ptrs = np.zeros(mop.num_segments + 1, np.int64)
        np.cumsum(counts, out=ptrs[1:])
        ins = {"ptrs": ptrs, "idxs": idxs[mask]}
        if vals is not None:
            ins["vals"] = vals[mask]
        inputs[name] = ins
        pos += mask.sum()
    return inputs


# ---------------------------------------------------------------------------
# AccessPlan layout
# ---------------------------------------------------------------------------

def test_plan_layout_capacities_and_local_bases():
    g = _csr_group()
    plan = ap.plan_for_group(g, shards=2)
    assert [s.rows for s in plan.slots] == [10, 7]
    assert [s.cap for s in plan.slots] == [5, 4]        # ceil splits
    assert [s.cold_base for s in plan.slots] == [0, 5]
    assert plan.local_rows == 9
    assert plan.hot_rows_total == 0
    # single-device roff: the stacked slot bases per segment
    assert plan.roff.tolist() == [0, 0, 0, 0, 10, 10, 10]


def test_plan_hot_layout_reserves_slab_after_cold():
    g = _csr_group()
    plan = ap.plan_for_group(g, shards=2,
                             hot_rows={"a": (2, 7), "b": (0,)})
    s0, s1 = plan.slots
    assert s0.hot_ids.tolist() == [2, 7] and s1.hot_ids.tolist() == [0]
    assert s0.cold_rows == 8 and s1.cold_rows == 6
    assert [s.cap for s in plan.slots] == [4, 3]
    assert [s.cold_base for s in plan.slots] == [0, 4]
    # hot slabs pack after ALL cold slices
    assert s0.hot_base == 7 and s1.hot_base == 9
    assert plan.local_rows == 7 + 3
    assert plan.hot_slab_bytes == 3 * 8 * 4


def test_hot_disabled_layout_matches_pr3_interleave():
    """With no hot classification the plan's stack/routing must reduce to
    the PR-3 interleaved ceil-split, element for element."""
    g = _csr_group()
    plan = ap.plan_for_group(g, shards=2)
    rng = np.random.default_rng(0)
    parts = [rng.standard_normal((10, 8)).astype(np.float32),
             rng.standard_normal((7, 8)).astype(np.float32)]
    glob = plan.stack_np(parts)
    assert glob.shape == (2 * plan.local_rows, 8)
    # PR-3 ownership math: global row r of slot t lives on shard r // C_t
    # at local offset base_t + (r - owner*C_t)
    for t, part in enumerate(parts):
        cap = plan.slots[t].cap
        base = plan.slots[t].cold_base
        for r in range(part.shape[0]):
            o = r // cap
            local = base + (r - o * cap)
            np.testing.assert_array_equal(
                glob[o * plan.local_rows + local], part[r])


def test_hot_stack_replicates_slab_on_every_shard():
    g = _csr_group()
    hot = {"a": (0, 9), "b": (3,)}
    plan = ap.plan_for_group(g, shards=2, hot_rows=hot)
    rng = np.random.default_rng(1)
    parts = [rng.standard_normal((10, 8)).astype(np.float32),
             rng.standard_normal((7, 8)).astype(np.float32)]
    glob = plan.stack_np(parts)
    for sh in range(2):
        for t, part in enumerate(parts):
            slot = plan.slots[t]
            for pos, row in enumerate(slot.hot_ids):
                np.testing.assert_array_equal(
                    glob[sh * plan.local_rows + slot.hot_base + pos],
                    part[row])
            for rank, row in enumerate(slot.cold_ids):
                o = rank // slot.cap
                if o != sh:
                    continue
                np.testing.assert_array_equal(
                    glob[sh * plan.local_rows + slot.cold_base
                         + rank - o * slot.cap], part[row])


# ---------------------------------------------------------------------------
# AccessPlan routing
# ---------------------------------------------------------------------------

def test_route_csr_emits_valid_rebased_per_shard_csr():
    g = _csr_group()
    plan = ap.plan_for_group(g, shards=2)
    num_segments = plan.num_segments
    # 7 segments; indices spread over both member tables
    seg = np.array([0, 0, 1, 3, 4, 4, 5, 6], np.int64)
    idxs = np.array([9, 2, 5, 0, 6, 1, 3, 4], np.int64)
    vals = np.arange(8, dtype=np.float32)
    routed = plan.route_csr(_group_inputs(g, seg, idxs, vals))
    assert routed["cap"] == exchange_capacity(routed["nnz"], [0])[0]
    assert routed["hot_nnz"] == 0 and routed["cold_nnz"] == 8
    # reconstruct: every (seg, owner, local, val) triple must round-trip
    got = set()
    for o in range(2):
        p = routed["ptrs"][o]
        lo, hi = routed["bounds"][o], routed["bounds"][o + 1]
        sh_idxs = routed["idxs"][lo:hi]
        sh_vals = routed["vals"][lo:hi]
        assert (np.diff(p) >= 0).all() and p[-1] == hi - lo
        pos = 0
        for b in range(num_segments):
            for _ in range(p[b + 1] - p[b]):
                got.add((b, o, int(sh_idxs[pos]), float(sh_vals[pos])))
                pos += 1
    # PR-3 oracle: member a has C=5 (slot base 0), member b C=4 (base 5)
    caps = np.array([5, 5, 5, 5, 4, 4, 4, 4], np.int64)
    base = np.array([0, 0, 0, 0, 5, 5, 5, 5], np.int64)
    want = {(int(s), int(i // c), int(b + i % c), float(v))
            for s, i, c, b, v in zip(seg, idxs, caps, base, vals)}
    assert got == want


def test_route_csr_hot_rows_pay_no_exchange():
    g = _csr_group()
    hot = {"a": (2, 9), "b": (1,)}
    plan = ap.plan_for_group(g, shards=2, hot_rows=hot)
    seg = np.array([0, 0, 1, 3, 4, 4, 5, 6], np.int64)
    idxs = np.array([9, 2, 5, 0, 6, 1, 3, 4], np.int64)
    vals = np.arange(8, dtype=np.float32)
    routed = plan.route_csr(_group_inputs(g, seg, idxs, vals))
    # idx 9 and 2 of member a, idx 1 of member b are hot
    assert routed["hot_nnz"] == 3 and routed["cold_nnz"] == 5
    # every hot lookup resolves into the slab address range of its slot
    slab_lo = min(s.hot_base for s in plan.slots if s.hot_rows)
    n_hot = 0
    for o in range(2):
        lo, hi = routed["bounds"][o], routed["bounds"][o + 1]
        n_hot += int((routed["idxs"][lo:hi] >= slab_lo).sum())
    assert n_hot == 3
    # round-robin assignment balances hot lookups across shards
    assert routed["nnz"].sum() == 8


def test_route_csr_all_hot_batch():
    g = _csr_group()
    plan = ap.plan_for_group(g, shards=2,
                             hot_rows={"a": tuple(range(10)),
                                       "b": tuple(range(7))})
    seg = np.array([0, 1, 4, 5], np.int64)
    idxs = np.array([3, 8, 2, 6], np.int64)
    routed = plan.route_csr(_group_inputs(g, seg, idxs))
    assert routed["cold_nnz"] == 0 and routed["hot_nnz"] == 4
    # round-robin: both shards serve half the batch
    assert routed["nnz"].tolist() == [2, 2]


def test_route_csr_empty_stream_and_empty_shard():
    g = _csr_group()
    plan = ap.plan_for_group(g, shards=2)
    empty = _group_inputs(g, np.zeros(0, np.int64), np.zeros(0, np.int64))
    routed = plan.route_csr(empty)
    assert routed["nnz"].tolist() == [0, 0]
    assert routed["cap"] == 1 and routed["max_lookups"] == 1
    assert routed["hot_nnz"] == 0 and routed["cold_nnz"] == 0
    # all indices owned by shard 0 -> shard 1 empty but still a valid CSR
    seg = np.zeros(3, np.int64)
    idxs = np.array([0, 1, 2], np.int64)
    routed = plan.route_csr(_group_inputs(g, seg, idxs))
    assert routed["nnz"].tolist() == [3, 0]
    assert (routed["ptrs"][1] == 0).all()


def _unpack_lattice(routed, plan, need_vals=True):
    """Pack a collective routing into its send lattice and flatten it back
    into the set of (seg, src, dst, local[, val]) tuples it carries (pad
    slots dropped) — the round-trip the device all_to_all relies on."""
    s = plan.shards
    B = plan.num_segments
    packed = plan.packed_lattice(routed)
    ints, vals = packed["ints"], packed["vals"]
    got = set()
    for src in range(s):
        for dst in range(s):
            for k in range(ints.shape[-1]):
                seg = int(ints[src, dst, 0, k])
                if seg >= B:            # pad sentinel
                    continue
                item = (seg, src, dst, int(ints[src, dst, 1, k]))
                if need_vals:
                    item += (float(vals[src, dst, k]),)
                got.add(item)
    return got


def test_route_csr_collective_matches_host_routing():
    """The collective send lattice carries exactly the host route's
    (segment, owner, local address, val) resolution, with the source shard
    = the lookup's contiguous segment slice."""
    g = _csr_group()
    plan = ap.plan_for_group(g, shards=2)
    seg = np.array([0, 0, 1, 3, 4, 4, 5, 6], np.int64)
    idxs = np.array([9, 2, 5, 0, 6, 1, 3, 4], np.int64)
    vals = np.arange(8, dtype=np.float32)
    routed = plan.route_csr_collective(_group_inputs(g, seg, idxs, vals))
    assert plan.seg_cap == 4            # 7 fused segments over 2 shards
    # same ownership oracle as test_route_csr_...: C=[5,4], base=[0,5]
    caps = np.array([5, 5, 5, 5, 4, 4, 4, 4], np.int64)
    base = np.array([0, 0, 0, 0, 5, 5, 5, 5], np.int64)
    want = {(int(b), int(b // plan.seg_cap), int(i // c), int(o + i % c),
             float(v))
            for b, i, c, o, v in zip(seg, idxs, caps, base, vals)}
    assert _unpack_lattice(routed, plan) == want
    # wire volume counts off-diagonal lookups only
    off_diag = sum(1 for (_, src, dst, _, _) in want if src != dst)
    assert routed["wire_nnz"] == off_diag
    assert routed["hot_nnz"] == 0 and routed["cold_nnz"] == 8
    # per-destination nnz agrees with the host route
    host = plan.route_csr(_group_inputs(g, seg, idxs, vals))
    assert routed["nnz"].tolist() == host["nnz"].tolist()


def test_route_csr_collective_hot_is_diagonal():
    """Hot lookups are served at their source shard under the collective
    exchange — the whole hot batch sits on the send-lattice diagonal and
    wire_nnz is zero."""
    g = _csr_group()
    plan = ap.plan_for_group(g, shards=2,
                             hot_rows={"a": tuple(range(10)),
                                       "b": tuple(range(7))})
    seg = np.array([0, 1, 4, 5], np.int64)
    idxs = np.array([3, 8, 2, 6], np.int64)
    routed = plan.route_csr_collective(_group_inputs(g, seg, idxs))
    assert routed["hot_nnz"] == 4 and routed["wire_nnz"] == 0
    for seg_, src, dst, _ in _unpack_lattice(routed, plan,
                                             need_vals=False):
        assert src == dst == seg_ // plan.seg_cap


def test_route_csr_collective_empty_and_boundary_buckets():
    from repro.core.capacity import collective_exchange_capacity
    g = _csr_group()
    plan = ap.plan_for_group(g, shards=2)
    empty = _group_inputs(g, np.zeros(0, np.int64), np.zeros(0, np.int64))
    routed = plan.route_csr_collective(empty)
    assert routed["cap"] == 1 and routed["max_lookups"] == 1
    assert routed["wire_nnz"] == 0
    ints = plan.packed_lattice(routed)["ints"]
    assert (ints[:, :, 0] == plan.num_segments).all()   # pad sentinel only
    # bucket boundary: a pair count exactly at the pow-2 edge keeps the
    # bucket; one more lookup doubles it
    assert collective_exchange_capacity([[4, 0], [0, 0]], [4]) == (4, 4)
    assert collective_exchange_capacity([[5, 0], [0, 0]], [5]) == (8, 6)
    # 4 lookups of segment 0 (source shard 0) all owned by shard 0 -> one
    # (0,0) pair of exactly 4 = the pow-2 edge
    seg = np.zeros(4, np.int64)
    idxs = np.array([0, 1, 2, 3], np.int64)
    vals = np.ones(4, np.float32)
    routed = plan.route_csr_collective(_group_inputs(g, seg, idxs, vals))
    assert routed["pair_counts"].tolist() == [[4, 0], [0, 0]]
    assert routed["cap"] == 4
    five = _group_inputs(g, np.zeros(5, np.int64),
                         np.array([0, 1, 2, 3, 4], np.int64),
                         np.ones(5, np.float32))
    assert plan.route_csr_collective(five)["cap"] == 8


def test_plan_single_row_vocab_slot():
    """A 1-row vocab splits into a 1-row cold slice on shard 0 and pure
    padding on shard 1; every lookup routes to shard 0."""
    prog = EmbeddingProgram("tiny", (
        ("one", EmbeddingOp("sls", 4, 1, 8, avg_lookups=2)),
        ("big", EmbeddingOp("sls", 3, 12, 8, avg_lookups=2)),
    ))
    units, _ = fuse_program(prog)
    (group,) = units
    plan = ap.plan_for_group(group, shards=2)
    assert plan.slots[0].cap == 1 and plan.slots[0].rows == 1
    seg = np.array([0, 2, 4], np.int64)     # two lookups of the 1-row slot
    idxs = np.array([0, 0, 5], np.int64)
    routed = plan.route_csr(_group_inputs(group, seg, idxs))
    host = {(int(routed["idxs"][k]), o)
            for o in range(2)
            for k in range(routed["bounds"][o], routed["bounds"][o + 1])}
    assert (plan.slots[0].cold_base, 0) in host
    coll = plan.route_csr_collective(_group_inputs(group, seg, idxs))
    for seg_, src, dst, local in _unpack_lattice(coll, plan,
                                                 need_vals=False):
        if seg_ in (0, 2):              # the 1-row slot's segments
            assert dst == 0 and local == plan.slots[0].cold_base
    # the stacked layout puts the single row on shard 0 only
    rng = np.random.default_rng(3)
    parts = [rng.standard_normal((1, 8)).astype(np.float32),
             rng.standard_normal((12, 8)).astype(np.float32)]
    glob = plan.stack_np(parts)
    np.testing.assert_array_equal(glob[plan.slots[0].cold_base], parts[0][0])


def test_plan_hot_covers_entire_slot():
    """hot_rows spanning a whole vocab leaves an empty cold tail: the cold
    slice degenerates to the 1-row padding cap, every lookup is hot, and
    routing still round-trips."""
    g = _csr_group()
    plan = ap.plan_for_group(g, shards=2,
                             hot_rows={"a": tuple(range(10))})
    s0 = plan.slots[0]
    assert s0.cold_rows == 0 and s0.hot_rows == 10
    assert s0.cap == 1                  # padding-only cold slice
    seg = np.array([0, 1, 2, 3], np.int64)
    idxs = np.array([7, 0, 9, 3], np.int64)
    routed = plan.route_csr(_group_inputs(g, seg, idxs))
    assert routed["hot_nnz"] == 4 and routed["cold_nnz"] == 0
    lo = s0.hot_base
    for o in range(2):
        a, b = routed["bounds"][o], routed["bounds"][o + 1]
        assert (routed["idxs"][a:b] >= lo).all()
    coll = plan.route_csr_collective(_group_inputs(g, seg, idxs))
    assert coll["wire_nnz"] == 0
    # the stacked table still replicates every row (as hot slab)
    rng = np.random.default_rng(4)
    parts = [rng.standard_normal((10, 8)).astype(np.float32),
             rng.standard_normal((7, 8)).astype(np.float32)]
    glob = plan.stack_np(parts)
    for sh in range(2):
        for pos, row in enumerate(s0.hot_ids):
            np.testing.assert_array_equal(
                glob[sh * plan.local_rows + s0.hot_base + pos],
                parts[0][row])


def test_route_gather_collective_round_trip():
    prog = EmbeddingProgram("gg", (
        ("g1", EmbeddingOp("gather", 3, 10, 8, block_rows=2)),
        ("g2", EmbeddingOp("gather", 3, 10, 8, block_rows=2)),
    ), shared_tables=(("g1", "g2"),))
    units, _ = fuse_program(prog)
    (group,) = units
    plan = ap.plan_for_group(group, shards=2)
    ins = {"g1": {"idxs": np.array([9, 0, 4], np.int64)},
           "g2": {"idxs": np.array([1, 6, 2], np.int64)}}
    routed = plan.route_gather_collective(ins)
    cap = plan.slots[0].cap
    base = plan.slots[0].cold_base
    want = set()
    for m, name in ((0, "g1"), (1, "g2")):
        for k, i in enumerate(ins[name]["idxs"]):
            seg = m * 3 + k
            want.add((seg, int(seg // plan.seg_cap), int(i // cap),
                      int(base + i % cap)))
    assert _unpack_lattice(routed, plan, need_vals=False) == want
    host = plan.route_gather(ins)
    assert routed["cold_segments"] == host["cold_segments"] == 6


def test_exchange_capacity_buckets():
    # pow-2 nnz bucket over the shard max; quarter-octave max_lookups —
    # the canonical policy of repro.core.capacity, re-exported by kernels
    from repro.core import capacity
    assert capacity.exchange_capacity is exchange_capacity  # ONE definition
    assert exchange_capacity([5, 3], [2, 9]) == (8, 12)
    assert exchange_capacity([0, 0], [0, 0]) == (1, 1)
    assert exchange_capacity([100, 1], [40, 1]) == (128, 48)


def test_hot_classification_from_traces():
    from repro.data.locality import classify_hot
    trace = np.array([5, 1, 5, 5, 2, 1, 9], np.int64)
    # row 5 reused twice, row 1 once, rows 2/9 never -> head = {5, 1}
    assert classify_hot(trace, 10, max_hot=2).tolist() == [1, 5]
    assert classify_hot(trace, 10, max_hot=1).tolist() == [5]
    assert classify_hot(np.arange(6), 10, max_hot=4).tolist() == []
    prog = EmbeddingProgram("p", (
        ("a", EmbeddingOp("sls", 4, 10, 8, avg_lookups=3)),))
    budget = cost_model.FusionBudget(shards=2, hot_slab_bytes=2 * 8 * 4)
    hot = ap.hot_rows_from_traces(prog, {"a": trace}, budget)
    assert hot == {"a": (1, 5)}
    assert ap.hot_rows_from_traces(
        prog, {"a": trace}, cost_model.FusionBudget(shards=2)) == {}


# ---------------------------------------------------------------------------
# Per-shard cost model
# ---------------------------------------------------------------------------

def test_fused_plan_resources_per_shard():
    ops = [EmbeddingOp("sls", 64, 4096, 64, avg_lookups=16)
           for _ in range(4)]
    r1 = cost_model.fused_plan_resources(ops, shards=1)
    r4 = cost_model.fused_plan_resources(ops, shards=4)
    assert r1["exchange_bytes"] == 0
    assert r4["exchange_bytes"] > 0
    assert r4["table_bytes_per_shard"] * 4 == r1["table_bytes"]
    assert r4["vmem_bytes"] < r1["vmem_bytes"]       # per-shard streams
    assert r4["tile_bytes"] == r1["tile_bytes"]      # tiles don't shard


def test_sharded_budget_splits_fewer_groups():
    prog = EmbeddingProgram("giant", tuple(
        (f"t{i}", EmbeddingOp("sls", 2000, 64, 16, avg_lookups=16))
        for i in range(8)))
    tight = cost_model.FusionBudget(vmem_bytes=400_000)
    units_repl, _ = fuse_program(prog, vlen=128, budget=tight)
    sharded = cost_model.FusionBudget(vmem_bytes=400_000, shards=8)
    units_shrd, _ = fuse_program(prog, vlen=128, budget=sharded)
    n_repl = len(units_repl)
    n_shrd = len(units_shrd)
    assert n_shrd < n_repl, (n_shrd, n_repl)  # per-shard budget: less split
    for u in units_shrd:
        if isinstance(u, FusedGroup):
            assert cost_model.fits_budget(u.member_ops, 128, sharded)


def test_budget_shards_in_compile_and_executor_cache_keys():
    clear_executor_cache()
    prog = EmbeddingProgram("p", (("a", EmbeddingOp("sls", 4, 9, 8)),))
    b1 = cost_model.FusionBudget()
    b2 = cost_model.FusionBudget(shards=2)
    r1 = compile_program(prog, "O1", vlen=4, budget=b1)
    r2 = compile_program(prog, "O1", vlen=4, budget=b2)
    assert not r2.cache_hit                    # distinct cache entries
    executor_for(prog, "O1", vlen=4, budget=b1)
    by = executor_cache_stats()["entries_by_shards"]
    assert by.get(1, 0) >= 1
    clear_executor_cache()


# ---------------------------------------------------------------------------
# Mesh of size 1 == the single-device executor, bit for bit
# ---------------------------------------------------------------------------

def test_size_one_mesh_is_single_device_path():
    import jax
    from repro.launch.mesh import axis_types_kw
    mesh = jax.make_mesh((1, 1), ("data", "model"), **axis_types_kw(2))
    prog = EmbeddingProgram("p", (
        ("a", EmbeddingOp("sls", 4, 9, 8, avg_lookups=3)),
        ("b", EmbeddingOp("sls", 3, 7, 8, avg_lookups=2)),
    ))
    pres = compile_program(prog, "O3", vlen=4, use_cache=False)
    ex_plain = ProgramExecutor(pres)
    ex_mesh = ProgramExecutor(pres, mesh=mesh)
    assert ex_mesh.shards == 1 and ex_mesh.mesh is None
    ins = make_program_inputs(prog, seed=0)
    got_p, got_m = ex_plain.step(ins), ex_mesh.step(ins)
    for n in got_p:
        np.testing.assert_array_equal(np.asarray(got_p[n]),
                                      np.asarray(got_m[n]))
    assert ex_plain.stats == ex_mesh.stats
    # executor_for canonicalizes the 1-wide mesh to the replicated key;
    # hot_rows are dropped on the single-device path (nothing to exchange)
    clear_executor_cache()
    e1 = executor_for(prog, "O3", vlen=4)
    e2 = executor_for(prog, "O3", vlen=4, mesh=mesh)
    e3 = executor_for(prog, "O3", vlen=4, mesh=mesh, hot_rows={"a": (0, 1)})
    assert e2 is e1 and e3 is e1
    clear_executor_cache()


def test_shard_count_helper():
    import jax
    from repro.launch.mesh import axis_types_kw, model_shard_count
    assert sp.shard_count(None) == 1
    assert model_shard_count(None) == 1
    mesh = jax.make_mesh((1,), ("data",), **axis_types_kw(1))
    assert sp.shard_count(mesh, "model") == 1   # axis absent


# ---------------------------------------------------------------------------
# End-to-end on a real 2-device mesh (subprocess; test_launch pattern)
# ---------------------------------------------------------------------------

def test_shard_stack_tables_matches_plan_layout_on_a_mesh(run_on_mesh):
    """The device stack equals the plan's numpy oracle on a (data 2,
    model 2) mesh, hot slab or not, and every device holds only its own
    shard's rows (replicated over ``data``)."""
    code = """
        import jax
        import numpy as np
        from repro.core import access_plan as ap, shard_plan as sp
        from repro.core.ops import EmbeddingOp, EmbeddingProgram
        from repro.core.passes import fuse_program
        from repro.launch.mesh import axis_types_kw
        mesh = jax.make_mesh((2, 2), ("data", "model"), **axis_types_kw(2))
        prog = EmbeddingProgram("g", (
            ("a", EmbeddingOp("sls", 4, 10, 8, avg_lookups=3)),
            ("b", EmbeddingOp("sls", 3, 7, 8, avg_lookups=2)),
        ))
        (group,), _ = fuse_program(prog)
        rng = np.random.default_rng(0)
        parts = [rng.standard_normal((10, 8)).astype(np.float32),
                 rng.standard_normal((7, 8)).astype(np.float32)]
        for hot in (None, {"a": (2, 7), "b": (0,)}):
            plan = ap.plan_for_group(group, shards=2, hot_rows=hot)
            got = sp.shard_stack_tables(parts, plan, mesh, "model")
            want = plan.stack_np(parts)
            np.testing.assert_array_equal(np.asarray(got), want)
            L = plan.local_rows
            assert len(got.addressable_shards) == 4
            for s in got.addressable_shards:
                k = s.index[0].start // L
                np.testing.assert_array_equal(np.asarray(s.data),
                                              want[k * L:(k + 1) * L])
        print("STACK_OK")
    """
    run_on_mesh(code, devices=4, sentinel="STACK_OK")


def test_sharded_executor_two_devices(run_on_mesh):
    code = """
        import jax
        import numpy as np
        from repro.core import cost_model
        from repro.core.executor import (ProgramExecutor,
                                         clear_executor_cache, executor_for)
        from repro.core.ops import (EmbeddingOp, EmbeddingProgram, Semiring,
                                    make_program_inputs, program_reference)
        from repro.core.pipeline import compile_program
        from repro.launch.mesh import axis_types_kw, model_shard_count

        mesh = jax.make_mesh((1, 2), ("data", "model"), **axis_types_kw(2))
        assert model_shard_count(mesh) == 2

        # weighted + unweighted + kg fused CSR, shared-table gather group,
        # and an unfusable singleton — the full fusion surface, sharded
        prog = EmbeddingProgram("mixed", (
            ("w", EmbeddingOp("sls", 5, 9, 8, avg_lookups=3, weighted=True)),
            ("u", EmbeddingOp("sls", 4, 7, 8, avg_lookups=2)),
            ("k", EmbeddingOp("kg", 6, 11, 8)),
            ("g1", EmbeddingOp("gather", 6, 20, 8)),
            ("g2", EmbeddingOp("gather", 6, 20, 8)),
            ("solo", EmbeddingOp("spmm", 3, 5, 16, avg_lookups=2)),
        ), shared_tables=(("g1", "g2"),))

        for backend in ("jax", "pallas"):
            pres = compile_program(prog, "O3", vlen=4, use_cache=False)
            ex = ProgramExecutor(pres, backend=backend, mesh=mesh)
            assert ex.shards == 2
            base = make_program_inputs(prog, seed=0)
            for seed in (0, 3):
                ins = make_program_inputs(prog, seed=seed)
                for n in ins:        # steady tables, fresh index streams
                    for k in ("table", "x"):
                        if k in base[n]:
                            ins[n][k] = base[n][k]
                got = ex.step(ins)
                want = program_reference(prog, ins)
                for n in want:
                    np.testing.assert_allclose(
                        np.asarray(got[n]), want[n], rtol=1e-5, atol=1e-5,
                        err_msg=f"{n} {backend}")
            assert ex.stats["table_rebinds"] == 0
            assert ex.stats["exchange_index_bytes"] > 0
            # footprint: each device holds ~half of each fused stack
            for u in ex._units:
                if u.group is None:
                    continue
                shards_b = [s.data.nbytes
                            for s in u.table.addressable_shards]
                assert len(shards_b) == 2 and shards_b[0] == shards_b[1]

        # max-semiring fused group (sls + kg) with an empty shard: the
        # cross-shard pmax merge must keep identity/zero conventions exact
        prog2 = EmbeddingProgram("maxmix", (
            ("a", EmbeddingOp("sls", 4, 8, 8, avg_lookups=3,
                              semiring=Semiring("max"))),
            ("m", EmbeddingOp("kg", 4, 8, 8, semiring=Semiring("max"))),
        ))
        pres2 = compile_program(prog2, "O3", vlen=4, use_cache=False)
        for backend in ("jax", "pallas"):
            ex2 = ProgramExecutor(pres2, backend=backend, mesh=mesh)
            ins = make_program_inputs(prog2, seed=1)
            for n in ("a", "m"):
                ins[n]["idxs"] = np.minimum(ins[n]["idxs"], 3)  # shard 1 idle
            got = ex2.step(ins)
            for n, w in program_reference(prog2, ins).items():
                np.testing.assert_allclose(np.asarray(got[n]), w,
                                           rtol=1e-5, atol=1e-5,
                                           err_msg=f"{n} {backend} max")

        # hot/cold sharding end-to-end: classified Zipf head replicated,
        # numerics identical, hot lookups measurably skip the exchange
        from repro.core import access_plan as apm
        progh = EmbeddingProgram("hot", (
            ("a", EmbeddingOp("sls", 6, 32, 8, avg_lookups=4)),
            ("b", EmbeddingOp("sls", 5, 24, 8, avg_lookups=3)),
        ))
        insh = make_program_inputs(progh, seed=2, alpha=1.2)
        traces = {n: np.asarray(insh[n]["idxs"]) for n in ("a", "b")}
        budget_h = cost_model.FusionBudget(shards=2,
                                           hot_slab_bytes=8 * 8 * 4)
        hot = apm.hot_rows_from_traces(progh, traces, budget_h)
        assert hot, "Zipf trace must classify a hot head"
        for backend in ("jax", "pallas"):
            presh = compile_program(progh, "O3", vlen=4, use_cache=False,
                                    budget=budget_h, hot_rows=hot)
            exh = ProgramExecutor(presh, backend=backend, mesh=mesh,
                                  hot_rows=hot)
            got = exh.step(insh)
            for n, w in program_reference(progh, insh).items():
                np.testing.assert_allclose(np.asarray(got[n]), w,
                                           rtol=1e-5, atol=1e-5,
                                           err_msg=f"{n} {backend} hot")
            assert exh.stats["hot_lookups"] > 0
            aps = exh.access_plan_stats()
            assert aps["hot_rows"] > 0 and aps["hot_slab_bytes"] > 0
            # vs the interleaved executor on the SAME step: fewer routed
            # bytes, identical outputs
            exi = ProgramExecutor(compile_program(progh, "O3", vlen=4,
                                                  use_cache=False),
                                  backend=backend, mesh=mesh)
            goti = exi.step(insh)
            for n in got:
                np.testing.assert_allclose(np.asarray(got[n]),
                                           np.asarray(goti[n]),
                                           rtol=1e-5, atol=1e-5)
            assert exh.stats["exchange_index_bytes"] < \
                exi.stats["exchange_index_bytes"]

            # batch entirely in the hot slab: zero exchange for the step
            all_hot = {n: dict(insh[n]) for n in insh}
            for n, ids in hot.items():
                pool = np.asarray(ids)
                take = all_hot[n]["idxs"]
                all_hot[n]["idxs"] = pool[
                    np.arange(len(take)) % len(pool)].astype(take.dtype)
            before = exh.stats["exchange_index_bytes"]
            goth = exh.step(all_hot)
            assert exh.stats["exchange_index_bytes"] == before, \
                "all-hot batch must not route any index"
            for n, w in program_reference(progh, all_hot).items():
                np.testing.assert_allclose(np.asarray(goth[n]), w,
                                           rtol=1e-5, atol=1e-5)

            # empty step: zero-nnz CSR on every member is a valid no-op
            empty = {n: dict(insh[n]) for n in insh}
            for n in empty:
                empty[n]["ptrs"] = np.zeros_like(empty[n]["ptrs"])
                empty[n]["idxs"] = empty[n]["idxs"][:0]
            gote = exh.step(empty)
            for n, w in program_reference(progh, empty).items():
                np.testing.assert_allclose(np.asarray(gote[n]), w,
                                           rtol=1e-5, atol=1e-5)

        # max semiring + hot slab, batch entirely COLD: the pmax merge must
        # keep identity/zero conventions exact when the slab sees no traffic
        progm = EmbeddingProgram("maxcold", (
            ("a", EmbeddingOp("sls", 4, 16, 8, avg_lookups=3,
                              semiring=Semiring("max"))),
            ("m", EmbeddingOp("kg", 4, 16, 8, semiring=Semiring("max"))),
        ))
        hotm = {"a": (0, 1, 2, 3), "m": (0, 1)}
        insm = make_program_inputs(progm, seed=4)
        for n in ("a", "m"):   # batch entirely cold: rows 4.. only
            insm[n]["idxs"] = 4 + (np.asarray(insm[n]["idxs"]) % 12)
        for backend in ("jax", "pallas"):
            presm = compile_program(
                progm, "O3", vlen=4, use_cache=False,
                budget=cost_model.FusionBudget(shards=2,
                                               hot_slab_bytes=4 * 8 * 4),
                hot_rows=hotm)
            assert presm.units[0].fused
            exm = ProgramExecutor(presm, backend=backend, mesh=mesh,
                                  hot_rows=hotm)
            gotm = exm.step(insm)
            for n, w in program_reference(progm, insm).items():
                np.testing.assert_allclose(np.asarray(gotm[n]), w,
                                           rtol=1e-5, atol=1e-5,
                                           err_msg=f"{n} {backend} maxcold")
            assert exm.stats["hot_lookups"] == 0
            assert exm.stats["cold_lookups"] > 0

        # sharded update_tables: device-side re-stack of the sharded layout
        prog3 = EmbeddingProgram("upd", (
            ("a", EmbeddingOp("sls", 4, 10, 8, avg_lookups=3)),
            ("b", EmbeddingOp("sls", 3, 7, 8, avg_lookups=2)),
        ))
        ex3 = ProgramExecutor(compile_program(prog3, "O3", vlen=4,
                                              use_cache=False),
                              backend="jax", mesh=mesh)
        ex3.step(make_program_inputs(prog3, seed=0))
        new = make_program_inputs(prog3, seed=7)
        ex3.update_tables(new)
        assert ex3.stats["table_restacks"] == 1
        got = ex3.step(new)
        for n, w in program_reference(prog3, new).items():
            np.testing.assert_allclose(np.asarray(got[n]), w,
                                       rtol=1e-5, atol=1e-5)

        # executor_for: sharded and replicated executors never collide
        clear_executor_cache()
        e_repl = executor_for(prog3, "O3", vlen=4, backend="jax")
        e_shrd = executor_for(prog3, "O3", vlen=4, backend="jax", mesh=mesh)
        assert e_repl is not e_shrd and e_shrd.shards == 2
        assert e_shrd.compiled.units[0].result.op is not None
        assert executor_for(prog3, "O3", vlen=4, backend="jax",
                            mesh=mesh) is e_shrd
        # exchange mode + output placement are cache-key components too
        e_coll = executor_for(prog3, "O3", vlen=4, backend="jax",
                              mesh=mesh, exchange="collective")
        e_host = executor_for(prog3, "O3", vlen=4, backend="jax",
                              mesh=mesh, exchange="host")
        e_esc = executor_for(prog3, "O3", vlen=4, backend="jax",
                             mesh=mesh, exchange="collective",
                             replicate_outputs=True)
        assert e_coll is e_shrd            # collective is the mesh default
        assert e_host is not e_coll and e_esc is not e_coll
        assert e_coll.replicate_outputs is False
        assert e_host.replicate_outputs is True
        print("SHARDED_EXEC_OK")
    """
    run_on_mesh(code, devices=2, sentinel="SHARDED_EXEC_OK")


def test_exchange_edge_cases_two_devices(run_on_mesh):
    """The exchange edge cases of both exchange modes, end-to-end: zero-nnz
    step, every-segment-empty under the max semiring (⊕-identity across the
    merge), single-row vocab slot, hot set covering an entire slot, and the
    bucket-boundary step (nnz exactly at a pow-2 capacity edge) — each
    checked against the numpy program reference on both backends, with
    reduce-scattered AND replicated outputs."""
    code = """
        import jax
        import numpy as np
        from repro.core.executor import ProgramExecutor
        from repro.core.ops import (EmbeddingOp, EmbeddingProgram, Semiring,
                                    make_program_inputs, program_reference)
        from repro.core.pipeline import compile_program
        from repro.launch.mesh import axis_types_kw

        mesh = jax.make_mesh((1, 2), ("data", "model"), **axis_types_kw(2))

        def check(ex, prog, ins, tag):
            got = ex.step(ins)
            for n, w in program_reference(prog, ins).items():
                np.testing.assert_allclose(
                    np.asarray(got[n]), w, rtol=1e-5, atol=1e-5,
                    err_msg=f"{n} {tag}")

        def sweep(prog, steps, tag, hot_rows=None):
            pres = compile_program(prog, "O3", vlen=4, use_cache=False)
            for backend in ("jax", "pallas"):
                for exchange in ("host", "collective"):
                    for repl in (True, False):
                        ex = ProgramExecutor(
                            pres, backend=backend, mesh=mesh,
                            exchange=exchange, replicate_outputs=repl,
                            hot_rows=hot_rows)
                        for k, ins in enumerate(steps):
                            check(ex, prog, ins,
                                  f"{tag} {backend} {exchange} repl={repl} "
                                  f"step{k}")

        # --- zero-nnz step + every-segment-empty under pmax ---
        progm = EmbeddingProgram("maxempty", (
            ("a", EmbeddingOp("sls", 4, 12, 8, avg_lookups=3,
                              semiring=Semiring("max"))),
            ("b", EmbeddingOp("sls", 3, 9, 8, avg_lookups=2,
                              semiring=Semiring("max"))),
        ))
        full = make_program_inputs(progm, seed=0)
        empty = {n: dict(full[n]) for n in full}
        for n in empty:
            empty[n]["ptrs"] = np.zeros_like(empty[n]["ptrs"])
            empty[n]["idxs"] = empty[n]["idxs"][:0]
        # all-empty first (the ⊕-identity-only merge), then a real step on
        # the SAME executors' trace caches
        sweep(progm, [empty, full, empty], "pmax-empty")

        # --- zero-nnz step, add semiring, weighted group ---
        progw = EmbeddingProgram("wempty", (
            ("w", EmbeddingOp("sls", 4, 10, 8, avg_lookups=3,
                              weighted=True)),
            ("u", EmbeddingOp("sls", 3, 7, 8, avg_lookups=2)),
        ))
        fullw = make_program_inputs(progw, seed=1)
        emptyw = {n: dict(fullw[n]) for n in fullw}
        for n in emptyw:
            emptyw[n]["ptrs"] = np.zeros_like(emptyw[n]["ptrs"])
            emptyw[n]["idxs"] = emptyw[n]["idxs"][:0]
            if "vals" in emptyw[n]:
                emptyw[n]["vals"] = emptyw[n]["vals"][:0]
        sweep(progw, [emptyw, fullw], "add-empty")

        # --- single-row vocab slot ---
        prog1 = EmbeddingProgram("tiny", (
            ("one", EmbeddingOp("sls", 4, 1, 8, avg_lookups=2)),
            ("big", EmbeddingOp("sls", 3, 12, 8, avg_lookups=2)),
        ))
        ins1 = make_program_inputs(prog1, seed=2)
        sweep(prog1, [ins1], "single-row")

        # --- hot set covering an entire slot ---
        progh = EmbeddingProgram("allhot", (
            ("a", EmbeddingOp("sls", 4, 8, 8, avg_lookups=3)),
            ("b", EmbeddingOp("sls", 3, 10, 8, avg_lookups=2)),
        ))
        insh = make_program_inputs(progh, seed=3)
        hot = {"a": tuple(range(8))}
        sweep(progh, [insh], "full-hot-slot", hot_rows=hot)

        # --- bucket-boundary step: fused nnz exactly at a pow-2 edge ---
        progb = EmbeddingProgram("edge", (
            ("a", EmbeddingOp("sls", 4, 16, 8, avg_lookups=4)),
            ("b", EmbeddingOp("sls", 4, 10, 8, avg_lookups=4)),
        ))
        insb = make_program_inputs(progb, seed=4)
        rng = np.random.default_rng(5)
        for n, rows in (("a", 16), ("b", 10)):         # fused nnz = 16 = 2^4
            insb[n]["ptrs"] = np.array([0, 2, 4, 6, 8], np.int64)
            insb[n]["idxs"] = rng.integers(0, rows, 8).astype(np.int32)
        plus = {n: dict(insb[n]) for n in insb}        # nnz = 17: next bucket
        plus["a"]["ptrs"] = np.array([0, 3, 5, 7, 9], np.int64)
        plus["a"]["idxs"] = rng.integers(0, 16, 9).astype(np.int32)
        sweep(progb, [insb, plus], "bucket-edge")
        print("EDGE_CASES_OK")
    """
    run_on_mesh(code, devices=2, sentinel="EDGE_CASES_OK")
