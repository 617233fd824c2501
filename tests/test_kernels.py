"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret
mode — the kernels are TPU targets validated under the Pallas interpreter)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.sls import max_lookups_of

RNG = np.random.default_rng(7)


def _csr(b, n, avg, with_empty=True):
    lens = RNG.poisson(avg, b)
    if with_empty and b > 1:
        lens[0] = 0
    ptrs = np.zeros(b + 1, np.int32)
    np.cumsum(lens, out=ptrs[1:])
    idxs = RNG.integers(0, n, int(ptrs[-1])).astype(np.int32)
    return ptrs, idxs


@pytest.mark.parametrize("b,n,e", [(6, 13, 10), (4, 9, 200), (3, 40, 33),
                                   (8, 64, 128), (1, 5, 1)])
@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("dtype", [np.float32])
def test_sls_shapes(b, n, e, weighted, dtype):
    ptrs, idxs = _csr(b, n, 4)
    table = RNG.standard_normal((n, e)).astype(dtype)
    w = RNG.standard_normal(len(idxs)).astype(dtype) if weighted else None
    want = ref.sls(table, idxs, ref.csr_to_lookups(ptrs), w, num_segments=b)
    got = ops.sls(table, jnp.asarray(ptrs), jnp.asarray(idxs),
                  None if w is None else jnp.asarray(w),
                  num_segments=b, max_lookups=max_lookups_of(ptrs),
                  interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("add_op", ["add", "max", "min"])
def test_sls_semirings(add_op):
    b, n, e = 5, 11, 36
    ptrs, idxs = _csr(b, n, 3)
    table = RNG.standard_normal((n, e)).astype(np.float32)
    want = ref.sls(table, idxs, ref.csr_to_lookups(ptrs), None,
                   num_segments=b, add_op=add_op)
    got = ops.sls(table, jnp.asarray(ptrs), jnp.asarray(idxs), None,
                  num_segments=b, max_lookups=max_lookups_of(ptrs),
                  add_op=add_op, interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_sls_bf16():
    b, n, e = 4, 16, 130
    ptrs, idxs = _csr(b, n, 3)
    table = (RNG.standard_normal((n, e)) * 0.5).astype(jnp.bfloat16)
    want = ref.sls(jnp.asarray(table), jnp.asarray(idxs),
                   jnp.asarray(ref.csr_to_lookups(ptrs)), None,
                   num_segments=b)
    got = ops.sls(jnp.asarray(table), jnp.asarray(ptrs), jnp.asarray(idxs),
                  None, num_segments=b, max_lookups=max_lookups_of(ptrs),
                  interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("g,n,r,e", [(5, 9, 2, 10), (7, 4, 1, 130),
                                     (3, 6, 8, 64), (1, 2, 4, 256)])
def test_block_gather(g, n, r, e):
    table = RNG.standard_normal((n * r, e)).astype(np.float32)
    idxs = RNG.integers(0, n, g).astype(np.int32)
    want = ref.block_gather(table, idxs, block_rows=r)
    got = ops.block_gather(jnp.asarray(table), jnp.asarray(idxs),
                           block_rows=r, interpret=True)
    np.testing.assert_allclose(got, want)


@pytest.mark.parametrize("b,avg,e", [(5, 3, 10), (4, 2, 64), (6, 4, 33)])
def test_fusedmm(b, avg, e):
    ptrs, idxs = _csr(b, b, avg)
    x = RNG.standard_normal((b, e)).astype(np.float32)
    want = ref.fusedmm(x, idxs, ref.csr_to_lookups(ptrs), num_segments=b)
    got = ops.fusedmm(jnp.asarray(x), jnp.asarray(ptrs), jnp.asarray(idxs),
                      num_segments=b, max_lookups=max_lookups_of(ptrs),
                      interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("weighted", [False, True])
def test_sls_split_launches(monkeypatch, weighted):
    """A step whose scalar-prefetched CSR streams overflow one launch's SMEM
    budget is split into segment chunks; the pooled rows are unchanged."""
    from repro.kernels import rowdma
    monkeypatch.setattr(rowdma, "SMEM_BUDGET", 1280)
    b, n, e = 80, 57, 128
    ptrs, idxs = _csr(b, n, 5)
    base = RNG.integers(0, 3, b).astype(np.int32)
    table = RNG.standard_normal((n + 3, e)).astype(np.float32)
    w = RNG.standard_normal(len(idxs)).astype(np.float32) if weighted \
        else None
    seg = ref.csr_to_lookups(ptrs)
    want = ref.sls(table, idxs + base[seg], seg, w, num_segments=b)
    got = ops.sls(jnp.asarray(table), jnp.asarray(ptrs), jnp.asarray(idxs),
                  None if w is None else jnp.asarray(w), num_segments=b,
                  max_lookups=max_lookups_of(ptrs),
                  seg_base=jnp.asarray(base), interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_fusedmm_split_launches(monkeypatch):
    from repro.kernels import rowdma
    monkeypatch.setattr(rowdma, "SMEM_BUDGET", 512)
    b, e = 37, 128
    ptrs, idxs = _csr(b, b, 4)
    x = RNG.standard_normal((b, e)).astype(np.float32)
    want = ref.fusedmm(x, idxs, ref.csr_to_lookups(ptrs), num_segments=b)
    got = ops.fusedmm(jnp.asarray(x), jnp.asarray(ptrs), jnp.asarray(idxs),
                      num_segments=b, max_lookups=max_lookups_of(ptrs),
                      interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_block_gather_split_launches(monkeypatch):
    from repro.kernels import rowdma
    monkeypatch.setattr(rowdma, "SMEM_BUDGET", 64)
    table = RNG.standard_normal((29, 128)).astype(np.float32)
    idxs = RNG.integers(0, 29, 45).astype(np.int32)
    got = ops.block_gather(jnp.asarray(table), jnp.asarray(idxs),
                           interpret=True)
    np.testing.assert_array_equal(got, ref.block_gather(table, idxs))


@pytest.mark.parametrize("bh,s,d,causal", [(2, 256, 64, True),
                                           (3, 128, 128, False),
                                           (1, 512, 64, True)])
def test_flash_attention(bh, s, d, causal):
    q, k, v = [RNG.standard_normal((bh, s, d)).astype(np.float32)
               for _ in range(3)]
    want = ref.attention_reference(jnp.asarray(q)[:, :, None, :],
                                   jnp.asarray(k)[:, :, None, :],
                                   jnp.asarray(v)[:, :, None, :],
                                   causal=causal)[:, :, 0, :]
    got = ops.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        causal=causal, block_q=64, block_k=64,
                        interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_compiler_pallas_backend_matches_reference():
    """End-to-end: emberc O3 → KernelPlan → Pallas kernel == numpy ref."""
    from repro.core.backend_pallas import execute, make_plan
    from repro.core.ops import EmbeddingOp, make_inputs, reference
    from repro.core.pipeline import compile_op
    for kind in ["sls", "kg", "gather", "spmm", "fusedmm"]:
        op = EmbeddingOp(kind=kind, num_segments=5, num_embeddings=11,
                         emb_len=12, avg_lookups=3,
                         block_rows=2 if kind == "gather" else 1,
                         weighted=(kind == "sls"))
        ins = make_inputs(op, seed=9)
        res = compile_op(op, "O3")
        plan = make_plan(res)
        assert plan.col_tile % 128 == 0
        got = execute(res, ins)
        np.testing.assert_allclose(np.asarray(got), reference(op, ins),
                                   rtol=1e-4, atol=1e-4)
