"""The plain references against the program at a tiny size on the CPU
(Pallas kernels interpreted), the weights and tables made again from the
seed, the traffic generator, and the byte and FLOP counts on hand-counted
cases."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import counts, generate  # noqa: E402
from chipbench.harness import load_module, seed_key  # noqa: E402

REFS = ROOT / "chipbench" / "references"
DRIVERS = ROOT / "chipbench" / "drivers"

TINY_LM = {"name": "tiny", "family": "dense", "num_layers": 2,
           "d_model": 64, "num_heads": 4, "num_kv_heads": 4, "d_ff": 128,
           "vocab_size": 256, "block_pattern": ["dense"],
           "rotary_pct": 0.25, "rope_theta": 10000.0, "norm_eps": 1e-5,
           "dtype": "float32", "attn_chunk": 8}


def test_sls_reference_matches_the_executor():
    from repro.core.executor import executor_for
    drv = load_module(DRIVERS / "pooled_lookup.py")
    ref = load_module(REFS / "sls_numpy.py")
    rows, bags, width, batch = [50, 7, 300], [3, 1, 6], 128, 8
    program = drv.program_for(rows, bags, width, batch)
    key = seed_key(2 ** 40 + 3)
    tables = drv.table_maker(rows, width)(key)
    pool = generate.pooled_batches(
        rows, bags, batch, {"kind": "pooled_lookups", "alpha": 0.8,
                            "pool": 1}, seed=5)[0]
    names = drv.table_names(len(rows))
    got = executor_for(program).step(
        {n: {"table": tables[t], "ptrs": pool[t][0], "idxs": pool[t][1]}
         for t, n in enumerate(names)})
    for t, n in enumerate(names):
        want = ref.pool(np.asarray(tables[t]), *pool[t])
        assert ref.max_rel_err(np.asarray(got[n]), want) < 1e-6


def test_sls_reference_pools_by_hand():
    ref = load_module(REFS / "sls_numpy.py")
    rows = np.arange(12, dtype=np.float32).reshape(4, 3)
    out = ref.pool(rows, np.array([0, 2, 2, 3]), np.array([1, 3, 0]))
    np.testing.assert_array_equal(out, [[12, 14, 16], [0, 0, 0],
                                        [0, 1, 2]])
    assert ref.max_rel_err(out + 1, out) > 0.5
    assert ref.max_rel_err(out[:2], out) == float("inf")


def test_one_table_is_made_again_exactly():
    drv = load_module(DRIVERS / "pooled_lookup.py")
    key = seed_key(9)
    rows = [17, 3, 40]
    all_tables = drv.table_maker(rows, 128)(key)
    for i, n in enumerate(rows):
        np.testing.assert_array_equal(
            np.asarray(all_tables[i]),
            np.asarray(drv.one_table(key, i, n, 128)))


def test_one_layer_is_made_again_exactly():
    ref = load_module(REFS / "lm_dense.py")
    key = seed_key(2 ** 35 + 1)
    params = ref.init_params(TINY_LM, key, 512)
    layer_fn = ref._layer_fn(ref._items(TINY_LM), None)
    for l in range(TINY_LM["num_layers"]):
        one = layer_fn(key, l)
        np.testing.assert_array_equal(
            np.asarray(params["scan"][0]["attn"]["wq"][l]), one["wq"])
        np.testing.assert_array_equal(
            np.asarray(params["scan"][0]["mlp"]["wo"][l]), one["wo_mlp"])
    assert params["embed"].shape == (512, 64)
    assert not np.asarray(params["embed"][256:]).any()


def test_lm_reference_agrees_with_served_tokens():
    """Greedy tokens served by DecodeServer (prefill replay, then decode
    through the KV cache) are the reference's best at every position."""
    from repro.models import LM
    from repro.runtime.server import DecodeServer, Request
    ref = load_module(REFS / "lm_dense.py")
    drv = load_module(DRIVERS / "lm_serving.py")
    mcfg = drv.model_config(TINY_LM)
    key = seed_key(123)
    params = ref.init_params(TINY_LM, key, mcfg.padded_vocab)
    srv = DecodeServer(LM(mcfg), params, batch_slots=2, max_len=64,
                       prefill_chunk=8)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, 256, n).astype(np.int32),
                    max_new_tokens=m) for n, m in ((11, 9), (5, 14))]
    for r in reqs:
        srv.submit(r)
    srv.run_until_drained()
    assert all(r.status == "ok" for r in reqs)
    tokens, tok, ask = drv.check_batch(reqs)
    gaps = ref.token_gaps(TINY_LM, key, tokens, tok, ask)
    assert ask.sum() == 23
    assert gaps[ask].max() < 1e-3
    # a wrong token lies well below the best
    wrong = np.where(ask, (tok + 1) % 256, tok)
    assert ref.token_gaps(TINY_LM, key, tokens, wrong, ask)[ask].max() > 0.05


def test_least_bytes_by_hand():
    # rows 5 and 7 are read once each, 2 pooled rows, 3 + 3 index words
    got = counts.sls_least_bytes(np.array([0, 2, 3]), np.array([5, 5, 7]),
                                 512, 512)
    assert got == 2 * 512 + 2 * 512 + 6 * 4
    assert counts.sls_flops(3, 128) == 384


def test_lm_flops_by_hand():
    cfg = {"d_model": 4, "num_heads": 2, "num_kv_heads": 2, "d_ff": 8,
           "num_layers": 1, "vocab_size": 10}
    # attention 16 + 32 + 16, MLP 96, head 40
    assert counts.dense_lm_matmul_params(cfg) == 200
    # 3 tokens: 2 * 200 * 3, plus 4 * layers * heads * head_dim * 6
    assert counts.dense_lm_flops(cfg, 3, 6) == 1200 + 96


def test_request_lengths_are_the_same_work_for_every_seed():
    traffic = {"kind": "lm_requests", "pool": 32,
               "prompt": {"median": 256, "sigma": 0.8, "min": 32,
                          "max": 1024},
               "output": {"median": 128, "sigma": 0.7, "min": 16,
                          "max": 512}}
    a = generate.lm_requests(traffic, 50304, 2 ** 33 + 7)
    b = generate.lm_requests(traffic, 50304, 11)
    assert [len(p) for p, _ in a] == [len(p) for p, _ in b]
    assert [m for _, m in a] == [m for _, m in b]
    assert not all((p == q).all() for (p, _), (q, _) in zip(a, b))
    assert all(32 <= len(p) <= 1024 and 16 <= m <= 512 for p, m in a)
    again = generate.lm_requests(traffic, 50304, 2 ** 33 + 7)
    assert all((p == q).all() and m == n
               for (p, m), (q, n) in zip(a, again))


def test_every_stretch_of_requests_spans_both_length_distributions():
    """Any 8 requests in a row hold prompts on both sides of the median
    and in both outer quartiles, and so do their outputs."""
    spec = {"median": 64, "sigma": 0.5, "min": 16, "max": 256}
    for base in (2, 3):
        x = generate.lognormal_lengths(spec, base, 64)
        assert np.median(x) == pytest.approx(64, abs=2)
        q1, q3 = np.exp(np.log(64) + 0.5 * np.array([-0.6745, 0.6745]))
        for k in range(0, 64 - 8):
            run = x[k:k + 8]
            assert run.min() < q1 and run.max() > q3, (base, k, run)
    assert generate.radical_inverse(6, 2) == 0.375
    assert generate.radical_inverse(5, 3) == pytest.approx(7 / 9)


@pytest.mark.parametrize("alpha", [0.0, 0.8, 1.4])
def test_zipf_keys_in_range_and_skewed(alpha):
    keys = generate.ZipfKeys(1000, alpha, np.random.default_rng(1))
    x = keys.draw(20000)
    assert x.min() >= 0 and x.max() < 1000
    top = np.bincount(x, minlength=1000).max() / len(x)
    # the head row's share is 1 / sum(r ** -alpha)
    want = 1.0 / (np.arange(1, 1001, dtype=float) ** -alpha).sum()
    if alpha == 0:
        assert top < 3 * want
    else:
        assert top == pytest.approx(want, rel=0.1)
