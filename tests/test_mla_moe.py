"""DeepSeek-V2-Lite's blocks against the plain float32 reference
(``chipbench/references/lm_mla_moe.py``) on seeded random weights, at the
``reduced()`` size: MLA with its latent norm and YaRN, the leading dense
layer, the expert share with its counters, and the untied head, served
through ``DecodeServer``'s cache."""
import dataclasses
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.harness import load_module, seed_key
from repro.configs import get_config, get_reduced
from repro.models import LM
from repro.models import moe as moe_mod
from repro.models.attention import mla_scale
from repro.models.common import yarn_inv_freq, yarn_rope
from repro.runtime.server import DecodeServer, Request

REF = load_module(Path(__file__).resolve().parents[1] / "chipbench"
                  / "references" / "lm_mla_moe.py")
ARCH = "deepseek-v2-lite-16b"
#: float32 on both sides: the program attends in latent space and adds the
#: experts' parts in another order than the reference, so logits differ by
#: rounding alone (under 1e-5); a dropped mscale², latent norm or YaRN
#: ramp, or renormalised router weights, each moves them past it
LOGIT_TOL = 1e-4


def ref_cfg(cfg) -> dict:
    return dataclasses.asdict(cfg)


def served(cfg, key, prompts, new, chunk=4):
    """Serve ``prompts`` (one per slot) for ``new`` tokens each; returns
    the requests and, per wave, ``(first position, lens, logits)``."""
    params = REF.init_params(ref_cfg(cfg), key, cfg.padded_vocab)
    srv = DecodeServer(LM(cfg), params, batch_slots=len(prompts),
                       max_len=32, prefill_chunk=chunk)
    inner, waves = srv._wave, []

    def wave(params, tokens, lens, caches):
        pos = srv._pos.copy()
        out = inner(params, tokens, lens, caches)
        waves.append((pos, np.asarray(lens), np.asarray(out[0][:, 0])))
        return out
    srv._wave = wave
    reqs = [Request(prompt=p, max_new_tokens=new) for p in prompts]
    for r in reqs:
        srv.submit(r)
    srv.run_until_drained()
    return srv, reqs, waves


def test_served_logits_match_the_reference():
    """Prefill in 4-token waves, then decode through the latent cache: at
    every position a wave returns, the served logits are the reference's
    full forward's."""
    cfg = get_reduced(ARCH)
    key = seed_key(2 ** 34 + 5)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (9, 5)]
    _, reqs, waves = served(cfg, key, prompts, 6)
    assert all(r.status == "ok" and len(r.out) == 6 for r in reqs)
    seqs = np.zeros((2, 16), np.int32)
    for i, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, np.asarray(r.out, np.int32)])
        seqs[i, :len(seq)] = seq
    want = REF.logits(ref_cfg(cfg), key, seqs)
    compared = 0
    for pos, lens, lg in waves:
        for i in np.flatnonzero(lens):
            np.testing.assert_allclose(
                lg[i], want[i, pos[i] + lens[i] - 1], rtol=0,
                atol=LOGIT_TOL)
            compared += 1
    # 9 = 4 + 4 + 1 and 5 = 4 + 1 prompt tokens, then 5 decode waves each
    assert compared == 3 + 5 + 2 + 5


def test_forward_matches_decode():
    """The prefill path (``forward``: latent up-projected per head) and
    the decode path (attention in latent space) give the same logits."""
    cfg = get_reduced(ARCH)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                cfg.vocab_size)
    hs, _ = lm.forward(params, {"tokens": tokens})
    want = jnp.einsum("bsd,vd->bsv", hs, lm.head(params))[..., :256]
    caches = lm.init_caches(2, 16)
    got = []
    for t in range(12):
        lg, caches = lm.decode_step(params, tokens[:, t:t + 1], caches)
        got.append(lg)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, 1)),
                               np.asarray(want), rtol=0, atol=LOGIT_TOL)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four devices' shares of 2 experts each (the routed parts), plus the
    shared expert once, are the reference's layer holding all 8."""
    cfg = dataclasses.replace(get_reduced(ARCH), experts_held=8)
    key = seed_key(7)
    p = REF._layer_fn(REF._items(ref_cfg(cfg)), None, False)(key, 1)
    x = jax.random.normal(jax.random.PRNGKey(2), (12, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        uncut, _ = REF._experts(x, p, ref_cfg(cfg))
    share = dataclasses.replace(cfg, experts_held=2)
    shared = {"wi_gate": p["s_gate"], "wi_up": p["s_up"], "wo": p["s_down"]}
    total = moe_mod.shared_experts({"shared": shared}, x, share)
    for first in range(0, 8, 2):
        part = {"router": p["router"],
                "wi_gate": p["e_gate"][first:first + 2],
                "wi_up": p["e_up"][first:first + 2],
                "wo": p["e_down"][first:first + 2]}
        total = total + moe_mod.moe_share(part, x, share, first)[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=0, atol=1e-5)


def test_expert_parallel_decode_matches_one_device(run_on_mesh):
    """On a 4-wide EP mesh each rank computes its share of the replicated
    decode tokens and one psum combines them: the layer's output and its
    counters are the one-device layer's."""
    code = """
        import dataclasses
        import jax, numpy as np
        from repro.configs import get_reduced
        from repro.launch.mesh import axis_types_kw
        from repro.models import moe
        cfg = dataclasses.replace(get_reduced("deepseek-v2-lite-16b"),
                                  experts_held=0)
        p = moe.init_moe(jax.random.PRNGKey(0), cfg, np.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 1, cfg.d_model))
        active = np.array([True, False, True, True])
        one = moe.moe_ffn(p, x, cfg, active=active)
        mesh = jax.make_mesh((1, 4), ("data", "model"), **axis_types_kw(2))
        with jax.set_mesh(mesh):
            ep = moe.moe_ffn(p, x, cfg, mesh=mesh, active=active)
        np.testing.assert_allclose(np.asarray(ep[0]), np.asarray(one[0]),
                                   rtol=0, atol=1e-5)
        assert np.asarray(ep[2]).tolist() == np.asarray(one[2]).tolist()
        print("EP_SHARES_OK")
    """
    run_on_mesh(code, devices=4, sentinel="EP_SHARES_OK")


def test_yarn_frequencies_and_softmax_scale_by_hand():
    """DeepSeek-V2-Lite's rope: 32 pairs, correction range [10, 23] from
    ``yarn_find_correction_range`` (β_fast 32, β_slow 1, original 4096,
    base 10000); cos/sin scaled by mscale(40, 0.707) / mscale(40, 0.707)
    = 1; softmax scale 192^-0.5 · mscale(40, 0.707)²."""
    cfg = get_config(ARCH)
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) /
                     (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi)) /
                     (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    freq = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    m = 1 - np.clip((np.arange(32) - low) / (high - low), 0, 1)
    want = freq / 40 * (1 - m) + freq * m
    got = np.asarray(yarn_inv_freq(cfg, 64))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[:10], freq[:10], rtol=1e-6)
    np.testing.assert_allclose(got[23:], freq[23:] / 40, rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mla_scale(cfg) == pytest.approx(192 ** -0.5 * mscale ** 2)
    assert mscale ** 2 == pytest.approx(1.5897, abs=1e-4)
    cos, sin = yarn_rope(jnp.arange(5.0), cfg, 64)
    np.testing.assert_allclose(np.asarray(cos ** 2 + sin ** 2), 1.0,
                               atol=1e-6)
    # the reference reads the same numbers from the configuration
    inv, rope_m, scale = REF.yarn(ref_cfg(cfg))
    np.testing.assert_allclose(inv, want, rtol=1e-12)
    assert rope_m == 1.0 and scale == pytest.approx(mla_scale(cfg))


def test_wave_counts_the_reference_routing():
    """One prefill wave of 4 micro-steps over prompts of 4, 2 and 1 tokens:
    ``serve_stats`` gets the held-expert assignments and the (layer,
    micro-step, held expert) triples that the reference's routing of the
    same tokens gives, fetched with the wave's logits."""
    cfg = get_reduced(ARCH)
    key = seed_key(11)
    rng = np.random.default_rng(3)
    lens = np.array([4, 2, 1])
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    params = REF.init_params(ref_cfg(cfg), key, cfg.padded_vocab)
    srv = DecodeServer(LM(cfg), params, batch_slots=3, max_len=16,
                       prefill_chunk=4)
    for p in prompts:
        srv.submit(Request(prompt=p, max_new_tokens=3))
    srv.step()
    tokens = np.zeros((3, 4), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    picks = REF.routing(ref_cfg(cfg), key, tokens)   # (layers, S, T, k)
    active = np.arange(4)[None, :] < lens[:, None]
    held = picks < cfg.experts_held
    assignments = int((held & active[None, :, :, None]).sum())
    touched = sum(len({int(e) for s in range(3) if active[s, t]
                       for e in picks[l, s, t] if e < cfg.experts_held})
                  for l in range(picks.shape[0]) for t in range(4))
    assert srv.serve_stats["moe_held_assignments"] == assignments
    assert srv.serve_stats["moe_experts_touched"] == touched
    assert 0 < touched < assignments


@pytest.mark.parametrize("norm", [False, True])
def test_router_weights_renormalised_only_when_asked(norm):
    cfg = dataclasses.replace(get_reduced(ARCH), norm_topk_prob=norm)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (6, cfg.d_model))
    probs, topw, tope = moe_mod.route(p, x, cfg)
    sums = np.asarray(topw.sum(-1))
    if norm:
        np.testing.assert_allclose(sums, 1.0, rtol=1e-6)
    else:
        np.testing.assert_array_equal(
            np.asarray(topw), np.take_along_axis(np.asarray(probs),
                                                 np.asarray(tope), -1))
        assert (sums < 1).all()


def test_dense_waves_return_no_counters():
    """A model without experts: the wave returns logits and caches only,
    and the server adds no expert counters."""
    cfg = get_reduced("stablelm-3b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    out = jax.jit(lm.wave_step)(params, jnp.zeros((2, 3), jnp.int32),
                                jnp.array([3, 1], jnp.int32),
                                lm.init_caches(2, 8))
    assert len(out) == 2
    srv = DecodeServer(lm, params, batch_slots=2, max_len=8)
    srv.submit(Request(prompt=np.array([1, 2], np.int32), max_new_tokens=2))
    srv.run_until_drained()
    assert not any(k.startswith("moe_") for k in srv.serve_stats)
