"""Continuous-batching serving loop: slot lifecycle, prioritized
admission, mid-wave EOS recycling (the PR-6 regression), chunked-prefill
bit-identity, and staggered-admission slot isolation.

The lifecycle tests drive the server with ``EchoLM`` — a minimal
deterministic stub (next token = last fed token + 1 mod vocab) whose cache
is just the per-slot position counter — so wave/slot bookkeeping is
observable without model noise.  The numerical tests use the reduced real
LMs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import LM
from repro.runtime.server import DecodeServer, Request


class EchoLM:
    """argmax(logits) == last fed token + 1 (mod vocab); the cache is the
    per-slot position counter, matching the LM cache tree layout.  It takes
    a wave's whole token block at once."""
    vocab = 64
    prefills_in_one_pass = True

    def init_caches(self, batch, max_len):
        return {"scan": (),
                "rest": ({"len": jnp.zeros((batch,), jnp.int32)},)}

    def wave_step(self, params, tokens, lens, caches, batch_ctx=None):
        b, c = tokens.shape
        idx = jnp.clip(lens - 1, 0, c - 1)
        last = jnp.take_along_axis(tokens, idx[:, None], axis=1)[:, 0]
        logits = jax.nn.one_hot((last + 1) % self.vocab, self.vocab)[:, None]
        new = {"scan": (),
               "rest": ({"len": caches["rest"][0]["len"] + lens},)}
        return logits, new

    def reset_slots(self, caches, keep):
        return {"scan": (),
                "rest": ({"len": jnp.where(
                    keep, caches["rest"][0]["len"], 0)},)}


def _req(prompt, **kw):
    return Request(prompt=np.asarray(prompt, np.int32), **kw)


# ---------------------------------------------------------------------------
# Slot lifecycle (EchoLM)
# ---------------------------------------------------------------------------

def test_eos_frees_slot_and_admits_same_iteration():
    """The PR-6 regression: a slot hitting EOS mid-wave must retire
    immediately and the next queued request must be admitted in the SAME
    serving iteration — not after the whole batch drains."""
    srv = DecodeServer(EchoLM(), {}, batch_slots=1, max_len=32,
                       eos_id=5, prefill_chunk=4)
    r1 = _req([4], max_new_tokens=10)     # first generated token is 5 = EOS
    r2 = _req([10], max_new_tokens=3)
    srv.submit(r1)
    srv.submit(r2)
    srv.run_until_drained()
    assert r1.done and r1.out == [5]
    assert r2.done and r2.out == [11, 12, 13]
    # same-iteration recycling: r2 entered the wave counter r1 retired on
    assert r2.admitted_wave == r1.finished_wave
    assert srv.serve_stats["slot_resets"] == 2
    assert srv.serve_stats["admitted"] == 2


def test_priority_queue_ordering():
    """Lower priority value serves first; FIFO within a class (on one slot
    the admission order is fully observable)."""
    srv = DecodeServer(EchoLM(), {}, batch_slots=1, max_len=32,
                       prefill_chunk=2)
    reqs = [_req([i + 1], max_new_tokens=2, priority=p)
            for i, p in enumerate([2, 0, 1, 0])]
    for r in reqs:
        srv.submit(r)
    srv.run_until_drained()
    order = sorted(range(4), key=lambda i: reqs[i].admitted_wave)
    assert order == [1, 3, 2, 0]          # priorities 0, 0 (FIFO), 1, 2
    assert all(r.done for r in reqs)


def test_zero_active_slot_wave_is_a_noop():
    srv = DecodeServer(EchoLM(), {}, batch_slots=2, max_len=16)
    assert srv.step() == 0
    assert srv.run_until_drained() == 0
    assert srv.serve_stats["waves"] == 0


def test_slot_recycling_under_full_queue():
    """More requests than slots with ragged lengths: every slot is recycled
    multiple times, all requests complete, and per-request output follows
    the echo chain from its own prompt (no stale-cache leakage)."""
    srv = DecodeServer(EchoLM(), {}, batch_slots=2, max_len=32,
                       prefill_chunk=4)
    rng = np.random.default_rng(0)
    reqs = []
    for k in range(9):
        n = int(rng.integers(1, 6))
        start = int(rng.integers(0, 40))
        reqs.append(_req([start], max_new_tokens=n))
    for r in reqs:
        srv.submit(r)
    srv.run_until_drained()
    for r in reqs:
        assert r.done
        start = int(r.prompt[0])
        want = [(start + 1 + j) % EchoLM.vocab
                for j in range(r.max_new_tokens)]
        assert r.out == want, (start, r.out, want)
    assert srv.serve_stats["admitted"] == 9
    assert srv.serve_stats["slot_resets"] == 9
    # the 2 slots turned over while others were mid-flight: some admission
    # happened at a wave where the other slot was already past prefill
    waves = sorted(r.admitted_wave for r in reqs)
    assert waves[2] > 0                   # third admission waited for a slot


def test_max_len_slot_retires_and_recycles():
    """A slot that exhausts cache room retires (finished, possibly short)
    and its successor still serves correctly."""
    srv = DecodeServer(EchoLM(), {}, batch_slots=1, max_len=8,
                       prefill_chunk=4)
    r1 = _req([3, 4, 5, 6], max_new_tokens=50)   # wants more than room
    r2 = _req([20], max_new_tokens=2)
    srv.submit(r1)
    srv.submit(r2)
    srv.run_until_drained(max_steps=200)
    # room after the prompt, +1: the first token spends no cache position
    # (it reads the prompt's last logits)
    assert r1.done and len(r1.out) == 8 - 4 + 1
    assert r2.done and r2.out == [21, 22]


def test_request_service_metrics_are_stamped():
    srv = DecodeServer(EchoLM(), {}, batch_slots=2, max_len=16)
    r = _req([7, 8], max_new_tokens=3)
    srv.submit(r)
    srv.run_until_drained()
    assert r.t_submit is not None and r.t_admit >= r.t_submit
    assert r.t_first >= r.t_admit and r.t_done >= r.t_first
    assert len(r.token_times) == 3
    assert r.finished_wave >= r.admitted_wave


# ---------------------------------------------------------------------------
# SLO edge cases (PR 7)
# ---------------------------------------------------------------------------

def test_zero_admissible_requests_with_nonempty_queue():
    """Every queued request's budget already lapsed: step() retires them
    all at admission (status expired), runs NO wave, and returns 0 — a
    queue of dead requests never spins the loop."""
    srv = DecodeServer(EchoLM(), {}, batch_slots=2, max_len=16)
    reqs = [_req([3], max_new_tokens=2, deadline_s=0.0) for _ in range(3)]
    for r in reqs:
        srv.submit(r)
    assert len(srv.queue) == 3
    assert srv.step() == 0
    assert srv.serve_stats["waves"] == 0
    assert srv.serve_stats["expired"] == 3
    assert not srv.queue
    for r in reqs:
        assert r.done and r.status == "expired"
        assert "lapsed in queue" in r.error


def test_all_slots_expire_in_one_wave_then_server_recovers():
    """Budgets that pass admission but lapse during the (artificially
    slowed) first wave: every active slot retires expired mid-wave, and a
    later request is still served normally."""
    from repro.runtime.faults import FaultInjector, FaultSpec
    srv = DecodeServer(
        EchoLM(), {}, batch_slots=2, max_len=16,
        faults=FaultInjector([FaultSpec("wave", at=(1,), delay_s=0.4,
                                        delay_only=True)]))
    # budget wide enough to always survive admission on a loaded box, but
    # narrower than the injected wave stall so it lapses *in service*
    reqs = [_req([3], max_new_tokens=2, deadline_s=0.1),
            _req([7], max_new_tokens=2, deadline_s=0.1)]
    for r in reqs:
        srv.submit(r)
    srv.step()
    for r in reqs:
        assert r.done and r.status == "expired"
        assert "lapsed in service" in r.error
        assert r.t_first is None and not r.out
    assert srv.serve_stats["expired"] == 2
    late = _req([10], max_new_tokens=2)        # no deadline: must serve
    srv.submit(late)
    srv.run_until_drained()
    assert late.status == "ok" and late.out == [11, 12]


def test_deadline_past_at_admission_pops_next_request():
    """One slot, two requests: the first expires at admission (not at
    submit — no capacity calibration), and the SAME admission pass admits
    the second into the slot."""
    import time
    srv = DecodeServer(EchoLM(), {}, batch_slots=1, max_len=16)
    dead = _req([3], max_new_tokens=2, deadline_s=0.01)
    live = _req([7], max_new_tokens=2)
    srv.submit(dead)
    srv.submit(live)
    time.sleep(0.02)                           # dead's budget lapses queued
    srv.run_until_drained()
    assert dead.status == "expired" and not dead.out
    assert live.status == "ok" and live.out == [8, 9]
    assert srv.serve_stats["admitted"] == 1
    # the wave count never stalled on the dead request
    assert dead.admitted_wave is None


def test_per_request_deadline_overrides_server_slo():
    """Request.deadline_s wins over ttft_slo_s: a generous per-request
    budget keeps a request alive that the server-wide SLO would expire."""
    import time
    srv = DecodeServer(EchoLM(), {}, batch_slots=1, max_len=16,
                       ttft_slo_s=0.01)
    r = _req([3], max_new_tokens=2, deadline_s=30.0)
    srv.submit(r)
    time.sleep(0.02)
    srv.run_until_drained()
    assert r.status == "ok" and r.out == [4, 5]


# ---------------------------------------------------------------------------
# Chunked prefill bit-identity (real LM)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["stablelm-3b", "qwen3-moe-235b-a22b"])
def test_chunked_prefill_bit_identical(arch):
    """Splitting a ragged prompt batch into waves of ANY chunk size replays
    the same masked micro-step sequence: logits at each slot's last prompt
    token and every cache leaf are bit-identical to the whole-prompt wave
    (the MoE arch exercises capacity contention across slots too)."""
    cfg = get_reduced(arch)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    b, L = 2, 9
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, L), 0,
                              cfg.vocab_size)
    lens = jnp.array([9, 6], jnp.int32)
    wave = jax.jit(lm.wave_step)
    lg_whole, cache_whole = wave(params, toks, lens,
                                 lm.init_caches(b, 16))[:2]
    for chunk in (1, 4):
        caches = lm.init_caches(b, 16)
        lg_by_slot = [None] * b
        off = 0
        while off < L:
            n = min(chunk, L - off)
            cl = jnp.clip(lens - off, 0, n)
            part = jnp.pad(toks[:, off:off + n], ((0, 0), (0, chunk - n)))
            lg, caches = wave(params, part, cl, caches)[:2]
            for i in range(b):
                if int(cl[i]) > 0 and off + int(cl[i]) == int(lens[i]):
                    lg_by_slot[i] = lg[i]
            off += chunk
        for i in range(b):
            np.testing.assert_array_equal(
                np.asarray(lg_by_slot[i]), np.asarray(lg_whole[i]),
                err_msg=f"{arch} chunk={chunk} slot={i}")
        for lw, lc in zip(jax.tree.leaves(cache_whole),
                          jax.tree.leaves(caches)):
            np.testing.assert_array_equal(np.asarray(lw), np.asarray(lc))


def _decode_cases():
    from repro.configs import list_archs
    return [pytest.param(a, "model", id=a) for a in list_archs()] + \
        [pytest.param("stablelm-3b", "int8", id="stablelm-3b-int8")]


@pytest.mark.parametrize("arch,kv", _decode_cases())
def test_wave_step_matches_decode_step_replay(arch, kv):
    """wave_step IS the fused masked decode loop: replaying the same
    tokens through per-step decode_step calls (the legacy serving path)
    produces bit-identical logits and caches, for every architecture's
    cache kind (K/V, int8 K/V, MLA latent, recurrent state, cross-attn
    decoder) — and a slot the wave does not feed keeps every cache leaf
    bit-identical, written in place or not."""
    import dataclasses
    cfg = dataclasses.replace(get_reduced(arch), kv_cache_dtype=kv)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    b, L = 3, 6
    ctx = None
    if cfg.enc_layers:
        ctx = {"enc_out": jax.random.normal(jax.random.PRNGKey(3),
                                            (b, 16, cfg.d_model))}
    wave = jax.jit(lm.wave_step)
    # a warm cache, so an untouched slot has state to keep
    warm = jax.random.randint(jax.random.PRNGKey(1), (b, 3), 0,
                              cfg.vocab_size)
    _, start = wave(params, warm, jnp.array([3, 2, 3], jnp.int32),
                    lm.init_caches(b, 16), ctx)[:2]
    toks = jax.random.randint(jax.random.PRNGKey(2), (b, L), 0,
                              cfg.vocab_size)
    lens = jnp.array([6, 4, 0], jnp.int32)
    lg_wave, cache_wave = wave(params, toks, lens, start, ctx)[:2]
    caches = start
    step = jax.jit(lm.decode_step)
    lg_by_slot = [None] * b
    for t in range(L):
        lg, caches = step(params, toks[:, t:t + 1], caches, ctx,
                          jnp.asarray(t < np.asarray(lens)))
        for i in range(b):
            if t == int(lens[i]) - 1:
                lg_by_slot[i] = lg[i]
    for i in range(b):
        if lg_by_slot[i] is not None:
            np.testing.assert_array_equal(np.asarray(lg_by_slot[i]),
                                          np.asarray(lg_wave[i]))
    assert jax.tree.structure(cache_wave) == jax.tree.structure(start)
    for lw, lc in zip(jax.tree.leaves(cache_wave), jax.tree.leaves(caches)):
        np.testing.assert_array_equal(np.asarray(lw), np.asarray(lc))
    # batch is axis 1 of scan-stacked leaves (layer first), 0 of the rest
    for part, axis in (("scan", 1), ("rest", 0)):
        for lw, l0 in zip(jax.tree.leaves(cache_wave[part]),
                          jax.tree.leaves(start[part])):
            np.testing.assert_array_equal(np.take(np.asarray(lw), 2, axis),
                                          np.take(np.asarray(l0), 2, axis))


@pytest.mark.parametrize("kv", ["model", "int8"])
def test_one_pass_block_past_max_len_writes_only_valid_rows(kv):
    """A one-pass wave whose (slots, chunk) block runs past the cache's
    end: slot 0 sits 3 rows short of ``max_len`` and feeds 3 of its 8
    tokens.  Its valid rows land at ``len + t``, exactly as the replayed
    micro-steps put them, and every other row of every leaf — slot 0's
    earlier rows, slot 1 past its own valid rows — stays bit-identical."""
    import dataclasses
    cfg = dataclasses.replace(get_reduced("stablelm-3b"), kv_cache_dtype=kv)
    lm = LM(cfg)
    assert lm.prefills_in_one_pass
    params = lm.init(jax.random.PRNGKey(0))
    b, max_len, c = 2, 16, 8
    wave = jax.jit(lm.wave_step)
    start = lm.init_caches(b, max_len)
    for key, lens in ((1, [8, 3]), (2, [5, 0])):
        warm = jax.random.randint(jax.random.PRNGKey(key), (b, c), 0,
                                  cfg.vocab_size)
        _, start = wave(params, warm, jnp.array(lens, jnp.int32), start)
    pos = np.asarray(start["scan"][0]["len"][0])
    assert pos.tolist() == [13, 3]
    toks = jax.random.randint(jax.random.PRNGKey(3), (b, c), 0,
                              cfg.vocab_size)
    lens = np.array([3, 5], np.int32)
    lg, cache = wave(params, toks, jnp.asarray(lens), start)
    step = jax.jit(lm.decode_step)
    replay = start
    for t in range(c):
        lg_t, replay = step(params, toks[:, t:t + 1], replay, None,
                            jnp.asarray(t < lens))
        for slot in range(b):
            if t == lens[slot] - 1:
                np.testing.assert_array_equal(np.asarray(lg[slot]),
                                              np.asarray(lg_t[slot]))
    for lw, lr in zip(jax.tree.leaves(cache), jax.tree.leaves(replay)):
        np.testing.assert_array_equal(np.asarray(lw), np.asarray(lr))
    layer = cache["scan"][0]
    assert np.asarray(layer["len"][0]).tolist() == [16, 8]
    for name, leaf in layer.items():
        if name == "len":
            continue
        new, old = np.asarray(leaf), np.asarray(start["scan"][0][name])
        for slot in range(b):
            keep = np.ones(max_len, bool)
            keep[pos[slot]:pos[slot] + lens[slot]] = False
            np.testing.assert_array_equal(new[:, slot, keep],
                                          old[:, slot, keep])
            assert not np.array_equal(new[:, slot, ~keep],
                                      old[:, slot, ~keep])


def test_one_pass_engages_for_dense_stacks_only():
    """The one-pass wave follows the stack's block kinds: exactly the
    architectures built of GQA attention + MLP blocks take it, at full
    and at reduced size; MoE, MLA, recurrent, hybrid and encoder-decoder
    stacks replay their waves."""
    from repro.configs import get_config, list_archs
    dense = {"stablelm-3b", "chatglm3-6b", "gemma3-4b", "h2o-danube-1.8b",
             "llava-next-34b"}
    for make in (get_config, get_reduced):
        assert {a for a in list_archs()
                if LM(make(a)).prefills_in_one_pass} == dense


@pytest.mark.parametrize("arch,kind", [
    ("stablelm-3b", "prefill_passes"),
    ("deepseek-v2-lite-16b", "prefill_replays")])
def test_server_counts_prefill_passes_and_replays(arch, kind):
    """After a drained run every prefill wave is counted once, as a pass
    (a dense stack) or as a replay (the MLA + MoE stack)."""
    cfg = get_reduced(arch)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    srv = DecodeServer(lm, params, batch_slots=2, max_len=32,
                       prefill_chunk=4)
    for n in (7, 3, 5):
        srv.submit(_req(rng.integers(0, cfg.vocab_size, n),
                        max_new_tokens=2))
    srv.run_until_drained()
    st = srv.serve_stats
    assert st["finished"] == 3 and st["prefill_waves"] > 0
    assert st["prefill_passes"] + st["prefill_replays"] == \
        st["prefill_waves"]
    assert st[kind] == st["prefill_waves"]


@pytest.mark.parametrize("arch,kv", _decode_cases())
def test_wave_step_is_independent_of_cache_layout(arch, kv, monkeypatch):
    """The wave holds every cache leaf it writes in its device's layout (on
    a TPU, K/V with head dim 80 keep the sequence axis minor).  Held with
    every non-leading axis reversed instead, two ragged waves give the same
    logits and caches as in the CPU's row-major layout.  (MLA contracts
    its latent cache in matmuls whose summation order the compiler picks
    by the operand's layout: equal there to rounding.)"""
    import dataclasses
    from jax.experimental.layout import Layout, with_layout_constraint
    from repro.models import attention
    cfg = dataclasses.replace(get_reduced(arch), kv_cache_dtype=kv)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    b = 3
    ctx = None
    if cfg.enc_layers:
        ctx = {"enc_out": jax.random.normal(jax.random.PRNGKey(3),
                                            (b, 16, cfg.d_model))}
    waves = [(jax.random.randint(jax.random.PRNGKey(1), (b, 6), 0,
                                 cfg.vocab_size),
              jnp.array([6, 3, 5], jnp.int32)),
             (jax.random.randint(jax.random.PRNGKey(2), (b, 6), 0,
                                 cfg.vocab_size),
              jnp.array([4, 0, 6], jnp.int32))]

    def serve():
        caches, out = lm.init_caches(b, 16), []
        wave = jax.jit(lm.wave_step)
        for toks, lens in waves:
            lg, caches = wave(params, toks, lens, caches, ctx)[:2]
            out.append(lg)
        return out, caches

    def reversed_layout(leaf):
        order = (0,) + tuple(reversed(range(1, leaf.ndim)))
        return with_layout_constraint(leaf, Layout(order))

    want = serve()
    monkeypatch.setattr(attention, "_keep_layout", reversed_layout)
    got = serve()
    tol = 1e-5 if "mla" in cfg.block_pattern else 0
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(w), np.asarray(g), rtol=tol,
                                   atol=tol)


@pytest.mark.parametrize("kv", ["model", "int8"])
def test_wave_step_writes_cache_in_place(kv):
    """Compile-time guard against whole-cache copies in the serving wave:
    the donated cache is written a row at a time, so the compiler's temp
    buffer does not grow with the cache.  Four slots of 64 positions, an
    8-token wave, the reduced stablelm-3b at 2 and at 8 layers: from 2 to 8
    the temp grows by under a quarter of what the cache grows by (a wave
    that slices, selects or copies the whole cache grows it by as much as
    the cache or more).  Depth is the axis, not the whole cache: the CPU
    compiler materialises one layer's K and V as attention's operands, half
    the cache at 2 layers, the same bytes at any depth."""
    import dataclasses

    def temp_and_cache(layers):
        cfg = dataclasses.replace(get_reduced("stablelm-3b"),
                                  num_layers=layers, kv_cache_dtype=kv)
        lm = LM(cfg)
        params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
        caches = jax.eval_shape(lambda: lm.init_caches(4, 64))
        compiled = jax.jit(lm.wave_step, donate_argnums=(3,)).lower(
            params, jax.ShapeDtypeStruct((4, 8), jnp.int32),
            jax.ShapeDtypeStruct((4,), jnp.int32), caches).compile()
        return (compiled.memory_analysis().temp_size_in_bytes,
                sum(x.size * x.dtype.itemsize
                    for x in jax.tree.leaves(caches)))

    (t2, c2), (t8, c8) = temp_and_cache(2), temp_and_cache(8)
    assert t8 - t2 < (c8 - c2) / 4, (t2, t8, c2, c8)


@pytest.mark.parametrize("arch", ["stablelm-3b", "qwen3-moe-235b-a22b",
                                  "deepseek-v2-lite-16b"])
def test_decode_server_compiles_no_embedding_program(arch):
    """The server embeds tokens inside the jitted wave: building it and
    serving a few waves compiles no Ember program and builds no
    executor."""
    from repro.core.executor import executor_cache_stats
    from repro.core.pipeline import compile_cache_stats
    cfg = get_reduced(arch)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    before = (compile_cache_stats(), executor_cache_stats())
    srv = DecodeServer(lm, params, batch_slots=2, max_len=32,
                       prefill_chunk=4)
    rng = np.random.default_rng(4)
    for n in (5, 2):
        srv.submit(_req(rng.integers(0, cfg.vocab_size, n),
                        max_new_tokens=2))
    srv.run_until_drained()
    assert srv.serve_stats["finished"] == 2
    assert (compile_cache_stats(), executor_cache_stats()) == before


# ---------------------------------------------------------------------------
# Staggered admission / slot isolation (real LM, through the server)
# ---------------------------------------------------------------------------

def test_staggered_admission_matches_solo_decode():
    """Requests recycled through a shared 2-slot server (admitted at
    different waves, into previously-used slots) must produce exactly the
    greedy continuation they get when served alone — slot recycling leaks
    no stale cache state (dense arch: slots are independent)."""
    cfg = get_reduced("h2o-danube-1.8b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in (5, 3, 7, 2, 4)]
    shared = [Request(prompt=p.copy(), max_new_tokens=4) for p in prompts]
    srv = DecodeServer(lm, params, batch_slots=2, max_len=32,
                       prefill_chunk=3)
    for r in shared:
        srv.submit(r)
    srv.run_until_drained()
    assert all(r.done for r in shared)
    # staggering actually happened: admissions span multiple waves
    assert len({r.admitted_wave for r in shared}) > 1
    for p, r in zip(prompts, shared):
        solo_req = Request(prompt=p.copy(), max_new_tokens=4)
        solo = DecodeServer(lm, params, batch_slots=1, max_len=32,
                            prefill_chunk=8)
        solo.submit(solo_req)
        solo.run_until_drained()
        assert solo_req.out == r.out, (p, solo_req.out, r.out)


def test_server_output_invariant_to_prefill_chunk():
    """End-to-end: the same workload through prefill_chunk=1 vs 4 servers
    yields identical greedy outputs (chunking is a scheduling choice, not a
    numerics choice)."""
    cfg = get_reduced("stablelm-3b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in (4, 6, 2)]
    outs = []
    for chunk in (1, 4):
        reqs = [Request(prompt=p.copy(), max_new_tokens=3) for p in prompts]
        srv = DecodeServer(lm, params, batch_slots=2, max_len=32,
                           prefill_chunk=chunk)
        for r in reqs:
            srv.submit(r)
        srv.run_until_drained()
        outs.append([r.out for r in reqs])
    assert outs[0] == outs[1]
