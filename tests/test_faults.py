"""Chaos suite: the seeded site-addressable FaultInjector, executor fault
recovery per DAE site, the serving wave watchdog + bounded retry, prompt
hardening policies, SLO shedding, and the spawn-retry helper.

The recovery tests all assert the same property the ISSUE names: after a
typed fault + ``reset()``, the next steps produce outputs **bit-identical**
to a fault-free run — recovery never corrupts the marshaling caches, the
staging pool, or a neighbouring slot.  ``CHAOS_SEED`` (the CI chaos leg
pins it) seeds the probabilistic specs through ``injector_for_env``.
"""
import os
import subprocess

import numpy as np
import pytest

from repro.core.executor import ProgramExecutor
from repro.core.ops import EmbeddingOp, EmbeddingProgram, make_program_inputs
from repro.core.pipeline import compile_program, run_program_interpreted
from repro.runtime.faults import (EmberFault, FaultInjector, FaultSpec,
                                  InjectedFailure, MalformedAccessError,
                                  SITES, StragglerTimeout, WaveTimeout,
                                  injector_for_env)
from repro.runtime.server import DecodeServer, Request

from test_server import EchoLM, _req


def _prog():
    return EmbeddingProgram("chaos", (
        ("s", EmbeddingOp("sls", 5, 9, 8, avg_lookups=3)),
        ("g", EmbeddingOp("gather", 6, 20, 8)),
    ))


# ---------------------------------------------------------------------------
# FaultInjector semantics
# ---------------------------------------------------------------------------

def test_spec_fires_at_exact_ordinals_and_respects_times():
    inj = FaultInjector([FaultSpec("dispatch", at=(2, 3), times=1)])
    inj.fire("dispatch")                       # call 1: pass
    with pytest.raises(InjectedFailure, match="site=dispatch call=2"):
        inj.fire("dispatch")
    inj.fire("dispatch")                       # call 3: times budget spent
    assert inj.total_fired() == 1
    assert inj.counts["dispatch"] == 3
    assert inj.log == [("dispatch", 2, "InjectedFailure")]


def test_sites_are_independent_counters():
    inj = FaultInjector([FaultSpec("result", at=(1,))])
    inj.fire("marshal")
    inj.fire("transfer")                       # other sites never match
    with pytest.raises(InjectedFailure):
        inj.fire("result")


def test_probabilistic_schedule_replays_per_seed():
    def schedule(seed):
        inj = FaultInjector([FaultSpec("wave", p=0.5, times=100)],
                            seed=seed)
        fired = []
        for k in range(40):
            try:
                inj.fire("wave")
                fired.append(False)
            except InjectedFailure:
                fired.append(True)
        return fired

    assert schedule(7) == schedule(7)          # bit-identical replay
    assert any(schedule(7))                    # and it actually fires


def test_delay_only_sleeps_without_raising():
    inj = FaultInjector([FaultSpec("wave", at=(1,), delay_s=0.01,
                                   delay_only=True)])
    inj.fire("wave")                           # no raise
    assert inj.log == [("wave", 1, "delay")]
    assert inj.total_fired() == 1


def test_custom_error_type_and_context():
    inj = FaultInjector([FaultSpec("step", at=(1,),
                                   error=StragglerTimeout)])
    with pytest.raises(StragglerTimeout, match=r"\[step=4\]"):
        inj.fire("step", step=4)


def test_injector_for_env_seeds_from_chaos_seed():
    assert injector_for_env("7").seed == 7
    assert injector_for_env(None).seed == 0
    assert injector_for_env("").seed == 0
    # the CI chaos leg: whatever CHAOS_SEED is pinned to must replay
    env = os.environ.get("CHAOS_SEED")
    a = injector_for_env(env, [FaultSpec("wave", p=0.3, times=5)])
    b = injector_for_env(env, [FaultSpec("wave", p=0.3, times=5)])
    for _ in range(20):
        ra = rb = None
        try:
            a.fire("wave")
        except InjectedFailure as e:
            ra = str(e)
        try:
            b.fire("wave")
        except InjectedFailure as e:
            rb = str(e)
        assert ra == rb


def test_unknown_site_rejected():
    with pytest.raises(AssertionError):
        FaultSpec("gpu-on-fire")
    assert set(SITES) == {"marshal", "transfer", "dispatch", "result",
                          "wave", "step", "rpc_send", "rpc_recv",
                          "heartbeat", "service_crash"}


# ---------------------------------------------------------------------------
# Executor recovery per DAE site: fault -> reset -> bit-identical steps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("site", ["marshal", "transfer", "dispatch",
                                  "result"])
def test_executor_site_fault_then_reset_recovers(site):
    pres = compile_program(_prog(), "O3", vlen=4, use_cache=False)
    # default (pallas) backend: the only one where every DAE phase runs —
    # jax-backend singletons marshal host views without scratch or puts
    ex = ProgramExecutor(pres,
                         faults=FaultInjector([FaultSpec(site, at=(1,))]))
    ins = make_program_inputs(_prog(), seed=0)
    with pytest.raises(InjectedFailure, match=f"site={site}"):
        ex.step(ins)
    ex.reset()
    assert ex.stats["resets"] == 1
    # the pool must not leak busy slots from the abandoned step
    assert all(o is None for e in ex.pool._entries.values()
               for o in e["owners"])
    for seed in (1, 2):
        ins = make_program_inputs(_prog(), seed=seed)
        got = ex.step(ins)
        want = run_program_interpreted(pres, ins)
        for n in want:
            np.testing.assert_array_equal(np.asarray(got[n]),
                                          np.asarray(want[n]),
                                          err_msg=f"{n} after {site} fault")


def test_executor_fault_types_are_ember_faults():
    assert issubclass(InjectedFailure, EmberFault)
    assert issubclass(MalformedAccessError, EmberFault)
    assert issubclass(WaveTimeout, EmberFault)
    assert issubclass(StragglerTimeout, EmberFault)


# ---------------------------------------------------------------------------
# Serving wave watchdog + bounded retry (EchoLM: outputs fully predictable)
# ---------------------------------------------------------------------------

def _echo_run(**kw):
    srv = DecodeServer(EchoLM(), {}, batch_slots=2, max_len=32,
                       prefill_chunk=4, **kw)
    reqs = [_req([10], max_new_tokens=3), _req([20], max_new_tokens=3),
            _req([30], max_new_tokens=2)]
    for r in reqs:
        srv.submit(r)
    srv.run_until_drained(max_steps=100)
    return srv, reqs


def test_wave_fault_retries_once_and_matches_fault_free():
    _, clean = _echo_run()
    srv, reqs = _echo_run(
        faults=FaultInjector([FaultSpec("wave", at=(2,), times=1)]),
        wave_retries=1)
    assert srv.serve_stats["wave_faults"] == 1
    assert srv.serve_stats["wave_retries"] == 1
    assert srv.serve_stats["failed"] == 0
    for r, c in zip(reqs, clean):
        assert r.done and r.status == "ok"
        assert r.out == c.out


def test_wave_fault_beyond_retries_fails_only_implicated():
    _, clean = _echo_run()
    srv, reqs = _echo_run(
        faults=FaultInjector([FaultSpec("wave", at=(2, 3), times=2)]),
        wave_retries=1)
    assert srv.serve_stats["wave_faults"] == 2
    failed = [r for r in reqs if r.status == "failed"]
    assert failed and len(failed) < len(reqs)
    for r in failed:
        assert r.done and "InjectedFailure" in r.error
    # the survivors still produce the exact fault-free echo chain
    for r, c in zip(reqs, clean):
        if r.status == "ok":
            assert r.out == c.out
    assert srv.serve_stats["failed"] == len(failed)


def test_hung_wave_watchdog_times_out_and_recovers():
    # wide margins (1s hang vs 0.25s deadline, ms-scale real waves) and
    # retries=2 so a loaded CI box tripping a *genuine* slow wave on top
    # of the injected hang still recovers
    _, clean = _echo_run()
    srv, reqs = _echo_run(
        faults=FaultInjector([FaultSpec("wave", at=(2,), delay_s=1.0,
                                        delay_only=True)]),
        wave_deadline_s=0.25, wave_retries=2)
    assert srv.serve_stats["watchdog_timeouts"] >= 1
    assert srv.serve_stats["wave_retries"] >= 1
    for r, c in zip(reqs, clean):
        assert r.done and r.status == "ok"
        assert r.out == c.out


def test_hung_wave_without_retries_fails_typed():
    srv, reqs = _echo_run(
        faults=FaultInjector([FaultSpec("wave", at=(1,), delay_s=0.2,
                                        delay_only=True)]),
        wave_deadline_s=0.05, wave_retries=0)
    failed = [r for r in reqs if r.status == "failed"]
    assert failed
    assert all("WaveTimeout" in r.error for r in failed)


# ---------------------------------------------------------------------------
# Prompt hardening + SLO shedding (EchoLM)
# ---------------------------------------------------------------------------

def test_prompt_hardening_strict_fails_typed():
    srv = DecodeServer(EchoLM(), {}, batch_slots=1, max_len=16)
    bad = _req([70, 3], max_new_tokens=2)      # vocab is 64
    srv.submit(bad)
    assert bad.done and bad.status == "failed"
    assert "MalformedAccessError" in bad.error
    assert not srv.queue                       # never admitted
    ok = _req([3], max_new_tokens=2)
    srv.submit(ok)
    srv.run_until_drained()
    assert ok.status == "ok" and ok.out == [4, 5]


@pytest.mark.parametrize("policy", ["clamp", "drop"])
def test_prompt_hardening_degrades_and_counts(policy):
    srv = DecodeServer(EchoLM(), {}, batch_slots=1, max_len=16,
                       index_policy=policy)
    r = _req([70, 3], max_new_tokens=2)
    srv.submit(r)
    srv.run_until_drained()
    assert r.status == "ok"
    # clamp: [63, 3]; drop: [3] — either way the echo runs from 3
    assert r.out == [4, 5]
    assert srv.serve_stats["oob_prompt_tokens"] == 1


def test_prompt_drop_to_empty_fails():
    srv = DecodeServer(EchoLM(), {}, batch_slots=1, max_len=16,
                       index_policy="drop")
    r = _req([70, 99], max_new_tokens=2)
    srv.submit(r)
    assert r.done and r.status == "failed"
    assert "empty" in r.error


def test_submit_shed_on_predicted_queue_wait():
    srv = DecodeServer(EchoLM(), {}, batch_slots=1, max_len=16,
                       capacity_rps=1.0, ttft_slo_s=0.5)
    r1, r2 = _req([3], max_new_tokens=2), _req([4], max_new_tokens=2)
    srv.submit(r1)                             # queue empty: admitted
    srv.submit(r2)                             # predicted wait 1.0s > 0.5s
    assert r2.done and r2.status == "shed"
    assert "predicted queue wait" in r2.error
    assert srv.serve_stats["shed"] == 1
    srv.run_until_drained()
    assert r1.status == "ok" and r1.out == [4, 5]


def test_every_request_reaches_exactly_one_terminal_status():
    srv, reqs = _echo_run(
        faults=FaultInjector([FaultSpec("wave", at=(1, 2), times=2)]),
        wave_retries=0)
    for r in reqs:
        assert r.done
        assert r.status in ("ok", "shed", "expired", "failed")
        assert r.t_done is not None


# ---------------------------------------------------------------------------
# Wave retry on the real stacks (the path that donates the caches)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", [
    "stablelm-3b",              # one-pass prefill
    "qwen3-moe-235b-a22b",      # replayed prefill, experts
    "deepseek-v2-lite-16b",     # MLA, a leading dense layer
])
def test_wave_fault_retry_bit_identical_on_real_stacks(arch):
    """A "wave" fault at the second wave, retried once, leaves every
    request with the fault-free run's tokens: the fault fires before the
    jitted wave, so the retry runs it once on the caches it donates."""
    import jax
    from repro.configs import get_reduced
    from repro.models import LM
    cfg = get_reduced(arch)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, 4).astype(np.int32)
               for _ in range(3)]

    def run(faults=None):
        srv = DecodeServer(lm, params, batch_slots=2, max_len=32,
                           prefill_chunk=4, faults=faults, wave_retries=1)
        reqs = [Request(prompt=p.copy(), max_new_tokens=3) for p in prompts]
        for r in reqs:
            srv.submit(r)
        srv.run_until_drained(max_steps=100)
        return srv, reqs

    _, clean = run()
    srv, reqs = run(FaultInjector([FaultSpec("wave", at=(2,), times=1)]))
    assert srv.serve_stats["wave_faults"] == 1
    assert srv.serve_stats["wave_retries"] == 1
    assert srv.serve_stats["failed"] == 0
    for r, c in zip(reqs, clean):
        assert r.done and r.status == "ok"
        assert r.out == c.out, (arch, r.out, c.out)


# ---------------------------------------------------------------------------
# Trainer: shared vocabulary + the "step" site
# ---------------------------------------------------------------------------

def test_trainer_reexports_shared_fault_types():
    from repro.runtime import faults as fl
    from repro.runtime import trainer as tr
    assert tr.InjectedFailure is fl.InjectedFailure
    assert tr.StragglerTimeout is fl.StragglerTimeout


def test_trainer_step_site_fires(tmp_path):
    import jax
    from repro.configs import get_reduced
    from repro.data.pipeline import DataConfig, SyntheticTokens
    from repro.models import LM
    from repro.runtime.trainer import Trainer, TrainerConfig
    cfg = get_reduced("stablelm-3b")
    lm = LM(cfg)
    data = SyntheticTokens(DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                                      global_batch=8))
    tcfg = TrainerConfig(total_steps=6, ckpt_every=100,
                         ckpt_dir=str(tmp_path / "ckpt"))
    inj = FaultInjector([FaultSpec("step", at=(3,))])
    with pytest.raises(InjectedFailure, match="site=step call=3"):
        Trainer(lm, data, tcfg, faults=inj).run(jax.random.PRNGKey(0))
    assert inj.counts["step"] == 3


# ---------------------------------------------------------------------------
# Spawn retry: infra failures retry, test failures never do
# ---------------------------------------------------------------------------

class _FakeRun:
    """Scripted subprocess.run: pops the next outcome per call (an int
    returncode or an OSError instance to raise)."""

    def __init__(self, outcomes):
        self.outcomes = list(outcomes)
        self.calls = 0

    def __call__(self, cmd, **kw):
        self.calls += 1
        out = self.outcomes.pop(0)
        if isinstance(out, OSError):
            raise out
        return subprocess.CompletedProcess(cmd, out)


def _retry(outcomes, attempts=3):
    from benchmarks._mesh import run_with_spawn_retry
    import benchmarks._mesh as mesh
    fake = _FakeRun(outcomes)
    sleeps = []
    orig = mesh.subprocess.run
    mesh.subprocess.run = fake
    try:
        r = run_with_spawn_retry(["x"], attempts=attempts,
                                 backoff_s=0.5, sleep=sleeps.append)
    finally:
        mesh.subprocess.run = orig
    return r, fake, sleeps


def test_spawn_retry_oserror_then_success():
    r, fake, sleeps = _retry([OSError("EAGAIN"), 0])
    assert r.returncode == 0 and fake.calls == 2
    assert sleeps == [0.5]                     # exponential from backoff_s


def test_spawn_retry_signal_killed_child_retries():
    r, fake, sleeps = _retry([-9, -9, 0])
    assert r.returncode == 0 and fake.calls == 3
    assert sleeps == [0.5, 1.0]


def test_spawn_retry_ordinary_failure_never_retries():
    r, fake, sleeps = _retry([1, 0])
    assert r.returncode == 1 and fake.calls == 1
    assert sleeps == []


def test_spawn_retry_exhausted_signal_kills_returns_last():
    r, fake, _ = _retry([-9, -9, -9])
    assert r.returncode == -9 and fake.calls == 3


def test_spawn_retry_exhausted_oserrors_reraises():
    with pytest.raises(OSError, match="ENOMEM"):
        _retry([OSError("ENOMEM"), OSError("ENOMEM"), OSError("ENOMEM")])
