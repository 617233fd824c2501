"""Disaggregated embedding service: transport, failover, re-warm, degrade.

Layered like the implementation:

* transport (``runtime/rpc.py``) — framing round-trips bit-identically,
  deadlines lapse typed, the backoff shape matches ``run_with_spawn_retry``;
* service contract — program specs round-trip, steps replay idempotently
  by sequence number;
* pool robustness — replica ``kill -9`` fails steps over to a live peer,
  the respawned replica re-warms from the checkpoint artifact (never a
  re-bind), every degrade policy resolves dark-pool steps as specified;
* chaos — the rpc sites replay deterministically under a pinned seed
  (the property the CI chaos leg pins with ``CHAOS_SEED=7``).

Process budget: the module-scoped pool serves most end-to-end tests; the
dark-pool degrade tests spawn their own single-replica pools (they must
kill them).
"""
from __future__ import annotations

import os
import socket
import time

import numpy as np
import pytest

from repro.core.executor import executor_for
from repro.core.ops import (EmbeddingOp, EmbeddingProgram,
                            make_program_inputs, single_op_program)
from repro.runtime.embedding_service import (ServicePool, program_to_spec,
                                             spec_to_program)
from repro.runtime.faults import (FaultInjector, FaultSpec, InjectedFailure,
                                  MalformedAccessError, RpcError, RpcTimeout,
                                  ServiceUnavailable)
from repro.runtime.rpc import (RpcClient, backoff_delays, raise_typed,
                               recv_msg, send_msg)

BACKOFF = dict(rpc_timeout_s=30.0, backoff_s=0.01)


def _program() -> EmbeddingProgram:
    sls = EmbeddingOp("sls", num_segments=8, num_embeddings=64, emb_len=16,
                      avg_lookups=4, weighted=True)
    gather = EmbeddingOp("gather", num_segments=6, num_embeddings=32,
                         emb_len=16, block_rows=2)
    return EmbeddingProgram("disagg_prog", (("sls0", sls), ("g0", gather)))


def _assert_outputs_equal(a: dict, b: dict) -> None:
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


@pytest.fixture(scope="module")
def pool():
    with ServicePool(2, **BACKOFF) as p:
        yield p


# ---------------------------------------------------------------------------
# Transport
# ---------------------------------------------------------------------------

def test_pool_refuses_to_spawn_when_this_process_holds_the_tpu(
        monkeypatch, tmp_path):
    """Replicas would block on the chip's lock: fail at once instead."""
    from repro.runtime import embedding_service as es
    monkeypatch.setattr(es, "_holds_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="already holds the TPU"):
        ServicePool(2, warm_dir=tmp_path)
    assert not any(tmp_path.iterdir())


def test_framing_roundtrip_bit_identical():
    a, b = socket.socketpair()
    arrays = {"f32": np.random.default_rng(0).normal(size=(7, 3)).astype(
                  np.float32),
              "i32": np.arange(11, dtype=np.int32),
              "i64": np.arange(5, dtype=np.int64) * -3,
              "empty": np.zeros((0,), np.int32)}
    send_msg(a, "step", {"seq": 42, "client": "c1"}, arrays)
    kind, meta, out = recv_msg(b, deadline_s=5.0)
    assert kind == "step" and meta == {"seq": 42, "client": "c1"}
    assert set(out) == set(arrays)
    for k in arrays:
        assert out[k].dtype == arrays[k].dtype
        np.testing.assert_array_equal(out[k], arrays[k])
    a.close(), b.close()


def test_recv_deadline_lapses_typed():
    a, b = socket.socketpair()
    t0 = time.perf_counter()
    with pytest.raises(RpcTimeout):
        recv_msg(b, deadline_s=0.2)
    assert time.perf_counter() - t0 < 5.0
    # a partial frame (header promised, body never sent) times out too —
    # the deadline spans partial reads, it is not per-chunk
    send_msg(a, "step", {"n": 1}, None)
    a.send(b"EMB1")                     # start of a frame that never ends
    recv_msg(b, deadline_s=5.0)         # the complete frame drains fine
    with pytest.raises(RpcTimeout):
        recv_msg(b, deadline_s=0.2)
    a.close(), b.close()


def test_closed_connection_is_typed_rpc_error():
    a, b = socket.socketpair()
    a.close()
    with pytest.raises(RpcError):
        recv_msg(b, deadline_s=5.0)
    b.close()


def test_backoff_matches_spawn_retry_shape():
    assert list(backoff_delays(4, 0.5)) == [0.0, 0.5, 1.0, 2.0]
    assert list(backoff_delays(1, 0.5)) == [0.0]


def test_raise_typed_preserves_class_and_degrades_multiarg():
    with pytest.raises(InjectedFailure):
        raise_typed({"error": "InjectedFailure", "msg": "boom"})
    # MalformedAccessError's 3-arg constructor can't rebuild from one
    # message: it degrades to the base fault with the name preserved
    with pytest.raises(Exception, match="MalformedAccessError"):
        raise_typed({"error": "MalformedAccessError", "msg": "bad ptrs"})


def test_program_spec_roundtrip():
    prog = _program()
    back = spec_to_program(program_to_spec(prog))
    assert back.signature() == prog.signature()
    assert back.name == prog.name


# ---------------------------------------------------------------------------
# End-to-end: bit identity, replay, failover, re-warm
# ---------------------------------------------------------------------------

def test_disagg_bit_identical_to_inproc(pool):
    prog = _program()
    ins = make_program_inputs(prog, seed=3)
    ref = executor_for(prog, backend="jax").run_steps([ins] * 3)
    ex = executor_for(prog, backend="jax", service="disagg",
                      service_pool=pool)
    out = ex.run_steps([ins] * 3)
    for r, o in zip(ref, out):
        _assert_outputs_equal(r, o)
    assert ex.stats["rpc_steps"] == 3


def test_step_replay_is_idempotent(pool):
    """Re-sending an already-executed sequence number (the lost-reply
    retry shape) returns the cached reply without re-executing."""
    prog = _program()
    ins = make_program_inputs(prog, seed=4)
    ex = executor_for(prog, backend="jax", service="disagg",
                      service_pool=pool)
    ex.step(ins)                        # ensures tables are bound
    r = next(r for r in pool.replicas if r.state == "live")
    cli = RpcClient("127.0.0.1", r.port, timeout_s=30.0)
    streams = {}
    for name, op in prog.ops:
        tkey = "x" if op.kind == "fusedmm" else "table"
        streams.update({f"{name}/{k}": np.asarray(v)
                        for k, v in ins[name].items() if k != tkey})
    meta = {"client": "replay-test", "seq": 1}
    m1, out1 = cli.call("step", meta, streams)
    steps_after_first = m1["steps"]
    m2, out2 = cli.call("step", meta, streams)     # same seq: replayed
    _assert_outputs_equal(out1, out2)
    ping, _ = cli.call("ping")
    assert ping["replays"] >= 1
    assert ping["steps"] == steps_after_first + 1  # did NOT re-execute
    # a stale (lower) seq is a typed protocol error, not silence
    m3, _ = cli.call("step", {"client": "replay-test", "seq": 2}, streams)
    with pytest.raises(RpcError, match="stale"):
        cli.call("step", meta, streams)
    cli.close()


def test_kill_replica_fails_over_and_rewarms(pool):
    """SIGKILL one replica mid-traffic: steps keep answering through the
    live peer (bounded retry, zero failures), the circuit opens, and the
    respawned replica re-warms from the checkpoint artifact — never a
    re-bind RPC."""
    prog = _program()
    ins = make_program_inputs(prog, seed=5)
    ref = executor_for(prog, backend="jax").step(ins)
    ex = executor_for(prog, backend="jax", service="disagg",
                      service_pool=pool)
    _assert_outputs_equal(ref, ex.step(ins))

    victim = next(i for i, r in enumerate(pool.replicas)
                  if r.state == "live")
    pool.kill_replica(victim)
    for _ in range(4):                  # round-robin hits the corpse
        _assert_outputs_equal(ref, ex.step(ins))
    assert pool.stats()["breaker_open"] >= 1

    t0 = time.perf_counter()
    while pool.replicas[victim].state != "live":
        pool.heartbeat_once()
        time.sleep(0.05)
        assert time.perf_counter() - t0 < 120, "revive timed out"
    s = pool.stats()
    assert s["respawns"] >= 1
    assert s["warm_sources"][-1] == "artifact"     # re-warmed, not re-bound
    assert s["recoveries_s"], "recovery time not recorded"
    for _ in range(3):                  # the revived replica serves
        _assert_outputs_equal(ref, ex.step(ins))


# ---------------------------------------------------------------------------
# Degradation while every replica is dark
# ---------------------------------------------------------------------------

def _dark_pool():
    return ServicePool(1, auto_respawn=False, **BACKOFF)


def test_dark_pool_degrade_fail_is_typed():
    prog = single_op_program(
        EmbeddingOp("sls", num_segments=4, num_embeddings=32, emb_len=8,
                    avg_lookups=2), "s")
    ins = make_program_inputs(prog, seed=6)
    with _dark_pool() as pool:
        ex = executor_for(prog, backend="jax", service="disagg",
                          service_pool=pool)
        ex.step(ins)
        pool.kill_replica(0)
        time.sleep(0.1)
        with pytest.raises(ServiceUnavailable):
            ex.step(ins)
        assert ex.stats["degraded_failed_steps"] == 1


def test_dark_pool_degrade_stale_serves_locally():
    prog = single_op_program(
        EmbeddingOp("sls", num_segments=4, num_embeddings=32, emb_len=8,
                    avg_lookups=2), "s")
    ins = make_program_inputs(prog, seed=7)
    ref = executor_for(prog, backend="jax").step(ins)
    with _dark_pool() as pool:
        ex = executor_for(prog, backend="jax", service="disagg",
                          service_pool=pool, degrade_policy="stale")
        _assert_outputs_equal(ref, ex.step(ins))
        pool.kill_replica(0)
        time.sleep(0.1)
        _assert_outputs_equal(ref, ex.step(ins))   # stale = local tables
        assert ex.stats["stale_steps"] == 1


def test_dark_pool_hot_slab_serves_under_fail_policy():
    """An all-hot step (every index in the replicated Zipf head) serves
    locally even under ``degrade_policy="fail"`` — only cold lookups pay
    the policy."""
    op = EmbeddingOp("sls", num_segments=4, num_embeddings=32, emb_len=8,
                     avg_lookups=2)
    prog = single_op_program(op, "s")
    ins = make_program_inputs(prog, seed=8)
    ref = executor_for(prog, backend="jax").step(ins)
    with _dark_pool() as pool:
        ex = executor_for(prog, backend="jax", service="disagg",
                          service_pool=pool,
                          hot_rows={"s": np.arange(32)})   # whole vocab hot
        _assert_outputs_equal(ref, ex.step(ins))
        pool.kill_replica(0)
        time.sleep(0.1)
        _assert_outputs_equal(ref, ex.step(ins))
        assert ex.stats["hot_local_steps"] == 1
        assert ex.stats["degraded_failed_steps"] == 0


# ---------------------------------------------------------------------------
# Chaos: deterministic replay on the rpc sites
# ---------------------------------------------------------------------------

def _chaos_run(seed: int) -> tuple:
    # the CI chaos leg pins CHAOS_SEED=7; the schedule must replay
    # bit-identically under whatever seed is pinned
    seed = int(os.environ.get("CHAOS_SEED", seed))
    prog = single_op_program(
        EmbeddingOp("sls", num_segments=4, num_embeddings=32, emb_len=8,
                    avg_lookups=2), "c")
    ins = make_program_inputs(prog, seed=9)
    ref = executor_for(prog, backend="jax").step(ins)
    faults = FaultInjector([FaultSpec("rpc_send", at=(4,)),
                            FaultSpec("rpc_recv", at=(3,))], seed=seed)
    with ServicePool(2, faults=faults, **BACKOFF) as pool:
        ex = executor_for(prog, backend="jax", service="disagg",
                          service_pool=pool)
        for _ in range(5):
            _assert_outputs_equal(ref, ex.step(ins))
        stats = pool.stats()
    return faults.stats(), stats["retries"] + stats["failovers"]


def test_rpc_chaos_replays_deterministically():
    """A pinned-seed schedule severing an rpc_send and an rpc_recv fires
    at identical call ordinals across runs, and the bounded retry heals
    every step — no request-visible failure."""
    s1, healed1 = _chaos_run(seed=7)
    s2, healed2 = _chaos_run(seed=7)
    assert s1["log"] == s2["log"] and s1["fired"] == 2
    assert healed1 >= 1 and healed1 == healed2


def test_service_crash_site_respawns_clean():
    """A --crash-at schedule makes the replica self-kill (os._exit) at a
    step ordinal; the pool heals the step and the respawned process runs
    WITHOUT the schedule — recovery terminates."""
    prog = single_op_program(
        EmbeddingOp("sls", num_segments=4, num_embeddings=32, emb_len=8,
                    avg_lookups=2), "k")
    ins = make_program_inputs(prog, seed=10)
    ref = executor_for(prog, backend="jax").step(ins)
    with ServicePool(2, crash_at={0: (2,)}, chaos_seed=7,
                     **BACKOFF) as pool:
        ex = executor_for(prog, backend="jax", service="disagg",
                          service_pool=pool)
        for _ in range(6):              # replica 0 dies at its 2nd step
            _assert_outputs_equal(ref, ex.step(ins))
        t0 = time.perf_counter()
        while any(r.state != "live" for r in pool.replicas):
            pool.heartbeat_once()
            time.sleep(0.05)
            assert time.perf_counter() - t0 < 120, "revive timed out"
        assert pool.replicas[0].spawns == 2       # exactly one extra life
        for _ in range(3):
            _assert_outputs_equal(ref, ex.step(ins))
