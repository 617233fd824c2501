"""AOT serving artifact (core/artifact.py) + warm-artifact durability.

Three layers, matching the PR-10 surface:

* artifact round trip — an ``executor_for(artifact_dir=...)`` boot saves
  the compiled program + serialized executables; a later boot (caches
  cleared = a fresh process, modulo jax's own jit caches) loads instead
  of compiling, with bit-identical outputs.  Every invalidation leg
  (fingerprint skew, identity mismatch, torn publish, corrupt AOT blob)
  must fall back to a *fresh compile that still serves* — a stale
  artifact can cost time, never numerics;
* durability bugfixes — ``program.json`` publishes fsync-before-rename
  through the ckpt tier's shared helper (bugfix 1), and the meta/tables
  pair can never be observed inconsistent: tables commit first, the meta
  stamp is cross-checked at read (bugfix 2).  The ordering tests
  monkeypatch the checkpoint layer and FAIL on the pre-fix code;
* disaggregated tier — a killed replica's respawn boots from the AOT
  artifact (``compile_source == "artifact"``), not a recompile.
"""
from __future__ import annotations

import json
import os
import pickle
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core import artifact as art
from repro.core.executor import clear_executor_cache, executor_for
from repro.core.ops import (EmbeddingOp, EmbeddingProgram,
                            make_program_inputs)
from repro.core.pipeline import clear_compile_cache
from repro.runtime import embedding_service as es


def _program(name: str = "artifact_prog") -> EmbeddingProgram:
    sls = EmbeddingOp("sls", num_segments=8, num_embeddings=64, emb_len=16,
                      avg_lookups=4, weighted=True)
    gather = EmbeddingOp("gather", num_segments=6, num_embeddings=32,
                         emb_len=16, block_rows=2)
    return EmbeddingProgram(name, (("sls0", sls), ("g0", gather)))


def _boot(artifact_dir, **kw):
    """One 'process boot': cleared executor/compile caches, then
    executor_for + first step (where the first-compile save and the AOT
    capture happen)."""
    clear_executor_cache()
    clear_compile_cache()
    prog = _program()
    ins = make_program_inputs(prog, seed=0)
    ex = executor_for(prog, artifact_dir=artifact_dir, **kw)
    outs = {k: np.asarray(v) for k, v in ex.step(ins).items()}
    return ex, outs


def _assert_outputs_equal(a: dict, b: dict) -> None:
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


# ---------------------------------------------------------------------------
# Round trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["pallas", "jax"])
def test_round_trip_loads_and_is_bit_identical(tmp_path, backend):
    art.reset_artifact_stats()
    ex, outs = _boot(tmp_path, backend=backend)
    assert ex.compile_source == "fresh"
    # executor_for already published the compile payload; this re-save
    # adds the AOT executables the step above specialized
    ex.save_artifact()
    assert (tmp_path / "current.COMMITTED").exists()

    ex2, outs2 = _boot(tmp_path, backend=backend)
    assert ex2.compile_source == "artifact"
    assert ex2.aot.stats["loads"] >= 1, ex2.aot.stats
    assert ex2.aot.stats["compiles"] == 0, ex2.aot.stats
    _assert_outputs_equal(outs, outs2)
    s = art.artifact_stats()
    assert s["loads"] >= 1 and s["aot_deserialized"] >= 1
    assert s["rejects"] == {}


def test_boot_save_alone_hydrates_compile_cache(tmp_path):
    """Even before any step ran (no AOT blobs yet), the boot-time save at
    executor_for means a second boot skips the PassManager: it loads the
    compile payload and AOT-compiles the kernels on first touch."""
    art.reset_artifact_stats()
    clear_executor_cache()
    clear_compile_cache()
    prog = _program()
    executor_for(prog, backend="jax", artifact_dir=tmp_path)

    clear_executor_cache()
    clear_compile_cache()
    ex2 = executor_for(prog, backend="jax", artifact_dir=tmp_path)
    assert ex2.compile_source == "artifact"
    outs = ex2.step(make_program_inputs(prog, seed=0))
    assert set(outs) == {"sls0", "g0"}
    assert ex2.aot.stats["compiles"] >= 1        # blobs weren't saved yet


# ---------------------------------------------------------------------------
# Invalidation: every reject leg falls back to a fresh compile that serves
# ---------------------------------------------------------------------------

def test_fingerprint_skew_rejects_and_counts(tmp_path):
    art.reset_artifact_stats()
    ex, outs = _boot(tmp_path, backend="jax")
    ex.save_artifact()
    mp = tmp_path / "current" / "meta.json"
    raw = json.loads(mp.read_text())
    raw["fingerprint"]["jax"] = "0.0.0-skewed"
    mp.write_text(json.dumps(raw))

    ex2, outs2 = _boot(tmp_path, backend="jax")
    assert ex2.compile_source == "fresh"
    s = art.artifact_stats()
    assert s["rejects"].get("fingerprint") == 1
    assert s["fresh_compiles"] >= 1              # the runbook counter
    _assert_outputs_equal(outs, outs2)


def test_identity_mismatch_rejects(tmp_path):
    art.reset_artifact_stats()
    ex, _ = _boot(tmp_path, backend="jax")
    ex.save_artifact()
    # same program, different opt_level: a different compile identity
    ex2, _ = _boot(tmp_path, backend="jax", opt_level="O2")
    assert ex2.compile_source == "fresh"
    assert art.artifact_stats()["rejects"].get("identity") == 1


def test_format_bump_rejects(tmp_path):
    art.reset_artifact_stats()
    ex, _ = _boot(tmp_path, backend="jax")
    ex.save_artifact()
    mp = tmp_path / "current" / "meta.json"
    raw = json.loads(mp.read_text())
    raw["format"] = art.FORMAT_VERSION + 1
    mp.write_text(json.dumps(raw))
    ex2, _ = _boot(tmp_path, backend="jax")
    assert ex2.compile_source == "fresh"
    assert art.artifact_stats()["rejects"].get("format") == 1


def test_torn_publish_rejects_and_serves_fresh(tmp_path):
    """Commit marker present but the directory contents gone — the crash
    window publish_dir leaves when dying between rename and marker."""
    art.reset_artifact_stats()
    ex, outs = _boot(tmp_path, backend="jax")
    ex.save_artifact()
    (tmp_path / "current" / "meta.json").unlink()

    ex2, outs2 = _boot(tmp_path, backend="jax")
    assert ex2.compile_source == "fresh"
    assert art.artifact_stats()["rejects"].get("torn") == 1
    _assert_outputs_equal(outs, outs2)


def test_corrupt_aot_blob_falls_back_per_key(tmp_path):
    """A payload that fails to deserialize (skew the fingerprint could not
    see) falls back to a live lower+compile for that key alone — the boot
    still counts as an artifact boot and numerics are unchanged."""
    art.reset_artifact_stats()
    ex, outs = _boot(tmp_path, backend="jax")
    ex.save_artifact()
    ap = tmp_path / "current" / "aot.pkl"
    payloads = pickle.loads(ap.read_bytes())
    assert payloads, "save captured no AOT payloads"
    ap.write_bytes(pickle.dumps({k: b"garbage" for k in payloads}))

    ex2, outs2 = _boot(tmp_path, backend="jax")
    assert ex2.compile_source == "artifact"
    assert ex2.aot.stats["fallbacks"] >= 1
    assert ex2.aot.stats["loads"] == 0
    _assert_outputs_equal(outs, outs2)


def test_round_trip_sharded_with_hot_slab(run_on_mesh):
    """2-device mesh + hot-slab identity: the artifact round-trips under
    sharded execution, and a different hot spec is a different identity
    (fresh compile), since the hot split changes the AccessPlan."""
    code = """
        import tempfile
        import jax
        import numpy as np
        from repro.core import artifact as art
        from repro.core.executor import clear_executor_cache, executor_for
        from repro.core.ops import (EmbeddingOp, EmbeddingProgram,
                                    make_program_inputs)
        from repro.core.pipeline import clear_compile_cache
        from repro.launch.mesh import axis_types_kw, model_shard_count

        mesh = jax.make_mesh((1, 2), ("data", "model"), **axis_types_kw(2))
        assert model_shard_count(mesh) == 2
        prog = EmbeddingProgram("mesh_prog", (
            ("a", EmbeddingOp("sls", 5, 9, 8, avg_lookups=3,
                              weighted=True)),
            ("g", EmbeddingOp("gather", 6, 20, 8)),
        ))
        hot = {"a": (0, 1)}
        ins = make_program_inputs(prog, seed=0)

        def boot(hot_rows, td):
            clear_executor_cache(); clear_compile_cache()
            ex = executor_for(prog, "O3", vlen=4, backend="jax",
                              mesh=mesh, hot_rows=hot_rows,
                              artifact_dir=td)
            outs = {k: np.asarray(v) for k, v in ex.step(ins).items()}
            return ex, outs

        with tempfile.TemporaryDirectory() as td:
            ex, outs = boot(hot, td)
            assert ex.compile_source == "fresh"
            ex.save_artifact()
            ex2, outs2 = boot(hot, td)
            assert ex2.compile_source == "artifact", ex2.compile_source
            assert ex2.shards == 2
            for k in outs:
                np.testing.assert_array_equal(outs[k], outs2[k])
            ex3, _ = boot({"a": (0, 2)}, td)
            assert ex3.compile_source == "fresh"
            assert art.artifact_stats()["rejects"].get("identity", 0) >= 1
        print("ARTIFACT_MESH_OK")
    """
    run_on_mesh(code, devices=2, sentinel="ARTIFACT_MESH_OK")


# ---------------------------------------------------------------------------
# Bugfix 1: program.json publishes durably through the shared ckpt helper
# ---------------------------------------------------------------------------

def test_atomic_write_text_fsyncs_data_and_directory(tmp_path, monkeypatch):
    """The tmp file is fsynced before the rename and the directory after —
    the two syncs a bare write_text+rename skips (the torn-publish window
    bugfix 1 closes)."""
    from repro.checkpoint import atomic_write_text
    count = {"n": 0}
    real = os.fsync

    def counting(fd):
        count["n"] += 1
        return real(fd)

    monkeypatch.setattr(os, "fsync", counting)
    atomic_write_text(tmp_path / "program.json", "{\"v\": 1}")
    assert json.loads((tmp_path / "program.json").read_text()) == {"v": 1}
    assert count["n"] >= 2, "missing data fsync or directory fsync"
    assert not list(tmp_path.glob(".*tmp*")), "tmp file left behind"


def test_warm_meta_routes_through_durable_publish(tmp_path, monkeypatch):
    """Both program.json writers — ``write_warm_artifact`` and the pool's
    ``publish_hot_spec`` — must go through the ckpt tier's
    ``atomic_write_text``, not a private rename.  Fails on the pre-fix
    code, which renamed without any fsync."""
    import repro.checkpoint as ckpt
    calls = []
    real = ckpt.atomic_write_text

    def spy(path, text):
        calls.append(Path(path).name)
        return real(path, text)

    monkeypatch.setattr(ckpt, "atomic_write_text", spy)
    meta, tables = _warm_fixture()
    es.write_warm_artifact(tmp_path, meta, tables, 1)
    assert calls == ["program.json"]

    pool = SimpleNamespace(_bind_call=(meta, tables), warm_dir=tmp_path,
                           pool_stats={"hot_publishes": 0},
                           _broadcast=lambda *a, **k: None,
                           _table_version=1)
    es.ServicePool.publish_hot_spec(pool, {"sls0": (1, 3)})
    assert calls == ["program.json", "program.json"]
    republished = json.loads((tmp_path / "program.json").read_text())
    assert republished["hot_spec"] == {"sls0": [1, 3]}
    assert republished["table_step"] == 1     # still the committed step


# ---------------------------------------------------------------------------
# Bugfix 2: the meta/tables pair is never observed inconsistent
# ---------------------------------------------------------------------------

def _warm_fixture(seed: int = 0):
    prog = _program("warm_prog")
    meta = {"program": es.program_to_spec(prog), "opt_level": "O3",
            "vlen": 128, "backend": "jax", "index_policy": "strict",
            "interpret": False, "table_ops": ["g0", "sls0"],
            "hot_spec": None, "hot_epoch": 0}
    rng = np.random.default_rng(seed)
    tables = {"sls0": rng.standard_normal((64, 16)).astype(np.float32),
              "g0": rng.standard_normal((32, 16)).astype(np.float32)}
    return meta, tables


def test_tables_commit_before_meta_publishes(tmp_path, monkeypatch):
    """Pins the write order: when the table checkpoint fails, the
    previously-published program.json must survive untouched.  The
    pre-fix order (meta first) would leave a new meta pointing at tables
    that never committed."""
    import repro.checkpoint as ckpt
    meta, tables = _warm_fixture()
    es.write_warm_artifact(tmp_path, meta, tables, 1)
    got = es.read_warm_artifact(tmp_path)
    assert got is not None and got[0]["table_step"] == 1

    def boom(*a, **k):
        raise OSError("disk full")

    monkeypatch.setattr(ckpt, "save_checkpoint", boom)
    meta2 = dict(meta)
    meta2["hot_epoch"] = 7
    with pytest.raises(OSError):
        es.write_warm_artifact(tmp_path, meta2, tables, 2)
    got = es.read_warm_artifact(tmp_path)
    assert got is not None, "consistent pair lost on failed update"
    assert got[0]["table_step"] == 1
    assert got[0]["hot_epoch"] == 0, "uncommitted meta became visible"


def test_meta_referencing_uncommitted_step_is_rejected(tmp_path):
    """A meta stamped with a step the checkpoint tier never committed
    (torn pair, or pre-stamp code paired with foreign tables) reads as
    no-artifact — the replica re-binds instead of warming inconsistent."""
    meta, tables = _warm_fixture()
    es.write_warm_artifact(tmp_path, meta, tables, 1)
    pj = tmp_path / "program.json"
    m = json.loads(pj.read_text())
    m["table_step"] = 99
    pj.write_text(json.dumps(m))
    assert es.read_warm_artifact(tmp_path) is None


def test_tables_ahead_of_meta_restores_stamped_pair(tmp_path):
    """Crash between the table commit and the meta publish: the reader
    must restore the step the surviving meta stamps — the previous
    consistent pair — not the newer orphaned tables."""
    from repro.checkpoint import save_checkpoint
    meta, tables = _warm_fixture()
    es.write_warm_artifact(tmp_path, meta, tables, 1)
    _, newer = _warm_fixture(seed=9)
    save_checkpoint(tmp_path / "tables", 2,
                    {op: np.asarray(a) for op, a in newer.items()})

    got = es.read_warm_artifact(tmp_path)
    assert got is not None
    got_meta, got_tables = got
    assert got_meta["table_step"] == 1
    np.testing.assert_array_equal(got_tables["sls0"], tables["sls0"])


def test_legacy_meta_without_stamp_reads_latest_committed(tmp_path):
    """A pre-stamp program.json (no table_step) still warms, best-effort
    paired with the latest committed step."""
    meta, tables = _warm_fixture()
    es.write_warm_artifact(tmp_path, meta, tables, 3)
    pj = tmp_path / "program.json"
    m = json.loads(pj.read_text())
    del m["table_step"]
    pj.write_text(json.dumps(m))
    got = es.read_warm_artifact(tmp_path)
    assert got is not None
    np.testing.assert_array_equal(got[1]["sls0"], tables["sls0"])


def test_table_step_retention_keeps_superseded_pair(tmp_path):
    """Keep-2 pruning: after N publishes the step the *previous* meta
    references is still restorable (one full publish cycle of grace)."""
    from repro.checkpoint import committed_steps
    meta, tables = _warm_fixture()
    for v in (1, 2, 3, 4):
        es.write_warm_artifact(tmp_path, meta, tables, v)
    assert committed_steps(tmp_path / "tables") == [3, 4]


# ---------------------------------------------------------------------------
# Disaggregated tier: respawn boots from the AOT artifact
# ---------------------------------------------------------------------------

def test_respawned_replica_skips_recompilation():
    """Kill a replica; the respawned process must report BOTH
    ``rewarm_source == "artifact"`` (tables from checkpoint, PR 8) and
    ``compile_source == "artifact"`` (program from the AOT artifact the
    first life saved after its first step — the PR-10 tentpole)."""
    prog = _program("disagg_aot")
    ins = make_program_inputs(prog, seed=5)
    ref = executor_for(prog, backend="jax").step(ins)
    with es.ServicePool(2, rpc_timeout_s=30.0, backoff_s=0.01) as pool:
        ex = executor_for(prog, backend="jax", service="disagg",
                          service_pool=pool)
        _assert_outputs_equal(ref, ex.step(ins))

        victim = next(i for i, r in enumerate(pool.replicas)
                      if r.state == "live")
        pool.kill_replica(victim)
        for _ in range(4):              # failover keeps serving
            _assert_outputs_equal(ref, ex.step(ins))

        t0 = time.perf_counter()
        while pool.replicas[victim].state != "live":
            pool.heartbeat_once()
            time.sleep(0.05)
            assert time.perf_counter() - t0 < 120, "revive timed out"
        s = pool.stats()
        assert s["respawns"] >= 1
        assert s["warm_sources"][-1] == "artifact"
        assert s["compile_sources"][-1] == "artifact", \
            "respawned replica recompiled instead of loading the artifact"
        for _ in range(3):              # the loaded program serves
            _assert_outputs_equal(ref, ex.step(ins))
