"""End-to-end system behaviour: training convergence, checkpoint/restart,
failure injection + supervised restart, straggler watchdog, decode server."""
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_reduced
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.models import LM
from repro.runtime.server import DecodeServer, Request
from repro.runtime.trainer import (InjectedFailure, StragglerTimeout,
                                   Trainer, TrainerConfig, run_supervised)

REPO = Path(__file__).resolve().parents[1]


def _mk(tmp_path, arch="stablelm-3b", steps=24, **kw):
    cfg = get_reduced(arch)
    lm = LM(cfg)
    data = SyntheticTokens(DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                                      global_batch=8))
    tcfg = TrainerConfig(total_steps=steps, ckpt_every=8,
                         ckpt_dir=str(tmp_path / "ckpt"), **kw)
    return Trainer(lm, data, tcfg)


def test_training_loss_decreases(tmp_path):
    out = _mk(tmp_path, steps=30).run(jax.random.PRNGKey(0))
    losses = out["losses"]
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.05, losses


def test_checkpoint_restart_continuity(tmp_path):
    t1 = _mk(tmp_path, steps=16)
    out1 = t1.run(jax.random.PRNGKey(0))
    assert out1["final_step"] == 15
    # a fresh trainer resumes from the committed step and finishes further
    t2 = _mk(tmp_path, steps=24)
    out2 = t2.run(jax.random.PRNGKey(0))
    assert out2["final_step"] == 23
    # resumed run only executed the remaining steps
    assert len(out2["losses"]) == 24 - 16


def test_supervised_restart_after_injected_failures(tmp_path):
    out = run_supervised(lambda: _mk(tmp_path, steps=30),
                         jax.random.PRNGKey(0),
                         failure_schedule={10, 20})
    assert out["restarts"] == 2
    assert out["final_step"] == 29


def test_straggler_watchdog(tmp_path):
    t = _mk(tmp_path, steps=5, step_deadline_s=1e-9)
    with pytest.raises(StragglerTimeout):
        t.run(jax.random.PRNGKey(0))


def test_grad_compression_training(tmp_path):
    out = _mk(tmp_path, steps=30, grad_compression=True).run(
        jax.random.PRNGKey(0))
    losses = out["losses"]
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.03, losses


def test_decode_server_drains(tmp_path):
    cfg = get_reduced("h2o-danube-1.8b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    srv = DecodeServer(lm, params, batch_slots=2, max_len=64)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, 4).astype(np.int32),
                    max_new_tokens=5) for _ in range(5)]
    for r in reqs:
        srv.submit(r)
    srv.run_until_drained()
    assert all(r.done for r in reqs)
    assert all(len(r.out) == 5 for r in reqs)


def test_elastic_checkpoint_reshard(tmp_path):
    """Save on one sharding layout, restore onto another (subprocess with 8
    fake devices exercises the offset-based assembly)."""
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.checkpoint import save_checkpoint, restore_checkpoint
        from repro.launch.mesh import axis_types_kw
        mesh8 = jax.make_mesh((8,), ("model",), **axis_types_kw(1))
        x = jnp.arange(64.0).reshape(16, 4)
        xs = jax.device_put(x, NamedSharding(mesh8, P("model", None)))
        save_checkpoint(r"{tmp_path}", 7, {{"w": xs}})
        # restore onto a DIFFERENT mesh (2-way) — elastic rescale
        mesh2 = jax.make_mesh((2, 4), ("a", "b"), **axis_types_kw(2))
        tgt = NamedSharding(mesh2, P("b", None))
        out, step = restore_checkpoint(r"{tmp_path}", {{"w": x}},
                                       shardings={{"w": tgt}})
        assert step == 7
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(x))
        print("ELASTIC_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True,
                       env={**__import__("os").environ,
                            "PYTHONPATH": "src"}, cwd=str(REPO))
    assert "ELASTIC_OK" in r.stdout, r.stderr[-2000:]
