"""Launch-layer units: sharding rules, roofline parsing, shape gating,
and an end-to-end dry-run cell on a tiny in-process mesh (subprocess)."""
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, get_reduced, list_archs
from repro.launch import sharding as shd
from repro.launch.mesh import axis_types_kw
from repro.launch.steps import SHAPES, make_batch_struct, shape_applicable
from repro.roofline.analysis import (analytic_flops, collective_bytes_from_hlo,
                                     model_flops, roofline_terms)

REPO = Path(__file__).resolve().parents[1]


def test_param_specs_structure():
    cfg = get_reduced("qwen3-moe-235b-a22b")
    from repro.models import LM
    params = jax.eval_shape(LM(cfg).init, jax.random.PRNGKey(0))
    specs = shd.param_specs(params)
    # embedding: vocab -> model
    assert specs["embed"] == P("model", None)
    # scanned MoE experts: (n_super, E, D, F) -> experts on model
    leaf = specs["scan"][0]["moe"]["wi_gate"]
    assert leaf == P(None, "model", None, None)
    # router replicated
    assert all(s is None for s in specs["scan"][0]["moe"]["router"])
    # attn col/row parallel
    assert specs["scan"][0]["attn"]["wq"][-1] == "model"
    assert specs["scan"][0]["attn"]["wo"][1] == "model"


def test_sanitize_drops_nondivisible():
    mesh = jax.make_mesh((1,), ("model",), **axis_types_kw(1))
    # shape 6 over model=1 fine; simulate bigger axis via fake mesh entry
    specs = {"a": P("model", None)}
    tree = {"a": jax.ShapeDtypeStruct((6, 4), jnp.float32)}
    out = shd.sanitize_specs(specs, tree, mesh)
    assert out["a"] == P("model", None)


def test_shape_gating_matrix():
    """The 40-cell applicability matrix: long_500k only for sub-quadratic."""
    runnable = {(a, s) for a in list_archs() for s in SHAPES
                if shape_applicable(get_config(a), s) is None}
    assert len(runnable) == 32
    assert ("xlstm-1.3b", "long_500k") in runnable
    assert ("zamba2-7b", "long_500k") in runnable
    assert ("gemma3-4b", "long_500k") not in runnable


def test_batch_struct_shapes():
    cfg = get_config("whisper-large-v3")
    b = make_batch_struct(cfg, 4096, 256, "train")
    assert b["tokens"].shape == (256, 4096)
    assert b["enc_embeds"].shape == (256, 4096, cfg.d_model)
    d = make_batch_struct(cfg, 32768, 128, "decode")
    assert d["tokens"].shape == (128, 1)


def test_collective_parser_hlo_form():
    hlo = """
    %ar = bf16[256,1024] all-reduce(%x), replica_groups={}
    %ag = f32[64,64] all-gather(%y), dimensions={0}
    %noise = bf16[8,8] add(%a, %b)
    %a2a = (bf16[4,4], bf16[4,4]) all-to-all(%p, %q)
    """
    got = collective_bytes_from_hlo(hlo)
    want = 256 * 1024 * 2 + 64 * 64 * 4 + 2 * 4 * 4 * 2
    assert got == want, (got, want)


def test_collective_parser_stablehlo_region():
    hlo = '''
    %0 = "stablehlo.all_reduce"(%arg) ({
      ^bb0(%a: tensor<f32>, %b: tensor<f32>):
        stablehlo.return %c : tensor<f32>
    }) : (tensor<128x64xbf16>) -> tensor<128x64xbf16>
    '''
    got = collective_bytes_from_hlo(hlo)
    assert got == 128 * 64 * 2, got


def test_roofline_terms_bottleneck():
    r = roofline_terms(flops=197e12, bytes_accessed=0.0, collective_bytes=0.0,
                       n_chips=1, device_kind="TPU v5 lite")
    assert r["bottleneck"] == "compute"
    assert abs(r["compute_s"] - 1.0) < 1e-9
    r2 = roofline_terms(flops=0.0, bytes_accessed=819e9,
                        collective_bytes=0.0, n_chips=1,
                        device_kind="TPU v5 lite")
    assert r2["bottleneck"] == "memory"


def test_peaks_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        roofline_terms(flops=1.0, bytes_accessed=0.0, collective_bytes=0.0,
                       n_chips=1, device_kind="cpu")


def test_model_flops_sane():
    cfg = get_config("stablelm-3b")
    mf = model_flops(cfg, 4096, 256, "train")
    # ~2.8B params * 6 * 1M tokens ≈ 1.7e16
    assert 5e15 < mf < 5e16
    af = analytic_flops(cfg, 4096, 256, "train")
    assert af > mf  # attention adds on top


def test_dryrun_cell_tiny_mesh():
    """The whole dry-run machinery on an 8-device fake mesh (subprocess so
    the device-count flag is fresh)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.configs import get_reduced
        from repro.launch.mesh import axis_types_kw
        from repro.launch.steps import build_bundle
        import repro.launch.steps as steps
        steps.SHAPES = {"train_4k": (32, 8, "train")}
        mesh = jax.make_mesh((2, 4), ("data", "model"), **axis_types_kw(2))
        cfg = get_reduced("gemma3-4b")
        with jax.set_mesh(mesh):
            b = build_bundle(cfg, mesh, "train_4k", remat="none")
            c = jax.jit(b.fn, in_shardings=b.in_shardings
                        ).lower(*b.args).compile()
            ca = c.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            assert ca.get("flops", 0) > 0
        print("TINY_DRYRUN_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True,
                       env={**__import__("os").environ, "PYTHONPATH": "src"},
                       cwd=str(REPO), timeout=600)
    assert "TINY_DRYRUN_OK" in r.stdout, r.stderr[-2000:]


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_dir_wins(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there and
    the helper sets no other directory (subprocess: fresh jax config)."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable_compile_cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        print("DIR", enable_compile_cache(),
              jax.config.jax_compilation_cache_dir)
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    """)
    env = {**__import__("os").environ, "PYTHONPATH": "src",
           "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=str(REPO), timeout=300)
    assert f"DIR {tmp_path} {tmp_path}" in r.stdout, r.stderr[-2000:]
    assert any(tmp_path.iterdir()), "no cache entry was written"


def test_forced_device_env_keeps_children_off_the_chip():
    from benchmarks._mesh import forced_device_env
    env = forced_device_env(2, base={"JAX_PLATFORMS": "tpu"})
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=2" in env["XLA_FLAGS"]
