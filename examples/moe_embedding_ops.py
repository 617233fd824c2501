"""Embedding operations inside a model: MoE dispatch as an SLS-class op and
the vocab-sharded embedding engine, on whatever devices this host has.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/moe_embedding_ops.py
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import embedding_engine as ee
from repro.models import moe as moe_mod
from repro.configs import get_reduced


def main():
    n = len(jax.devices())
    model_par = min(4, n)
    from repro.launch.mesh import axis_types_kw
    mesh = jax.make_mesh((n // model_par, model_par), ("data", "model"),
                         **axis_types_kw(2))
    print(f"devices={n}, mesh=({n // model_par}×{model_par})")

    # 1) vocab-sharded embedding lookup + vocab-parallel xent
    V, D, B, S = 128, 32, 4, 16
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal((V, D)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    with jax.set_mesh(mesh):
        tbl = jax.device_put(table, NamedSharding(mesh, P("model", None)))
        emb = ee.lookup(tbl, ids, mesh=mesh, vocab_axis="model",
                        strategy="masked_psum", data_axes=("data",))
        err = float(jnp.abs(emb - jnp.take(table, ids, axis=0)).max())
        print(f"sharded embedding lookup: err={err:.2e} ✓")

        # 2) MoE dispatch = the SLS-class embedding op, with EP all-to-all.
        # capacity_factor=8 → no token drops, so the EP layout must agree
        # with the single-device layer, which is dropless (at production
        # capacity 1.25 the all-to-all path drops tokens — expected).
        import dataclasses
        cfg = dataclasses.replace(get_reduced("qwen3-moe-235b-a22b"),
                                  capacity_factor=8.0)
        p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (B, 16, cfg.d_model))
        ref = moe_mod.moe_ffn(p, x, cfg, mesh=None)[0]
        out, aux, _ = moe_mod.moe_ffn(
            p, jax.device_put(x, NamedSharding(mesh, P("data", None, None))),
            cfg, mesh=mesh)
        print(f"EP MoE dispatch (all-to-all over {model_par} expert shards): "
              f"err={float(jnp.abs(out - ref).max()):.2e} "
              f"aux={float(aux):.3f} ✓")


if __name__ == "__main__":
    main()
