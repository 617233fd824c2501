"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``--xla_force_host_platform_device_count=512`` before any jax import, and
smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax


def axis_types_kw(n_axes: int) -> dict:
    """``axis_types=`` kwarg for ``jax.make_mesh``: every axis Auto (the
    compiler propagates shardings; no explicit-sharding typing)."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips for the multi-pod run.

    Axes: ``data`` (in-pod DP), ``model`` (TP/EP/vocab/head sharding),
    ``pod`` (cross-pod pure-DP; its gradient all-reduce crosses DCN).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **axis_types_kw(len(axes)))


def data_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_shard_count(mesh, axis: str = "model") -> int:
    """Vocab-shard count the steady-state executor will use on ``mesh`` —
    the launch-layer alias of :func:`repro.core.shard_plan.shard_count`
    (one definition; imported lazily so this module stays importable before
    the kernel stack)."""
    from ..core.shard_plan import shard_count
    return shard_count(mesh, axis)


def make_host_mesh(model_parallel: int = 1):
    """Whatever this host actually has — used by examples and tests."""
    n = len(jax.devices())
    dp = n // model_parallel
    return jax.make_mesh((dp, model_parallel), ("data", "model"),
                         **axis_types_kw(2))
