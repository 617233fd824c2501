"""The Pallas kernels compile for a TPU v5e at the widths the system serves.

Nothing runs: each kernel is lowered and compiled for a described (not
attached) v5e chip, which is what the chip's compiler would accept or
refuse.  Each must lower to a ``tpu_custom_call`` (a Mosaic kernel, not the
interpreter) and must not stage a copy of its table: the compiler's temp
buffer stays a small fraction of the table's bytes.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fusedmm import fusedmm_pallas
from repro.kernels.gather import block_gather_pallas
from repro.kernels.sls import sls_pallas

#: largest compiler temp buffer allowed, as a fraction of the table bytes
TEMP_FRACTION = 0.02


@pytest.fixture(scope="module")
def topo():
    # libtpu's compiler describes the chip; no chip need be attached
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes


def _check(text: str, temp: int, table_bytes: int):
    assert "tpu_custom_call" in text
    assert temp < TEMP_FRACTION * table_bytes, (temp, table_bytes)


@pytest.mark.parametrize("dtype,rows,batch,nnz,max_lookups,weighted", [
    (jnp.float32, 1 << 22, 2048, 1 << 15, 64, True),
    (jnp.bfloat16, 1 << 20, 2048, 1 << 15, 64, True),
    # chip_smoke.py's fused step: 8 tables of 2^20 rows, 8 x 2048 bags,
    # 337920 lookups — more CSR stream than one launch's SMEM holds
    (jnp.float32, 8 << 20, 8 * 2048, 337920, 128, False),
])
def test_sls_compiles_for_v5e(one_chip, dtype, rows, batch, nnz,
                              max_lookups, weighted):
    """DLRM-v2 row width, the fused table-offset stream of a multi-table
    program."""
    width = 128

    def step(table, ptrs, idxs, base, vals=None):
        return sls_pallas(table, ptrs, idxs, vals, num_segments=batch,
                          max_lookups=max_lookups, seg_base=base)

    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    args = [s((rows, width), dtype), s((batch + 1,), jnp.int32),
            s((nnz,), jnp.int32), s((batch,), jnp.int32)]
    if weighted:
        args.append(s((nnz,), dtype))
    text, temp = _compile(step, *args)
    _check(text, temp, rows * width * jnp.dtype(dtype).itemsize)


def test_block_gather_compiles_for_v5e(one_chip):
    """One-row blocks of the stablelm-3b token table (50304 x 2560 bf16)."""
    rows, width = 50304, 2560

    def step(table, idxs):
        return block_gather_pallas(table, idxs, block_rows=1)

    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text, temp = _compile(step, s((rows, width), jnp.bfloat16),
                          s((64,), jnp.int32))
    _check(text, temp, rows * width * 2)


def test_fusedmm_compiles_for_v5e(one_chip):
    """Message passing over 128-wide node features (ogbn-arxiv's node
    count)."""
    nodes, width, nnz = 169343, 128, 1 << 15

    def step(x, ptrs, idxs):
        return fusedmm_pallas(x, ptrs, idxs, num_segments=nodes,
                              max_lookups=64)

    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text, temp = _compile(step, s((nodes, width), jnp.float32),
                          s((nodes + 1,), jnp.int32), s((nnz,), jnp.int32))
    _check(text, temp, nodes * width * 4)
