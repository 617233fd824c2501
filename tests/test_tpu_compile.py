"""The Pallas kernels and the serving wave compile for a TPU v5e at the
widths the system serves.

Nothing runs: each program is lowered and compiled for a described (not
attached) v5e chip, which is what the chip's compiler would accept or
refuse.  Each kernel must lower to a ``tpu_custom_call`` (a Mosaic kernel,
not the interpreter) and must not stage a copy of its table: the
compiler's temp buffer stays a small fraction of the table's bytes.  The
serving wave must write its KV cache in place.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import rowdma
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


def _rings(fn, *shapes):
    """``(slots, slot_shape, dtype)`` of each kernel's row ring: its first
    VMEM scratch, one DMA semaphore per slot."""
    rings = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                refs = [v.aval for v in eqn.params["jaxpr"].invars]
                rings.append(next(
                    (buf.shape[0], buf.shape[1:], buf.dtype)
                    for buf, sems in zip(refs, refs[1:])
                    if str(buf.memory_space) == "vmem"
                    and str(sems.memory_space) == "semaphore_mem"
                    and sems.shape == buf.shape[:1]))
            for param in eqn.params.values():
                inner = getattr(param, "jaxpr", param)
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(jax.make_jaxpr(fn)(*shapes).jaxpr)
    return rings


@pytest.mark.parametrize("dtype,rows,batch,nnz,max_lookups,weighted", [
    (jnp.float32, 1 << 22, 2048, 1 << 15, 64, True),
    (jnp.bfloat16, 1 << 20, 2048, 1 << 15, 64, True),
    # chip_smoke.py's fused step: 8 tables of 2^20 rows, 8 x 2048 bags,
    # 337920 lookups — more CSR stream than one launch's SMEM holds
    (jnp.float32, 8 << 20, 8 * 2048, 337920, 128, False),
    # dlrm-v2.zipf's fused group: 26 tables stacked, 2048 bags each,
    # 438,272 lookups (bucket 2^19), the 100-hot table's bucket of 128
    (jnp.float32, 7408088, 26 * 2048, 1 << 19, 128, False),
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
    # the row stream's queue is as deep as the ring's bytes allow
    rings = _rings(step, *args)
    assert rings
    for slots, (granule, col_tile), dt in rings:
        assert slots == rowdma.stream_depth(granule, col_tile, dt)
        assert slots * rowdma.slot_bytes(granule, col_tile, dt) \
            <= rowdma.RING_BYTES


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


def _stablelm_wave(topo, one_chip, monkeypatch, kv):
    """The compiled text of an 8-token prefill wave at stablelm-3b widths
    (2 of its 32 layers), 4 slots x 2048 positions, over a donated cache
    in the chip's default layout (sequence minor: head dim 80 is no
    multiple of 128).  Returns (cfg, slots, max_len, text)."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import LM
    from repro.models import attention
    # the layouts the wave adapts to are the described chip's
    monkeypatch.setattr(attention, "_default_device",
                        lambda: topo.devices[0])
    cfg = dataclasses.replace(get_config("stablelm-3b"), num_layers=2,
                              kv_cache_dtype=kv)
    lm = LM(cfg)
    slots, max_len = 4, 2048
    on_chip = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = on_chip(jax.eval_shape(lm.init, jax.random.PRNGKey(0)))
    caches = on_chip(jax.eval_shape(lambda: lm.init_caches(slots, max_len)))
    text = jax.jit(lm.wave_step, donate_argnums=(3,)).lower(
        params, on_chip(jax.ShapeDtypeStruct((slots, 8), jnp.int32)),
        on_chip(jax.ShapeDtypeStruct((slots,), jnp.int32)),
        caches).compile().as_text()
    return cfg, slots, max_len, text


#: an op's result name, shape and opcode in the compiled text
_OP = r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\((.*)"


@pytest.mark.parametrize("kv", ["model", "int8"])
def test_serving_wave_writes_cache_in_place_on_v5e(topo, one_chip,
                                                   monkeypatch, kv):
    """Every op that yields a whole layer-stacked cache leaf of the
    8-token prefill wave is a write in place — no relayout copy at the
    wave's entry or exit, no whole-layer slice, select or copy."""
    import re
    cfg, slots, max_len, text = _stablelm_wave(topo, one_chip, monkeypatch,
                                               kv)
    # a layer-stacked leaf with a sequence axis
    stack = re.compile(r"^\s*(?:ROOT )?%\S+ = \w+\[(" +
                       f"{cfg.n_super},{slots}," + r"[\d,]+)\]\S* ([\w-]+)\(")
    ops = [(m.group(2), m.group(0)) for m in map(stack.match,
                                                  text.splitlines())
           if m and str(max_len) in m.group(1).split(",")]
    moved = [op for op, _ in ops
             if op not in ("parameter", "get-tuple-element", "tuple")]
    assert "dynamic-update-slice" in moved, moved
    assert set(moved) == {"dynamic-update-slice"}, \
        [line for op, line in ops if op not in
         ("parameter", "get-tuple-element", "tuple", "dynamic-update-slice")]


@pytest.mark.parametrize("kv,leaves", [("model", 2), ("int8", 4)])
def test_one_pass_wave_writes_a_block_per_slot_on_v5e(topo, one_chip,
                                                      monkeypatch, kv,
                                                      leaves):
    """The prefill wave of a dense stack runs in one pass: no op copies a
    whole K/V leaf (a layer's or the stack's), and the cache takes one
    8-row write per slot per leaf per layer — one ``dynamic-update-slice``
    of a (1, 1, 8, ...) block per slot and leaf in the layer loop — not a
    row per token."""
    import re
    cfg, slots, max_len, text = _stablelm_wave(topo, one_chip, monkeypatch,
                                               kv)
    ops = [m.groups() for m in map(re.compile(_OP).match, text.splitlines())
           if m]
    shape = {name: dims.split(",") for name, dims, _, _ in ops}
    leaf = lambda dims: f"{slots},{max_len}" in ",".join(dims)
    copies = [name for name, dims, op, _ in ops
              if op.startswith("copy") and leaf(dims.split(","))]
    assert not copies, copies
    writes = [args.split(", ")[1].lstrip("%") for name, dims, op, args in ops
              if op == "dynamic-update-slice" and
              dims.startswith(f"{cfg.n_super},{slots},{max_len}")]
    assert len(writes) == slots * leaves, writes
    for update in writes:
        assert shape[update][:3] == ["1", "1", "8"], (update, shape[update])
