"""ProgramExecutor — the steady-state runtime of a compiled embedding program.

The compile cache (PR 1) made per-step *pass* overhead free; this module
removes the per-step *data-movement* overhead and runs the program the way
the DAE machine is meant to run — the access stream ahead of execute:

    compile cache                 marshaling cache              step loop
    ─────────────                 ────────────────              ─────────
    (signature, O?, vlen)   ──▶   device-resident stacked   ──▶ double-
    ProgramCompileResult          tables + roff streams +       buffered
    (executor_for, LRU)           bucketed scratch buffers      submit/result

Three mechanisms, mirroring the DAE queue at program scope:

* **Marshaling cache** — everything per-*signature* is built once and kept
  device-resident: the fused units' row-stacked tables (device-side concat,
  donated in place on :meth:`ProgramExecutor.update_tables`), the per-segment
  ``roff`` table-offset streams, and per-batch-shape scratch buffers for the
  CSR operands.  A steady-state step does **zero host table stacking**.
* **Capacity buckets** — ``idxs``/``vals`` nnz and the ``max_lookups`` grid
  extent are padded to the capacity-bucket lattice carried by each unit's
  compiled :class:`~repro.core.access_plan.AccessPlan`
  (:mod:`repro.core.capacity`), so a ragged batch sequence reuses one
  kernel trace per bucket instead of re-specializing every step.
* **Cross-step access/execute overlap** — :meth:`ProgramExecutor.submit`
  marshals step N+1's access-side operands (host index packing + device
  transfer, dispatched asynchronously) while step N's execute phase is still
  in flight; ``jax.block_until_ready`` happens only at the consume point
  (:meth:`StepHandle.result`), with a bounded in-flight depth for
  backpressure.  Host scratch is double-buffered per bucket so packing
  step N+1 never races step N's transfer.

``executor_for`` memoizes executors on the program signature (bounded LRU)
alongside the compile cache, which is what the runtimes
(:mod:`repro.runtime.server`, :mod:`repro.runtime.trainer`) hold on to.

**Sharded programs** — pass ``mesh`` (and optionally ``shard_axis``) and the
fused units' stacked tables are vocab-partitioned over that mesh axis per
each unit's compiled :class:`~repro.core.access_plan.AccessPlan`: each
device holds a 1/S slice of every slot's cold tail plus the replicated hot
slab (the classified Zipf head — pass ``hot_rows`` to enable), the per-step
CSR streams are routed to their owning shards by the host interpreting the
plan (the access unit doing the offset-stream exchange, padded to the same
pow-2/quarter-octave capacity buckets so the exchange is retrace-free; hot
lookups stay local and pay no exchange), and the batched SLS kernel runs
under ``shard_map`` (:mod:`repro.core.shard_plan` owns the device bodies).
With ``exchange="collective"`` (the ≥2-shard default) the routed buckets
become the *send lattice* of a ``jax.lax.all_to_all`` executed inside the
shard_map body — one resident send buffer per step instead of per-shard
host scatters — and pooled outputs are **reduce-scattered** over the mesh
(each shard owns a contiguous segment slice; ``replicate_outputs=True`` is
the escape hatch back to the fully-replicated ``psum``/``pmax`` combine,
which is also the ``exchange="host"`` default).  A mesh of size 1 (or
``mesh=None``) takes exactly the single-device path.
"""
from __future__ import annotations

import dataclasses
import functools
import weakref
from collections import deque
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from ..kernels import ops as kops
from . import access_plan as ap
from . import backend_jax as bj
from . import backend_pallas as bp
from . import cost_model
from . import shard_plan as sp
from .cost_model import FusionBudget
from .ops import EmbeddingProgram
from .passes.fuse import FusedGroup
from .pipeline import (BoundedLru, ProgramCompileResult, compile_program,
                       entries_by_shards)


@dataclasses.dataclass(eq=False)  # identity semantics: outputs hold arrays
class StepHandle:
    """One in-flight program step.  ``outputs`` are lazy device arrays;
    :meth:`result` is the consume point (the only place that blocks)."""

    outputs: dict                 # op name -> device array (async)
    index: int                    # step number within the executor
    done: bool = False
    faults: object = None         # chaos injector (site "result"), if any
    # disaggregated steps: a zero-arg resolver for outputs still on the
    # wire — the RPC left at submit, the reply is consumed here, so the
    # submit/result overlap hides the extra hop exactly like it hides the
    # device round trip
    pending: object = None

    def result(self) -> dict:
        if self.faults is not None:
            self.faults.fire("result", step=self.index)
        with tracing.span("result", self.index):
            if self.pending is not None:
                fn, self.pending = self.pending, None
                self.outputs.update(fn())
            jax.block_until_ready(self.outputs)
        self.done = True
        return self.outputs


class BufferPool:
    """Rotating host staging buffers behind the per-step marshaling.

    Each *entry* is a small ring of identically-shaped buffer sets; every
    slot remembers the :class:`StepHandle` that last packed it (recorded by
    :meth:`ProgramExecutor.submit`), so a slot is never rewritten while its
    transfer may still be in flight.  Acquisition scans the ring for a free
    slot; when every slot is busy the ring **grows** (up to ``max_slots``)
    instead of stalling.  Only a full ring at ``max_slots`` pays a
    ``forced_drains`` stall.  Each executor owns its pool, and keys its
    entries by ``(unit, capacity bucket)``.
    """

    def __init__(self, n_slots: int = 2, max_slots: Optional[int] = None):
        self.n_slots = max(2, n_slots)
        self.max_slots = max(self.n_slots, max_slots or self.n_slots * 4)
        self._entries: dict = {}
        self.stats = {"entries": 0, "hits": 0, "misses": 0, "grown": 0,
                      "forced_drains": 0, "bytes": 0}

    @staticmethod
    def _alloc(spec: dict) -> dict:
        return {k: np.zeros(shape, dt) for k, (shape, dt) in spec.items()}

    def _count_bytes(self, spec: dict, n: int) -> None:
        self.stats["bytes"] += n * sum(
            int(np.prod(shape)) * np.dtype(dt).itemsize
            for shape, dt in spec.values())

    def acquire(self, key, spec: dict):
        """Returns ``(entry, turn, created)``; the caller packs
        ``entry["slots"][turn]`` and records the owning handle at submit."""
        entry = self._entries.get(key)
        created = entry is None
        if created:
            entry = {"slots": [self._alloc(spec)
                               for _ in range(self.n_slots)],
                     "owners": [None] * self.n_slots, "turn": 0, "uses": 0}
            self._entries[key] = entry
            self.stats["misses"] += 1
            self.stats["entries"] = len(self._entries)
            self._count_bytes(spec, self.n_slots)
        else:
            self.stats["hits"] += 1
        entry["uses"] += 1
        n = len(entry["slots"])
        turn = None
        for k in range(1, n + 1):
            t = (entry["turn"] + k) % n
            owner = entry["owners"][t]
            if owner is None or owner.done:
                turn = t
                break
        if turn is None:
            if n < self.max_slots:    # every slot in flight: grow the ring
                entry["slots"].append(self._alloc(spec))
                entry["owners"].append(None)
                turn = n
                self.stats["grown"] += 1
                self._count_bytes(spec, 1)
            else:                     # full ring: drain the oldest owner
                turn = (entry["turn"] + 1) % n
                with tracing.span("submit.wait"):
                    entry["owners"][turn].result()
                self.stats["forced_drains"] += 1
        entry["turn"] = turn
        entry["owners"][turn] = None
        return entry, turn, created

    def release_all(self) -> None:
        """Forget every slot's owning handle (fault recovery: the owners
        were marked done and abandoned, so their transfers will never be
        consumed — the slots must become reusable, not leak busy)."""
        for entry in self._entries.values():
            entry["owners"] = [None] * len(entry["slots"])
        self.stats["releases"] = self.stats.get("releases", 0) + 1


@dataclasses.dataclass
class _UnitState:
    """Device-resident state of one compiled unit (the marshaling cache).

    ``plan`` is the unit's compiled :class:`~repro.core.access_plan.AccessPlan`
    — ALL host marshaling of this unit (stream merge, capacity buckets,
    shard routing, hot/cold addressing) is interpretation of it."""

    unit: object                  # CompiledUnit
    plan: Optional[ap.AccessPlan] = None
    table: Optional[jax.Array] = None
    roff: Optional[jax.Array] = None       # fused units only (device)
    # weakrefs to the bound source table arrays: identity comparison that
    # cannot be fooled by CPython id reuse (a collected source reads as
    # "changed" and triggers a rebind) and does not pin caller memory
    src_refs: tuple = ()
    owns_table: bool = False      # stacked buffer built by us (donatable)

    def sources_unchanged(self, srcs: list) -> bool:
        return (len(self.src_refs) == len(srcs) and
                all(r() is a for r, a in zip(self.src_refs, srcs)))

    @property
    def group(self) -> Optional[FusedGroup]:
        return self.unit.group

    @property
    def res(self):
        return self.unit.result


@functools.partial(jax.jit, donate_argnums=(0,))
def _restack(old: jax.Array, parts: tuple) -> jax.Array:
    """Device-side table restack: writes the member tables into the donated
    previous stacked buffer — an in-place update (steady-state training
    refresh), never a host round trip."""
    off = 0
    for p in parts:
        old = jax.lax.dynamic_update_slice(old, p.astype(old.dtype), (off, 0))
        off += p.shape[0]
    return old


class ProgramExecutor:
    """Steady-state executor over one :class:`ProgramCompileResult`.

    Per-step input contract matches :func:`run_program_interpreted`:
    ``inputs`` maps op name -> that op's concrete inputs.  Tables bind on
    the first step and are reused while the caller keeps passing the *same
    array objects* (the steady-state fast path: params are long-lived);
    handing different table objects — fresh arrays, another model's params
    sharing this signature, per-step ``fusedmm`` features — is detected by
    identity and triggers a rebind, never a silently stale lookup.
    :meth:`update_tables` refreshes in place when the same objects mutate
    on device.  Per-step index data flows through bucketed, double-buffered
    scratch.

    ``backend`` selects the execute unit: ``"pallas"`` (the DAE kernels —
    the TPU target, interpreter-validated on CPU) or ``"jax"`` (the stock
    XLA gather/segment-sum path of :mod:`repro.core.backend_jax` — the
    production path on hosts without the kernels).  The marshaling cache
    and overlap machinery are identical; only per-step operand placement
    differs (the jax backend's reference kernels take host CSR streams).
    """

    def __init__(self, compiled: ProgramCompileResult, depth: int = 2,
                 backend: str = "pallas", mesh=None,
                 shard_axis: str = "model", hot_rows=None,
                 exchange: Optional[str] = None,
                 replicate_outputs: Optional[bool] = None,
                 index_policy: str = "strict",
                 faults=None, service: str = "inproc",
                 service_pool=None, degrade_policy: str = "fail",
                 adaptive=None):
        assert depth >= 1, depth
        assert backend in ("pallas", "jax"), backend
        assert index_policy in ap.INDEX_POLICIES, index_policy
        assert service in ("inproc", "disagg"), service
        assert degrade_policy in ("fail", "stale"), degrade_policy
        if service == "disagg":
            assert service_pool is not None, \
                "service='disagg' requires a service_pool"
        self.compiled = compiled
        # Pallas kernels run compiled on a TPU and interpreted elsewhere
        self.interpret = kops.default_interpret()
        self.depth = depth
        self.backend = backend
        self.shards = sp.shard_count(mesh, shard_axis)
        # a 1-wide mesh IS the single-device executor (bit-identical path)
        self.mesh = mesh if self.shards > 1 else None
        self.shard_axis = shard_axis
        # exchange mode of the sharded offset streams: "collective" (the
        # default on >=2 shards) ships ONE resident send buffer per step and
        # runs the index exchange as jax.lax.all_to_all inside the shard_map
        # body; "host" is the PR-3/4 single-controller routed scatter.
        assert exchange in (None, "host", "collective"), exchange
        self.exchange = ("host" if self.shards == 1
                         else (exchange or "collective"))
        # pooled outputs: reduce-scattered over the mesh (each shard owns
        # its contiguous segment slice — the default with the collective
        # exchange) or fully replicated via psum/pmax (the escape hatch,
        # and the host-exchange default for PR-4 compatibility).
        if replicate_outputs is None:
            replicate_outputs = self.exchange == "host"
        self.replicate_outputs = bool(replicate_outputs) \
            if self.shards > 1 else True
        # disaggregated embedding tier: steps route to a replica pool
        # (runtime.embedding_service.ServicePool-shaped, duck-typed so
        # core never imports runtime) instead of executing here; the
        # degrade policy decides what a step does while every replica is
        # dark (ServiceUnavailable): hot-slab steps always serve locally,
        # cold steps serve from the local tables under "stale" or fail
        # typed under "fail"
        self.service = service
        self.service_pool = service_pool
        self.degrade_policy = degrade_policy
        assert not (service == "disagg" and sp.shard_count(
            mesh, shard_axis) > 1), \
            "disaggregated service is a single-shard client path"
        # the replicated Zipf head: the slab a dark-shard step can serve
        # locally (independent of the sharded hot/cold machinery below)
        self._svc_hot = (
            {n: np.unique(np.asarray(list(ids), dtype=np.int64))
             for n, ids in dict(hot_rows).items()}
            if (service == "disagg" and hot_rows) else {})
        self._svc_srcs: Optional[tuple] = None  # tables last shipped
        # hot/cold vocab classification ({op name: replicated row ids});
        # only meaningful on sharded executors — see core/access_plan.py
        self.hot_rows = dict(hot_rows) if (hot_rows and self.shards > 1) \
            else {}
        self._hot_spec = ap.canonical_hot(self.hot_rows)
        # adaptive hot-slab re-classification (data.locality.AdaptiveHotConfig
        # or None): a sliding window of per-row access counts drives live
        # slab swaps (swap_hot_slab) and hot-aware spill routing.  The
        # windowed hot/cold counters below are ALWAYS maintained — they are
        # the drift observable window_stats() exposes to operators even on
        # static executors.
        from ..data.locality import AdaptiveHotConfig, WindowedCounts
        if adaptive is not None and not isinstance(adaptive,
                                                   AdaptiveHotConfig):
            raise TypeError("adaptive must be an AdaptiveHotConfig or None")
        self.adaptive = adaptive
        _w = adaptive or AdaptiveHotConfig()
        self._win_stride = max(1, _w.window_steps // _w.num_windows)
        self._win_ring = np.zeros((_w.num_windows, 2), np.int64)  # hot, cold
        self._win_slot = 0
        self._win_steps = 0
        self._win_full = False
        self.slab_epoch = 0
        self._adapt_counts = {}           # op name -> WindowedCounts
        self._adapt_ref: Optional[float] = None  # post-swap reference rate
        self._adapt_last_swap = 0
        self._adapt_refine = 0            # settling passes still owed
        if adaptive is not None:
            for name, op in compiled.program.ops:
                if (self.shards > 1 and name in self.hot_rows) or \
                        (service == "disagg" and name in self._svc_hot):
                    self._adapt_counts[name] = WindowedCounts(
                        op.num_embeddings, adaptive.window_steps,
                        adaptive.num_windows)
        self._shard_fns: dict = {}        # (unit_idx, bucket) -> jitted call
        self._units = [_UnitState(u) for u in compiled.units]
        for u in self._units:
            u.plan = self._plan_for(u)
        self.pool = BufferPool(n_slots=max(2, depth + 1))  # host staging
        self._slots_packed: list = []     # slots the current dispatch used
        self._inflight: deque = deque()
        self._steps = 0
        # input hardening of the per-step offset streams (every marshaling
        # path interprets the hardened dict): "strict" raises a typed
        # MalformedAccessError, "clamp"/"drop" degrade per-lookup and count
        self.index_policy = index_policy
        # chaos injector (runtime.faults.FaultInjector-shaped, duck-typed
        # so core never imports runtime); None in production
        self.faults = faults
        self.stats = {"steps": 0, "table_stacks": 0, "table_restacks": 0,
                      "table_rebinds": 0, "marshal_hits": 0,
                      "marshal_misses": 0, "max_inflight": 0,
                      "exchange_index_bytes": 0, "exchange_row_bytes": 0,
                      "hot_lookups": 0, "cold_lookups": 0,
                      "host_syncs": 0, "oob_lookups": 0,
                      "dropped_lookups": 0, "resets": 0,
                      "rpc_steps": 0, "hot_local_steps": 0,
                      "stale_steps": 0, "degraded_failed_steps": 0,
                      "hot_swaps": 0, "hot_swaps_rejected": 0,
                      "spilled_lookups": 0}
        # serving artifact (core/artifact.py): attach_artifact() arms the
        # AOT executable cache; executors built without an artifact_dir
        # keep aot=None — the plain jit C++ fastpath, zero new overhead
        self.aot = None
        self.compile_source = "fresh"     # fresh | artifact
        self._artifact_dir: Optional[Path] = None
        self._artifact_meta: Optional[dict] = None

    def _fire(self, site: str) -> None:
        if self.faults is not None:
            self.faults.fire(site, program=self.compiled.program.name)

    # ------------------------------------------------------------------
    # Serving artifact (core/artifact.py)
    # ------------------------------------------------------------------

    def attach_artifact(self, artifact_dir, meta: dict,
                        payloads: Optional[dict] = None,
                        source: str = "fresh") -> None:
        """Arm the AOT executable cache against a serving artifact: eager
        kernel dispatches now run AOT-compiled executables, hydrated from
        ``payloads`` (deserialized lazily per call key) or lowered once."""
        from . import artifact as art
        self._artifact_dir = Path(artifact_dir)
        self._artifact_meta = dict(meta)
        self.aot = art.AotCache(payloads)
        self.compile_source = source

    def save_artifact(self) -> Optional[Path]:
        """Persist the compile result + every AOT executable captured so
        far (atomic re-publish; idempotent).  Call again after the first
        step so the artifact carries the executables of the shapes this
        deployment actually serves — that is what lets the next boot reach
        its first token without a single trace."""
        if self._artifact_dir is None or self._artifact_meta is None:
            return None
        from . import artifact as art
        if self.aot is None:
            self.aot = art.AotCache()
        return art.save_artifact(self._artifact_dir, self.compiled,
                                 meta=self._artifact_meta,
                                 aot_payloads=self.aot.payloads())

    def _plan_for(self, u: _UnitState) -> ap.AccessPlan:
        """The unit's AccessPlan: the compiled artifact when it matches this
        executor's shard count + hot classification, else respecialized
        (a caller that compiled without shard info — direct
        ``ProgramExecutor(compile_program(...), mesh=...)`` construction —
        still interprets exactly one plan)."""
        plan = u.unit.result.access_plan
        shards = self.shards if u.group is not None else 1
        hot = self.hot_rows if u.group is not None else None
        hot_spec = self._hot_spec if u.group is not None else ()
        if plan is None or plan.shards != shards or \
                plan.hot_spec != hot_spec:
            plan = ap.build_plan(u.res.op, u.group, shards=shards,
                                 hot_rows=hot, epoch=self.slab_epoch)
        elif self.adaptive is not None:
            # adaptive executors mutate plan.spill / plan.rr_start as
            # per-step feedback — never on the shared compiled artifact
            plan = dataclasses.replace(plan, spill={}, rr_start=0,
                                       epoch=self.slab_epoch)
        return plan

    @property
    def signature(self) -> tuple:
        return (self.compiled.program.signature(), self.compiled.opt_level,
                self.compiled.vlen)

    # ------------------------------------------------------------------
    # Marshaling cache: device-resident tables + roff
    # ------------------------------------------------------------------

    def _table_key(self, u: _UnitState) -> str:
        return "x" if u.res.op.kind == "fusedmm" else "table"

    def _src_tables(self, u: _UnitState, inputs: dict) -> list:
        """The unit's source table arrays, one per stacked slot (the plan's
        slot order — shared slots read once)."""
        if u.group is None:
            return [inputs[u.unit.names[0]][self._table_key(u)]]
        return [inputs[name]["table"]
                for name in u.plan.slot_first_member]

    def _bind_unit(self, u: _UnitState, inputs: dict) -> None:
        srcs = self._src_tables(u, inputs)
        u.src_refs = tuple(weakref.ref(a) for a in srcs)
        if u.group is not None and self.shards > 1:
            # vocab-sharded stacked table: every device materializes only
            # its own 1/S slice of each cold slice + the replicated hot
            # slabs (the AccessPlan layout).  Routed indices arrive fully
            # rebased, so the kernel's seg_base stream is all-zero.
            if u.roff is None:
                u.roff = sp.put_replicated(
                    np.zeros(u.plan.num_segments, np.int32), self.mesh)
            u.table = sp.shard_stack_tables(
                [jnp.asarray(a) for a in srcs], u.plan, self.mesh,
                self.shard_axis)
            u.owns_table = True
            return
        if u.group is None:
            u.table = jnp.asarray(srcs[0])
            u.owns_table = False
        else:
            parts = tuple(jnp.asarray(a) for a in srcs)
            # a single-slot stack may alias the caller's array — only a
            # buffer WE built (concat) may later be donated by _restack
            u.owns_table = len(parts) > 1
            u.table = (parts[0] if len(parts) == 1
                       else jnp.concatenate(parts, axis=0))
            if u.roff is None:
                u.roff = jnp.asarray(u.plan.roff)

    def bind_tables(self, inputs: dict) -> None:
        """Build the device-resident stacked tables (once per signature)."""
        for u in self._units:
            self._bind_unit(u, inputs)
            self.stats["table_stacks"] += 1

    def update_tables(self, inputs: dict) -> None:
        """Refresh the stacked tables after the member tables changed (e.g.
        a train step updated the embeddings).  Device-side concat with the
        old stacked buffer donated where we own it — an in-place update,
        never a host round trip.

        ``inputs`` may be *partial*: units with any member absent are left
        untouched (the trainer feeds only the param-backed tables each
        optimizer step; per-step operand tables such as the MoE capacity
        buffer stay bound to their last step).  Units already bound to these
        exact arrays are also skipped, so a steady-state caller can feed
        every step for free.  An owned multi-slot stack is refreshed by the
        donated device restack (``table_restacks``); an aliased single
        table just rebinds the reference (``table_rebinds``) — the
        train-serve handoff path, which never re-stacks."""
        todo = []
        for u in self._units:
            if not all(n in inputs for n in u.unit.names):
                continue
            if u.table is not None and \
                    u.sources_unchanged(self._src_tables(u, inputs)):
                continue
            todo.append(u)
        if not todo:
            return
        self.drain()   # a donated buffer must not be read by in-flight steps
        for u in todo:
            if u.table is None:
                self._bind_unit(u, inputs)
                self.stats["table_stacks"] += 1
                continue
            srcs = self._src_tables(u, inputs)
            u.src_refs = tuple(weakref.ref(a) for a in srcs)
            if u.group is not None and self.shards > 1:
                u.table = sp.shard_stack_tables(
                    [jnp.asarray(a) for a in srcs], u.plan, self.mesh,
                    self.shard_axis)
                self.stats["table_restacks"] += 1
            elif u.group is not None and u.owns_table:
                u.table = _restack(u.table,
                                   tuple(jnp.asarray(a) for a in srcs))
                self.stats["table_restacks"] += 1
            else:   # bound buffer aliases caller data: never donate it
                u.table = jnp.asarray(srcs[0])
                self.stats["table_rebinds"] += 1

    # ------------------------------------------------------------------
    # Per-step access-stream marshaling (bucketed, double-buffered)
    # ------------------------------------------------------------------

    def _scratch_for(self, unit_idx: int, bucket: tuple, spec: dict):
        """Rotating host scratch per (unit, shape bucket), drawn from the
        executor's :class:`BufferPool` (``depth + 1`` slots min 2 keep the
        steady-state pipeline from ever stalling on a busy slot).
        Slot-owner accounting (recorded by :meth:`submit`) guarantees
        packing step N+k never races an in-flight transfer, regardless of
        how ``submit`` and ``step`` calls interleave."""
        self._fire("marshal")
        entry, turn, created = self.pool.acquire((unit_idx, bucket), spec)
        self.stats["marshal_misses" if created else "marshal_hits"] += 1
        self._slots_packed.append((entry, turn))
        return entry["slots"][turn]

    def _marshal_csr(self, idx: int, u: _UnitState, inputs: dict):
        """Fused CSR unit: interpret the AccessPlan — per-member CSR shapes,
        capacity buckets and the offset-merged pack all come from the plan;
        this method only manages the rotating scratch and device transfer.
        The pallas backend gets device-put capacity buffers; the jax backend
        gets exact-length host views (its reference kernels derive segment
        ids from ``ptrs`` on the host anyway)."""
        plan = u.plan
        op = plan.op
        need_vals = plan.need_vals
        with tracing.span("submit.marshal"):
            parts, nnz, max_seg = plan.csr_parts(inputs)
            cap = plan.lattice.lookup_capacity(nnz)
            ml = plan.lattice.grid_capacity(max_seg)
            spec = {"ptrs": ((op.num_segments + 1,), np.int32),
                    "idxs": ((cap,), np.int32)}
            if need_vals:
                spec["vals"] = ((cap,), np.dtype(op.dtype))
            buf = self._scratch_for(idx, (cap, ml), spec)
            plan.pack_csr(buf, parts, inputs)
            if self.backend == "jax":
                ins = {"table": u.table, "roff": plan.roff,
                       "ptrs": buf["ptrs"], "idxs": buf["idxs"][:nnz]}
                if need_vals:
                    ins["vals"] = buf["vals"][:nnz]
                return ins, ml
            buf["idxs"][nnz:cap] = 0      # pad rows must stay in bounds
        dev = {"table": u.table, "roff": u.roff,
               "ptrs": self._put(buf["ptrs"]),
               "idxs": self._put(buf["idxs"])}
        if need_vals:
            dev["vals"] = self._put(buf["vals"])
        return dev, ml

    def _put(self, arr) -> jax.Array:
        """Host→device transfer of one per-step operand, counted in
        ``host_syncs`` (the executor's per-step transfer-issue stat)."""
        self._fire("transfer")
        self.stats["host_syncs"] += 1
        with tracing.span("submit.put"):
            return jax.device_put(arr)

    def _marshal_gather(self, idx: int, u: _UnitState, inputs: dict):
        plan = u.plan
        n = plan.num_segments
        with tracing.span("submit.marshal"):
            buf = self._scratch_for(idx, (), {"idxs": ((n,), np.int32)})
            plan.pack_gather(buf, inputs)
        if self.backend == "jax":
            return {"table": u.table, "roff": plan.roff,
                    "idxs": buf["idxs"]}, None
        return {"table": u.table, "roff": u.roff,
                "idxs": self._put(buf["idxs"])}, None

    # ------------------------------------------------------------------
    # Sharded fused units: host-routed offset-stream exchange + shard_map
    # ------------------------------------------------------------------

    def _put_sharded(self, arr) -> jax.Array:
        """Leading-dim-sharded placement of one per-step operand buffer,
        counted as a host sync (a host→device transfer the device pipeline
        must wait on — the collective exchange's whole point is issuing
        fewer of these per step)."""
        self._fire("transfer")
        self.stats["host_syncs"] += 1
        with tracing.span("submit.put"):
            return sp.put_sharded(arr, self.mesh, self.shard_axis)

    def _shard_fn(self, idx: int, u: _UnitState, bucket: tuple):
        """Memoized jit(shard_map) callable per (unit, capacity bucket) —
        the sharded analogue of the per-bucket kernel trace.  The exchange
        mode and output placement are executor-level constants, so they
        need no key component."""
        key = (idx, bucket)
        fn = self._shard_fns.get(key)
        if fn is not None:
            return fn
        op = u.group.op
        plan = u.plan
        collective = self.exchange == "collective"
        repl = self.replicate_outputs
        axis = self.shard_axis
        kw = dict(axis=axis, backend=self.backend, replicate=repl,
                  shards=self.shards, seg_cap=plan.seg_cap)
        if op.kind == "gather":
            make = (sp.make_gather_collective_body if collective
                    else sp.make_gather_body)
            body = make(op, interpret=self.interpret, **kw)
            fn = sp.sharded_call(
                body, self.mesh, axis,
                sp.gather_in_specs(axis, collective=collective),
                sp.pooled_out_specs(axis, 3, replicate=repl))
        else:
            kind, cap, ml, need_vals = bucket
            kplan = bp.make_plan(u.res)
            col_tile = kplan.col_tile if kplan.whole_row_dma else 128
            make = (sp.make_csr_collective_body if collective
                    else sp.make_csr_body)
            body = make(op, max_lookups=ml, need_vals=need_vals,
                        interpret=self.interpret, col_tile=col_tile, **kw)
            fn = sp.sharded_call(
                body, self.mesh, axis,
                sp.csr_in_specs(axis, collective=collective,
                                need_vals=need_vals),
                sp.pooled_out_specs(axis, 2, replicate=repl))
        self._shard_fns[key] = fn
        return fn

    def _count_row_bytes(self, op, blk: int, plan) -> None:
        """Pooled-rows-back volume of one sharded step: the replicated
        psum/pmax ships every shard's partials everywhere ((S-1)·B·E·4);
        the reduce-scatter leaves each shard only its own segment slice —
        1/S of that, plus the padding rows of the scatter grid."""
        s = self.shards
        width = blk * op.emb_len * 4
        if self.replicate_outputs:
            self.stats["exchange_row_bytes"] += \
                op.num_segments * width * (s - 1)
        else:
            self.stats["exchange_row_bytes"] += \
                plan.padded_segments * width * (s - 1) // s

    def _run_csr_sharded(self, idx: int, u: _UnitState, inputs: dict):
        """Fused CSR unit over S vocab shards, host exchange: the
        AccessPlan merges the member streams and routes every lookup to its
        owning shard (indices out — hot rows resolve to the replicated slab
        and pay no exchange), then the batched kernel runs per shard under
        shard_map and the partial pools combine (pooled rows back)."""
        if self.exchange == "collective":
            return self._run_csr_collective(idx, u, inputs)
        plan = u.plan
        op = plan.op
        need_vals = plan.need_vals
        with tracing.span("submit.marshal"):
            routed = plan.route_csr(inputs)
            s, cap, ml = self.shards, routed["cap"], routed["max_lookups"]
            spec = {"ptrs": ((s, op.num_segments + 1), np.int32),
                    "idxs": ((s, cap), np.int32)}
            if need_vals:
                spec["vals"] = ((s, cap), np.dtype(op.dtype))
            buf = self._scratch_for(idx, (cap, ml), spec)
            buf["ptrs"][:] = routed["ptrs"]
            bounds = routed["bounds"]
            for o in range(s):
                a, b = bounds[o], bounds[o + 1]
                n = b - a
                buf["idxs"][o, :n] = routed["idxs"][a:b]
                buf["idxs"][o, n:] = 0    # pad rows must stay in bounds
                if need_vals:
                    buf["vals"][o, :n] = routed["vals"][a:b]
                    buf["vals"][o, n:] = 0
            # only the cold tail is exchanged; hot lookups were absorbed by
            # the replicated slab (local lookup on a round-robin shard)
            self.stats["exchange_index_bytes"] += \
                routed["cold_nnz"] * (8 if need_vals else 4)
            self._note_hot_cold(routed["hot_nnz"], routed["cold_nnz"])
            # next step's round-robin hot assignment starts at the shard
            # whose routed bucket was lightest this step
            if self.adaptive is not None:
                plan.rr_start = int(np.argmin(routed["nnz"]))
            self._count_row_bytes(op, 1, plan)
        args = [u.table, u.roff, self._put_sharded(buf["ptrs"]),
                self._put_sharded(buf["idxs"])]
        if need_vals:
            args.append(self._put_sharded(buf["vals"]))
        fn = self._shard_fn(idx, u, ("csr", cap, ml, need_vals))
        with tracing.span("submit.dispatch"):
            return fn(*args)

    def _run_csr_collective(self, idx: int, u: _UnitState, inputs: dict):
        """Fused CSR unit over S vocab shards, collective exchange: the
        AccessPlan packs the step into the (src, dst) send lattice — ONE
        resident send buffer (plus its vals twin when weighted) is
        device_put per step — and the index exchange itself runs as
        ``jax.lax.all_to_all`` inside the shard_map body (hot lookups sit
        on the diagonal: zero wire traffic)."""
        plan = u.plan
        op = plan.op
        need_vals = plan.need_vals
        with tracing.span("submit.marshal"):
            routed = plan.route_csr_collective(inputs)
            s, cap, ml = self.shards, routed["cap"], routed["max_lookups"]
            spec = {"ints": ((s, s, 2, cap), np.int32)}
            if need_vals:
                spec["vals"] = ((s, s, cap), np.dtype(op.dtype))
            buf = self._scratch_for(idx, ("coll", cap, ml), spec)
            plan.fill_lattice(routed, buf["ints"],
                              buf["vals"] if need_vals else None)
            # wire volume: only off-diagonal (src != owner) lookups
            # actually cross a link in the all_to_all; hot lookups are
            # always diagonal.  Each wire lookup carries its segment id +
            # local index (+ val): 8 (12 weighted) bytes — matching the
            # gather path's seg+idx count
            self.stats["exchange_index_bytes"] += \
                routed["wire_nnz"] * (12 if need_vals else 8)
            self._note_hot_cold(routed["hot_nnz"], routed["cold_nnz"])
            self.stats["spilled_lookups"] += routed.get("spilled_nnz", 0)
            # feedback for the NEXT step: when one source's diagonal bucket
            # is overloaded, spill a bounded fraction of its hot lookups to
            # the least-loaded peer (the slab is replicated — owner choice
            # is free)
            if self.adaptive is not None:
                plan.spill = sp.compute_spill(routed["pair_counts"],
                                              self.adaptive.spill_fraction,
                                              self.adaptive.spill_overload)
            self._count_row_bytes(op, 1, plan)
        args = [u.table, u.roff, self._put_sharded(buf["ints"])]
        if need_vals:
            args.append(self._put_sharded(buf["vals"]))
        fn = self._shard_fn(idx, u, ("csr", cap, ml, need_vals))
        with tracing.span("submit.dispatch"):
            return fn(*args)

    def _run_gather_sharded(self, idx: int, u: _UnitState, inputs: dict):
        plan = u.plan
        n = plan.num_segments
        blk = plan.op.block_rows
        s = self.shards
        with tracing.span("submit.marshal"):
            if self.exchange == "collective":
                routed = plan.route_gather_collective(inputs)
                cap = routed["cap"]
                spec = {"ints": ((s, s, 2, cap), np.int32)}
                buf = self._scratch_for(idx, ("gather-coll", cap), spec)
                plan.fill_lattice(routed, buf["ints"])
                self.stats["exchange_index_bytes"] += \
                    routed["wire_segments"] * 8   # seg + idx word
                sent = ("ints",)
                bucket = ("gather-coll", cap)
            else:
                routed = plan.route_gather(inputs)
                spec = {"idxs": ((s, n), np.int32),
                        "mask": ((s, n), np.float32)}
                buf = self._scratch_for(idx, ("gather",), spec)
                buf["idxs"][:] = routed["idxs"]
                buf["mask"][:] = routed["mask"]
                self.stats["exchange_index_bytes"] += \
                    routed["cold_segments"] * 8   # idx + mask word
                sent = ("idxs", "mask")
                bucket = ("gather",)
            self._note_hot_cold(routed["hot_segments"],
                                routed["cold_segments"])
            self._count_row_bytes(plan.op, blk, plan)
        args = [u.table, u.roff] + [self._put_sharded(buf[k]) for k in sent]
        fn = self._shard_fn(idx, u, bucket)
        with tracing.span("submit.dispatch"):
            return fn(*args)

    def _marshal_single(self, idx: int, u: _UnitState, inputs: dict):
        """Singleton unit: device-transfer the per-step operands, bucketing
        the ragged CSR streams to the plan's capacity lattice."""
        op = u.res.op
        name = u.unit.names[0]
        ins = inputs[name]
        if op.kind == "gather":
            return {"table": u.table,
                    "idxs": self._put(np.asarray(ins["idxs"]))}, None
        if op.kind == "kg":
            return {"table": u.table,
                    "idxs": self._put(np.asarray(ins["idxs"])),
                    "vals": self._put(np.asarray(ins["vals"]))}, 1
        key = "x" if op.kind == "fusedmm" else "table"
        need_vals = u.plan.need_vals and "vals" in ins
        with tracing.span("submit.marshal"):
            if op.index_format == "lengths" and "ptrs" not in ins:
                ptrs = np.zeros(op.num_segments + 1, np.int64)
                np.cumsum(ins["lens"], out=ptrs[1:])
            else:
                ptrs = np.asarray(ins["ptrs"], np.int64)
            nnz = int(ptrs[-1])
            cap = u.plan.lattice.lookup_capacity(nnz)
            ml = u.plan.lattice.grid_capacity(
                int(np.diff(ptrs).max(initial=0)))
            spec = {"ptrs": ((op.num_segments + 1,), np.int32),
                    "idxs": ((cap,), np.int32)}
            if need_vals:
                spec["vals"] = ((cap,), np.dtype(op.dtype))
            buf = self._scratch_for(idx, (cap, ml), spec)
            buf["ptrs"][:] = ptrs
            buf["idxs"][:nnz] = ins["idxs"]
            buf["idxs"][nnz:cap] = 0
            if need_vals:
                buf["vals"][:nnz] = ins["vals"]
        dev = {key: u.table, "ptrs": self._put(buf["ptrs"]),
               "idxs": self._put(buf["idxs"])}
        if need_vals:
            dev["vals"] = self._put(buf["vals"])
        return dev, ml

    # ------------------------------------------------------------------
    # Step loop
    # ------------------------------------------------------------------

    def _execute(self, u: _UnitState, ins: dict, ml, aot=None):
        """``aot`` is only ever passed at *eager* call sites: shard_map
        bodies cannot invoke an AOT-compiled callable mid-trace, so they
        keep the plain jit path (trace-on-load fallback, see
        :mod:`repro.core.artifact`)."""
        with tracing.span("submit.dispatch"):
            if self.backend == "jax":
                return bj.execute(u.res.op, ins, aot=aot)
            return bp.execute(u.res, ins, max_lookups=ml, aot=aot)

    def _harden_unit(self, u: _UnitState, inputs: dict) -> dict:
        """Validate the unit's offset streams against its AccessPlan under
        this executor's ``index_policy`` before ANY marshaling path reads
        them.  Returns the (possibly repaired) inputs dict — the same
        object on clean streams, so the hardened steady state is
        bit-identical to an unhardened executor."""
        fallback = u.unit.names[0] if u.group is None else None
        with tracing.span("submit.harden"):
            hardened, oob, dropped = u.plan.harden_step(
                inputs, self.index_policy, fallback_name=fallback)
        self.stats["oob_lookups"] += oob
        self.stats["dropped_lookups"] += dropped
        return hardened

    def _dispatch(self, inputs: dict) -> dict:
        outs: dict = {}
        for idx, u in enumerate(self._units):
            uin = self._harden_unit(u, inputs)
            if u.table is None:
                self._bind_unit(u, uin)
                self.stats["table_stacks"] += 1
            elif not u.sources_unchanged(self._src_tables(u, uin)):
                # the caller handed different table objects (fresh arrays,
                # another model's params, per-step fusedmm features):
                # rebind rather than silently serve stale tables.  Identity
                # is the steady-state fast path — stable params never pay.
                self._bind_unit(u, uin)
                self.stats["table_rebinds"] += 1
            if u.group is None:
                if self.backend == "jax":
                    name = u.unit.names[0]
                    key = "x" if u.res.op.kind == "fusedmm" else "table"
                    ins = {**uin[name], key: u.table}
                    with tracing.span("submit.dispatch"):
                        outs[name] = bj.execute(u.res.op, ins,
                                                aot=self.aot)
                    continue
                dev, ml = self._marshal_single(idx, u, uin)
                outs[u.unit.names[0]] = self._execute(u, dev, ml,
                                                      aot=self.aot)
                continue
            if self.shards > 1:
                # epoch-checked marshaling: the plan interpreted here must
                # be the one the device tables were stacked under — a
                # mismatch means a half-applied slab swap
                if u.plan.epoch != self.slab_epoch:
                    raise RuntimeError(
                        f"stale access plan (epoch {u.plan.epoch} != slab "
                        f"epoch {self.slab_epoch}) — swap_hot_slab left a "
                        f"unit behind")
                fused = (self._run_gather_sharded(idx, u, uin)
                         if u.group.op.kind == "gather"
                         else self._run_csr_sharded(idx, u, uin))
            elif u.group.op.kind == "gather":
                dev, ml = self._marshal_gather(idx, u, uin)
                fused = self._execute(u, dev, ml, aot=self.aot)
            else:
                dev, ml = self._marshal_csr(idx, u, uin)
                fused = self._execute(u, dev, ml, aot=self.aot)
            with tracing.span("submit.split"):
                for name, mop, off in zip(u.group.members,
                                          u.group.member_ops,
                                          u.group.seg_offsets):
                    outs[name] = fused[off:off + mop.num_segments]
        return outs

    def submit(self, inputs: dict) -> StepHandle:
        """Dispatch one step asynchronously: marshal + launch now, block
        never.  At ``depth`` steps in flight the oldest is drained first
        (backpressure), so step N+1's access stream is prepared while step
        N's execute phase runs — the cross-step DAE overlap."""
        with tracing.span("submit", self._steps):
            self._fire("dispatch")
            with tracing.span("submit.wait"):
                while len(self._inflight) >= self.depth:
                    self._inflight.popleft().result()
            self._slots_packed = []
            if self._adapt_counts:
                self._adapt_observe(inputs)
                if self.service == "disagg":
                    self._note_svc_traffic(inputs)
            if self.service == "disagg":
                outs, pending = self._submit_disagg(inputs)
            else:
                outs, pending = self._dispatch(inputs), None
            h = StepHandle(outs, self._steps, faults=self.faults,
                           pending=pending)
            for entry, turn in self._slots_packed:
                entry["owners"][turn] = h     # slot busy until h resolves
            self._steps += 1
            self.stats["steps"] += 1
            self._inflight.append(h)
            self.stats["max_inflight"] = max(self.stats["max_inflight"],
                                             len(self._inflight))
            self._win_tick()
            if self.adaptive is not None:
                self._adapt_tick()
            return h

    def step(self, inputs: dict) -> dict:
        """Synchronous convenience: submit + block on this step's result."""
        h = self.submit(inputs)
        if h in self._inflight:     # an end-of-submit slab swap drains the
            self._inflight.remove(h)    # queue before we get here
        return h.result()

    # ------------------------------------------------------------------
    # Disaggregated service path (service="disagg")
    # ------------------------------------------------------------------

    def _svc_tables(self, inputs: dict) -> dict:
        return {name: inputs[name]["x" if op.kind == "fusedmm" else "table"]
                for name, op in self.compiled.program.ops}

    def _svc_sync(self, inputs: dict) -> None:
        """Ship tables to the service pool on first step / object change —
        the same identity discipline as :meth:`_bind_unit`: stable params
        never re-ship, fresh arrays trigger an update (with the in-flight
        remote steps drained first, so they land on the tables they were
        submitted against)."""
        tables = self._svc_tables(inputs)
        srcs = tuple(tables.values())
        if self._svc_srcs is not None and \
                len(self._svc_srcs) == len(srcs) and \
                all(a is b for a, b in zip(self._svc_srcs, srcs)):
            return
        host = {n: np.asarray(a) for n, a in tables.items()}
        if self._svc_srcs is None:
            self.service_pool.bind(
                self.compiled.program, host,
                opt_level=self.compiled.opt_level, vlen=self.compiled.vlen,
                backend=self.backend, index_policy=self.index_policy,
                hot_spec={n: tuple(int(i) for i in v)
                          for n, v in self._svc_hot.items()} or None)
            self.stats["table_stacks"] += 1
        else:
            self.drain()
            self.service_pool.update_tables(host)
            self.stats["table_rebinds"] += 1
        self._svc_srcs = srcs

    def _submit_disagg(self, inputs: dict):
        """Send the step's offset streams to the service; the reply is
        consumed at :meth:`StepHandle.result` via the handle's ``pending``
        resolver.  :class:`~repro.core.access_plan.ServiceUnavailable`
        (pool exhausted its bounded retry, every replica dark) resolves
        per the degrade policy; every other fault propagates typed."""
        self._svc_sync(inputs)
        streams: dict = {}
        for name, op in self.compiled.program.ops:
            tkey = "x" if op.kind == "fusedmm" else "table"
            for k, v in inputs[name].items():
                if k != tkey:
                    streams[f"{name}/{k}"] = np.asarray(v)
        self.stats["rpc_steps"] += 1
        try:
            fut = self.service_pool.submit_step(streams)
        except ap.ServiceUnavailable as e:
            return self._degrade_outputs(inputs, e), None

        def pending() -> dict:
            try:
                return fut.wait()
            except ap.ServiceUnavailable as e:
                return self._degrade_outputs(inputs, e)

        return {}, pending

    def _step_all_hot(self, inputs: dict) -> bool:
        """True when every lookup of this step stays inside the replicated
        Zipf head (the hot slab every client keeps locally)."""
        if not self._svc_hot:
            return False
        for name, op in self.compiled.program.ops:
            hot = self._svc_hot.get(name)
            if hot is None:
                return False
            idxs = np.asarray(inputs[name].get("idxs", ()))
            if idxs.size and not np.isin(idxs, hot).all():
                return False
        return True

    def _degrade_outputs(self, inputs: dict, cause) -> dict:
        """Resolve a step while the service tier is dark.  Hot-slab steps
        always serve locally (the head is replicated client-side and kept
        fresh); cold steps serve from the local table copy under
        ``degrade_policy="stale"`` or re-raise typed under ``"fail"`` —
        each path counted."""
        if self._step_all_hot(inputs):
            self.stats["hot_local_steps"] += 1
        elif self.degrade_policy == "stale":
            self.stats["stale_steps"] += 1
        else:
            self.stats["degraded_failed_steps"] += 1
            raise cause
        # local fallback execution: binds the local tables lazily on the
        # first dark step (tables stack once, then it's the normal path)
        outs = self._dispatch(inputs)
        self._slots_packed = []
        return outs

    def run_steps(self, steps) -> list:
        """Run a sequence of step inputs through the double-buffered loop;
        returns each step's materialized outputs, in order."""
        out: list = []
        for ins in steps:
            out.append(self.submit(ins))
        return [h.result() for h in out]

    def drain(self) -> None:
        while self._inflight:
            self._inflight.popleft().result()

    def reset(self) -> None:
        """Fault recovery: abandon every in-flight step and free its
        staging slots.  The abandoned handles are marked ``done`` (their
        outputs may be garbage — a faulted marshal can leave a partially
        packed buffer — and must not be consumed), the pool's owner
        accounting is cleared so slots don't leak busy, and the next
        :meth:`submit` starts from a clean pipeline.  Device-resident
        tables and jitted kernels survive — recovery costs no recompile."""
        for h in self._inflight:
            h.done = True
        self._inflight.clear()
        self._slots_packed = []
        self.pool.release_all()
        self.stats["resets"] += 1

    # ------------------------------------------------------------------
    # Adaptive locality: windowed counters, drift detection, slab swap
    # ------------------------------------------------------------------

    def _note_hot_cold(self, hot: int, cold: int) -> None:
        """Count one routing's hot/cold split: cumulative (back-compat
        stats) AND into the sliding-window ring drift detection reads."""
        self.stats["hot_lookups"] += hot
        self.stats["cold_lookups"] += cold
        self._win_ring[self._win_slot, 0] += hot
        self._win_ring[self._win_slot, 1] += cold

    def _win_tick(self) -> None:
        """Advance the hot/cold window ring by one step (rotating out the
        oldest stripe each ``window_steps / num_windows`` steps)."""
        self._win_steps += 1
        if self._win_steps % self._win_stride == 0:
            self._win_slot = (self._win_slot + 1) % len(self._win_ring)
            if self._win_slot == 0:
                self._win_full = True
            self._win_ring[self._win_slot] = 0

    def window_stats(self) -> dict:
        """Hot/cold traffic over the last window — the drift observable.

        Unlike the lifetime-cumulative ``stats["hot_lookups"]`` /
        ``hot_traffic_fraction`` (kept for back-compat), these age out:
        a head rotation shows up within one window instead of being
        averaged into history.  The re-classifier and operators read the
        same snapshot."""
        hot = int(self._win_ring[:, 0].sum())
        cold = int(self._win_ring[:, 1].sum())
        total = hot + cold
        span = self._win_stride * len(self._win_ring)
        return {
            "window_steps": span,
            "steps_in_window": min(self._win_steps, span),
            "window_full": self._win_full,
            "hot_lookups": hot,
            "cold_lookups": cold,
            "hot_traffic_fraction": round(hot / total, 4) if total else 0.0,
            "adaptive": self.adaptive is not None,
            "slab_epoch": self.slab_epoch,
            "hot_swaps": self.stats["hot_swaps"],
            "hot_swaps_rejected": self.stats["hot_swaps_rejected"],
            "spilled_lookups": self.stats["spilled_lookups"],
            "reference_hot_fraction": self._adapt_ref,
        }

    def _note_svc_traffic(self, inputs: dict) -> None:
        """Disagg steps never route shard-side, so an adaptive client feeds
        the hot/cold window itself: each index stream is split against the
        replicated head it keeps locally (``_svc_hot``)."""
        for name, hot in self._svc_hot.items():
            ins = inputs.get(name)
            if ins is None or "idxs" not in ins:
                continue
            idxs = np.asarray(ins["idxs"]).ravel()
            if idxs.size:
                nh = int(np.isin(idxs, hot).sum())
                self._note_hot_cold(nh, idxs.size - nh)

    def _adapt_observe(self, inputs: dict) -> None:
        """Feed the step's index streams into the per-op windowed row
        counters (the re-classifier's ranking signal)."""
        for name, wc in self._adapt_counts.items():
            ins = inputs.get(name)
            if ins is not None and "idxs" in ins:
                wc.add(np.asarray(ins["idxs"]))

    def _adapt_tick(self) -> None:
        """Drift detection, once per step: compare the windowed hot
        hit-rate against the reference captured over the first full window
        after the last (re)classification; a collapse below
        ``drift_threshold × reference`` re-ranks and swaps the slab."""
        cfg = self.adaptive
        if cfg is None or not self._adapt_counts or not self._win_full:
            return
        span = self._win_stride * len(self._win_ring)
        if self._adapt_refine > 0:
            # settling pass: the window has refilled since the reactive
            # swap flushed it, so the ranking now sees purely post-swap
            # traffic — re-rank to evict rows the contaminated (partially
            # pre-drift) reactive ranking kept.  Drift detection stays
            # paused while the slab is settling.
            if self._win_steps % span == 0:
                self._adapt_refine -= 1
                self._reclassify()
            return
        hot = int(self._win_ring[:, 0].sum())
        cold = int(self._win_ring[:, 1].sum())
        if not hot + cold:
            return
        frac = hot / (hot + cold)
        if self._adapt_ref is None:
            self._adapt_ref = float(frac)
            return
        if frac >= cfg.drift_threshold * self._adapt_ref:
            # healthy window: let a better-than-reference rate raise the bar
            self._adapt_ref = max(self._adapt_ref, float(frac))
            return
        if self._steps - self._adapt_last_swap < cfg.min_swap_interval:
            return
        self._adapt_last_swap = self._steps
        if self._reclassify():
            # the reactive ranking saw pre-drift history: flush the window
            # and counters so the settling passes rank on clean data
            self._reset_windows()
            self._adapt_refine = cfg.refine_passes

    def _reset_windows(self) -> None:
        """Flush the hot/cold ring and every per-op count sketch — called
        after a reactive swap so settling passes rank on post-swap traffic
        only."""
        self._win_ring[:] = 0
        self._win_slot = 0
        self._win_steps = 0
        self._win_full = False
        for wc in self._adapt_counts.values():
            wc.reset()

    def _reclassify(self) -> bool:
        """Re-rank each tracked op's hot set from its windowed counts and
        swap the slab (size-preserving — see ``classify_hot_from_counts``).
        Returns True when a swap actually happened."""
        from ..data.locality import classify_hot_from_counts
        prev = self.hot_rows if self.shards > 1 else self._svc_hot
        new: dict = {}
        for name, wc in self._adapt_counts.items():
            prev_ids = np.asarray(sorted(int(i) for i in prev.get(name, ())),
                                  np.int64)
            if not len(prev_ids):
                continue
            ids = classify_hot_from_counts(wc.totals(), len(prev_ids),
                                           prev_hot=prev_ids)
            new[name] = tuple(int(i) for i in ids)
        return bool(new) and self.swap_hot_slab(new)

    def swap_hot_slab(self, hot_rows) -> bool:
        """Swap the replicated hot slab in place: same shapes, new
        membership.  The slab is *data* — per-slot hot counts (and so the
        local table shape, the capacity lattice, and every memoized
        ``_shard_fn``/scratch bucket) are unchanged, so the swap re-ranks
        the plan and re-stacks the device tables through the
        ``update_tables`` respecialization path without a single retrace.
        A candidate set that WOULD change a slot's geometry (shared-table
        slot unions can) is rejected and counted, never half-applied.
        Returns True when a swap happened."""
        new_hot = {n: tuple(int(i) for i in ids)
                   for n, ids in dict(hot_rows).items()}
        new_spec = ap.canonical_hot(new_hot)
        if self.service == "disagg":
            cur = ap.canonical_hot({n: tuple(int(i) for i in v)
                                    for n, v in self._svc_hot.items()})
            if new_spec == cur:
                return False
            self.drain()
            self._svc_hot = {n: np.unique(np.asarray(list(ids), np.int64))
                             for n, ids in new_hot.items()}
            self.slab_epoch += 1
            self.stats["hot_swaps"] += 1
            self._adapt_ref = None
            self._adapt_last_swap = self._steps
            # propagate through the artifact-republish path so a respawned
            # replica re-warms with the CURRENT slab (and live replicas
            # learn the new spec without a table re-ship)
            publish = getattr(self.service_pool, "publish_hot_spec", None)
            if publish is not None:
                publish(new_hot)
            return True
        if self.shards == 1 or new_spec == self._hot_spec:
            return False
        epoch = self.slab_epoch + 1
        rebuilt: list = []
        for u in self._units:
            if u.group is None:
                continue
            plan = ap.build_plan(u.res.op, u.group, shards=self.shards,
                                 hot_rows=new_hot, epoch=epoch)
            old = u.plan
            if plan.local_rows != old.local_rows or any(
                    a.hot_rows != b.hot_rows or a.cap != b.cap
                    for a, b in zip(plan.slots, old.slots)):
                self.stats["hot_swaps_rejected"] += 1
                return False
            plan.rr_start, plan.spill = old.rr_start, dict(old.spill)
            rebuilt.append((u, plan))
        self.drain()    # restacked buffers must not be read by old steps
        self.hot_rows, self._hot_spec = new_hot, new_spec
        for u, plan in rebuilt:
            u.plan = plan
            if u.table is None:
                continue
            srcs = [r() for r in (u.src_refs or ())]
            if not srcs or any(s is None for s in srcs):
                u.table = None          # sources gone: rebind next step
                continue
            u.table = sp.shard_stack_tables(
                [jnp.asarray(a) for a in srcs], plan, self.mesh,
                self.shard_axis)
            self.stats["table_restacks"] += 1
        self.slab_epoch = epoch
        self.stats["hot_swaps"] += 1
        self._adapt_ref = None
        self._adapt_last_swap = self._steps
        return True

    def access_plan_stats(self) -> dict:
        """The compiled access side, observable: per-plan hot/cold layout,
        cost-model exchange estimate vs. the measured counters, and the
        plan-build time the ``plan-access`` pass recorded."""
        fused = [u for u in self._units if u.group is not None]
        steps = self.stats["steps"]
        est = [cost_model.exchange_bytes(
                   u.group.member_ops, self.shards,
                   replicate_outputs=self.replicate_outputs,
                   collective=self.exchange == "collective")
               for u in fused]
        est_idx = sum(e["index_bytes"] for e in est) * steps
        hot = self.stats["hot_lookups"]
        cold = self.stats["cold_lookups"]
        total = hot + cold
        return {
            "shards": self.shards,
            "exchange": self.exchange,
            "replicate_outputs": self.replicate_outputs,
            "host_syncs": self.stats["host_syncs"],
            "host_syncs_per_step": round(
                self.stats["host_syncs"] / steps, 2) if steps else 0.0,
            "exchange_row_bytes": self.stats["exchange_row_bytes"],
            "exchange_row_bytes_est": sum(e["row_bytes"]
                                          for e in est) * steps,
            "units": len(self._units),
            "fused_units": len(fused),
            "hot_rows": sum(u.plan.hot_rows_total for u in fused),
            "hot_slab_bytes": sum(u.plan.hot_slab_bytes for u in fused),
            "hot_lookups": hot,
            "cold_lookups": cold,
            "hot_traffic_fraction": round(hot / total, 4) if total else 0.0,
            "exchange_index_bytes": self.stats["exchange_index_bytes"],
            # the interleaved (no hot slab) cost-model estimate — actual
            # below it means the hot slab absorbed that much routed volume
            "exchange_index_bytes_est": est_idx,
            "exchange_savings_bytes": max(
                0, est_idx - self.stats["exchange_index_bytes"]),
            "hot_swaps": self.stats["hot_swaps"],
            "spilled_lookups": self.stats["spilled_lookups"],
            "window": self.window_stats(),
            "plan_build_s": round(sum(
                r.duration_s for r in self.compiled.pass_records()
                if r.name == "plan-access" and r.ran), 6),
        }


# ---------------------------------------------------------------------------
# Executor cache: one steady-state executor per program signature, kept
# alongside the compile artifact (bounded LRU like the compile cache).
# ---------------------------------------------------------------------------

_EXECUTOR_CACHE = BoundedLru(16)


def executor_for(program: EmbeddingProgram, opt_level: str = "O3",
                 vlen: int = 128,
                 budget: Optional[FusionBudget] = None,
                 depth: int = 2, backend: str = "pallas",
                 mesh=None, shard_axis: str = "model",
                 hot_rows=None, exchange: Optional[str] = None,
                 replicate_outputs: Optional[bool] = None,
                 index_policy: str = "strict", service: str = "inproc",
                 service_pool=None,
                 degrade_policy: str = "fail",
                 adaptive=None, artifact_dir=None) -> ProgramExecutor:
    """The steady-state entry point: compile (compile-cache backed) and
    return the memoized executor whose marshaling cache is already warm for
    this signature.

    The key is the program's *structural* signature: a hit can hand back an
    executor whose tables were bound by another caller, which is exactly
    what the per-step table identity check in :meth:`ProgramExecutor.step`
    resolves (same arrays → warm fast path; different model's arrays →
    automatic rebind).

    ``mesh``/``shard_axis`` select vocab-sharded execution: the fused
    stacked tables partition over ``mesh.shape[shard_axis]`` shards and the
    ``budget`` is rewritten to budget per-shard VMEM (``FusionBudget.shards``
    — part of the compile-cache key, so replicated and sharded plans never
    collide).  A 1-wide axis (or ``mesh=None``) is the single-device path.

    ``hot_rows`` (``{op name: replicated row ids}``, e.g. from
    :func:`repro.core.access_plan.hot_rows_from_traces`) selects
    locality-aware hot/cold sharding: the classified Zipf head of each
    vocab is replicated on every shard (local lookups, zero exchange) while
    the tail stays interleave-sharded.  Ignored on the single-device path;
    part of both cache keys.

    ``exchange`` selects how the routed offset streams move on a ≥2-shard
    mesh: ``"collective"`` (the default) marshals one resident send buffer
    per step and runs the index exchange as ``jax.lax.all_to_all`` inside
    the shard_map body; ``"host"`` is the PR-3/4 single-controller routed
    scatter.  ``replicate_outputs`` picks the pooled-output placement:
    reduce-scattered segment slices (collective default) or fully
    replicated via psum/pmax (host default, and the escape hatch).

    ``adaptive`` (a :class:`repro.data.locality.AdaptiveHotConfig`) turns
    the hot slab into a live cache: windowed per-row counters re-rank the
    head when the windowed hot hit-rate collapses and swap the slab in
    place (no recompile — see :meth:`ProgramExecutor.swap_hot_slab`), plus
    hot-aware spill routing off overloaded lattice diagonals.  Hashable,
    so it keys the executor cache like every other knob.

    ``artifact_dir`` points at a serving artifact (:mod:`repro.core
    .artifact`): on an executor-cache miss the compile payload + AOT
    executables hydrate from disk *before* any compilation (fingerprint/
    identity mismatches fall back to a fresh compile, counted), and a
    fresh compile is saved back so the next boot loads.  Deliberately NOT
    part of the executor-cache key — the artifact changes where a compile
    comes from, never what it computes."""
    # canonicalize defaults so explicit-default calls hit the same entry
    shards = sp.shard_count(mesh, shard_axis)
    # disaggregated clients keep their hot-slab spec even on one shard
    # (it's the local-serving slab, not the sharded hot/cold plan) — and
    # the pool's identity keys the cache so two pools never share a
    # client executor
    service_hot = None
    if service == "disagg":
        assert service_pool is not None, \
            "service='disagg' requires a service_pool"
        assert shards == 1, \
            "disaggregated service is a single-shard client path"
        service_hot = hot_rows
    if shards == 1:
        mesh = None
        hot_rows = None
        exchange = "host"
        replicate_outputs = True
    else:
        exchange = exchange or "collective"
        if replicate_outputs is None:
            replicate_outputs = exchange == "host"
    budget = budget or FusionBudget()
    if budget.shards != shards:
        budget = dataclasses.replace(budget, shards=shards)
    hot_spec = ap.canonical_hot(hot_rows)
    key = (program.signature(), opt_level, vlen, budget, depth,
           backend, mesh, shard_axis if mesh is not None else None,
           hot_spec, exchange, bool(replicate_outputs), index_policy,
           service, degrade_policy if service == "disagg" else None,
           service_pool.pool_id if service_pool is not None else None,
           ap.canonical_hot(service_hot), adaptive)
    ex = _EXECUTOR_CACHE.get(key)
    if ex is not None:
        return ex
    compiled = None
    payloads = None
    source = "fresh"
    ameta = None
    if artifact_dir is not None:
        from . import artifact as art
        ameta = art.artifact_meta(program, opt_level=opt_level, vlen=vlen,
                                  budget=budget, hot_rows=hot_rows,
                                  backend=backend)
        loaded = art.load_artifact(artifact_dir, ameta)
        if loaded is not None:
            compiled, payloads = loaded
            source = "artifact"
            # hydrate the compile cache: later compile_program calls with
            # this identity (other executors, direct callers) hit too
            from .pipeline import seed_compile_cache
            seed_compile_cache(
                art.compile_key_of(program, ameta, budget=budget,
                                   hot_rows=hot_rows), compiled)
        else:
            art.note_fresh_compile()
    if compiled is None:
        compiled = compile_program(program, opt_level, vlen=vlen,
                                   budget=budget, hot_rows=hot_rows)
    ex = ProgramExecutor(compiled, depth=depth,
                         backend=backend, mesh=mesh, shard_axis=shard_axis,
                         hot_rows=hot_rows if shards > 1 else service_hot,
                         exchange=exchange,
                         replicate_outputs=replicate_outputs,
                         index_policy=index_policy, service=service,
                         service_pool=service_pool,
                         degrade_policy=degrade_policy, adaptive=adaptive)
    if artifact_dir is not None:
        ex.attach_artifact(artifact_dir, ameta, payloads, source)
        if source == "fresh":
            # save on first compile, so the NEXT boot loads; callers that
            # step the executor re-save (save_artifact is idempotent) to
            # capture the AOT executables of the shapes actually served
            ex.save_artifact()
    _EXECUTOR_CACHE.put(key, ex)
    return ex


def executor_cache_stats() -> dict:
    s = _EXECUTOR_CACHE.stats()
    s["entries_by_shards"] = entries_by_shards(_EXECUTOR_CACHE)
    return s


def set_executor_cache_limit(limit: int) -> int:
    return _EXECUTOR_CACHE.set_limit(limit)


def clear_executor_cache() -> None:
    _EXECUTOR_CACHE.clear()
