"""Disaggregated embedding tier: service process + fault-tolerant client.

ROADMAP item 1 taken to its serving conclusion (FlexEMR's disaggregation
argument): the stacked embedding tables live in their OWN process pool —
separately scalable from the dense tier, restartable without killing the
server — and the :class:`~repro.core.executor.ProgramExecutor` reaches
them over :mod:`repro.runtime.rpc` with its existing submit/result overlap
hiding the extra hop (the request leaves at ``submit``, the reply is
consumed at ``result``).

**Service side** (:class:`EmbeddingService`, ``python -m
repro.runtime.embedding_service``): one process owns the compiled program
+ device-resident stacked tables and serves ``AccessPlan`` step requests —
the per-step offset streams arrive over the wire, the tables never do
(after bind).  Steps replay idempotently: each request carries a monotone
per-client sequence number and the service caches the last reply per
client, so a retried request (reply lost on the wire, client failed over
and back) never double-executes.  A replica that boots next to a complete
*warm artifact* (``program.json`` + a :class:`CheckpointManager` table
checkpoint, written by the pool at bind time) **re-warms from the
artifact** instead of waiting for a bind RPC — the respawn path never
re-ships or re-stacks tables.

**Client side** (:class:`ServicePool`): N replicas serving the same
tables, round-robin dispatch with

* bounded exponential-backoff retry (the ``run_with_spawn_retry`` shape,
  :func:`repro.runtime.rpc.backoff_delays`),
* failover — a transport failure reroutes the step (and every other
  pending step on that connection) to a live peer; the computation is
  deterministic, so a step that executed on the dead replica before the
  reply was lost re-executes identically on the peer,
* a heartbeat monitor with a circuit breaker — ``breaker_misses``
  consecutive missed probes (or ``breaker_failures`` consecutive data
  failures) open the circuit: the replica is marked dark, respawned
  (bounded OSError retry, same backoff shape), and only rejoins rotation
  after a successful probe against its re-warmed process,
* recovery observability — per-revival recovery seconds and the revived
  replica's ``warm_source`` land in :meth:`ServicePool.stats`.

What the pool does NOT decide: what happens to a step when every replica
is dark.  That is the executor's ``degrade_policy`` (hot-slab / stale /
fail — see :class:`~repro.core.executor.ProgramExecutor`); the pool's
contract is to raise a typed :class:`ServiceUnavailable` only after the
bounded retry is exhausted.

Chaos sites (``runtime/faults.py``): ``rpc_send``/``rpc_recv`` fire in
the transport, ``heartbeat`` per liveness probe, ``service_crash`` in the
service's step loop (the replica self-kills with ``os._exit`` — the
``kill -9`` shape the failover path must absorb).
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
from collections import OrderedDict
from pathlib import Path
from typing import Optional

import numpy as np

from ..core.ops import EmbeddingOp, EmbeddingProgram, Semiring
from .faults import (FAULT_TYPES, EmberFault, FaultInjector, FaultSpec,
                     InjectedFailure, RpcError, ServiceUnavailable)
from .rpc import RpcClient, backoff_delays, recv_msg, send_msg

__all__ = ["EmbeddingService", "ServicePool", "StepFuture",
           "program_to_spec", "spec_to_program", "write_warm_artifact",
           "TRANSPORT_FAULTS"]

#: exception classes the retry/failover loop treats as "this replica (or
#: this wire) is gone" — everything else is an application error that
#: must surface typed to the caller, never trigger a reroute
TRANSPORT_FAULTS = (OSError, RpcError, InjectedFailure)

_HOST = "127.0.0.1"


# ---------------------------------------------------------------------------
# Program spec: the JSON identity of an EmbeddingProgram (bind frames and
# the warm artifact both carry it; EmbeddingOp is a flat dataclass)
# ---------------------------------------------------------------------------

def program_to_spec(program: EmbeddingProgram) -> dict:
    return {"name": program.name,
            "ops": [[n, dataclasses.asdict(op)] for n, op in program.ops],
            "shared_tables": [list(g) for g in program.shared_tables]}


def spec_to_program(spec: dict) -> EmbeddingProgram:
    ops = []
    for name, d in spec["ops"]:
        d = dict(d)
        d["semiring"] = Semiring(**d["semiring"])
        ops.append((name, EmbeddingOp(**d)))
    return EmbeddingProgram(spec["name"], tuple(ops),
                            tuple(tuple(g) for g in spec["shared_tables"]))


def _table_key(op: EmbeddingOp) -> str:
    return "x" if op.kind == "fusedmm" else "table"


def _write_program_meta(warm_dir, meta: dict) -> None:
    """Durable atomic publish of ``program.json`` — the ckpt tier's
    fsync-before-rename helper, shared rather than re-implemented: a bare
    ``tmp.write_text(); tmp.rename()`` is atomic against concurrent
    readers but leaves the torn-publish window against power loss that
    PR 8 closed for checkpoints."""
    from ..checkpoint import atomic_write_text
    atomic_write_text(Path(warm_dir) / "program.json", json.dumps(meta))


def _prune_table_steps(tables_dir: Path, keep: int = 2) -> None:
    """Keep-N retention over the warm tables (the CheckpointManager._gc
    shape).  ``keep >= 2`` so the step a just-superseded ``program.json``
    still references survives one more publish cycle."""
    import shutil

    from ..checkpoint import committed_steps
    for s in committed_steps(tables_dir)[:-keep]:
        (tables_dir / f"step_{s:09d}.COMMITTED").unlink(missing_ok=True)
        shutil.rmtree(tables_dir / f"step_{s:09d}", ignore_errors=True)


def write_warm_artifact(warm_dir, bind_meta: dict, tables: dict,
                        version: int) -> None:
    """Publish the re-warm artifact.  Order is the crash-safety contract:
    the table checkpoint commits FIRST (``save_checkpoint``'s
    commit-marker protocol), then ``program.json`` — stamped with the
    committed ``table_step`` — publishes atomically.  A crash between the
    two leaves the *previous* meta referencing its own still-committed
    step (a consistent pair); the reverse order could pair post-update
    meta with pre-update tables, which ``read_warm_artifact`` would have
    no way to detect without the stamp."""
    from ..checkpoint import save_checkpoint
    warm_dir = Path(warm_dir)
    warm_dir.mkdir(parents=True, exist_ok=True)
    save_checkpoint(warm_dir / "tables", version,
                    {op: np.asarray(a) for op, a in tables.items()})
    meta = dict(bind_meta)
    meta["table_step"] = int(version)
    _write_program_meta(warm_dir, meta)
    _prune_table_steps(warm_dir / "tables")


def read_warm_artifact(warm_dir) -> Optional[tuple]:
    """``(bind_meta, tables)`` when a complete *consistent* artifact
    exists, else None.  The meta's ``table_step`` stamp is cross-checked
    against the committed checkpoint steps: a meta referencing a torn or
    pruned step (a crash inside the publish window, or a mismatched pair
    written by pre-stamp code) is rejected rather than silently re-warming
    a replica with tables from a different version than its hot spec."""
    from ..checkpoint import committed_steps, restore_checkpoint
    warm_dir = Path(warm_dir)
    pj = warm_dir / "program.json"
    if not pj.exists():
        return None
    meta = json.loads(pj.read_text())
    steps = committed_steps(warm_dir / "tables")
    step = meta.get("table_step")
    if step is None:
        # legacy (pre-stamp) artifact: best-effort latest committed step
        step = steps[-1] if steps else None
    if step is None or step not in steps:
        return None
    like = {name: np.zeros((), np.float32)
            for name, _ in meta["program"]["ops"]
            if name in meta["table_ops"]}
    tables, _ = restore_checkpoint(warm_dir / "tables", like, step=step)
    return meta, tables


# ---------------------------------------------------------------------------
# Service side
# ---------------------------------------------------------------------------

class EmbeddingService:
    """One replica process: owns the compiled program + stacked tables,
    serves step requests.  Thread-per-connection (the pool uses one data
    and one control connection); all program state mutates under a lock."""

    def __init__(self, warm_dir=None, faults: Optional[FaultInjector] = None):
        self.warm_dir = Path(warm_dir) if warm_dir else None
        self.faults = faults
        self.executor = None
        self.tables: dict = {}           # op name -> {"table"/"x": array}
        self.table_keys: dict = {}
        self.steps = 0
        self.replays = 0
        self.warm_source = "none"        # none | bind | artifact
        self.compile_source = "none"     # none | fresh | artifact
        self._aot_saved = False          # first-step AOT capture done
        self.hot_epoch = 0               # adaptive slab generation bound
        self._replay: dict = {}          # client id -> (seq, meta, arrays)
        self._lock = threading.Lock()
        self._stop = threading.Event()

    # -- binding -----------------------------------------------------------

    def _bind_from(self, meta: dict, tables: dict, source: str) -> None:
        from ..core import artifact as art
        from ..core.executor import ProgramExecutor
        from ..core.pipeline import compile_program, seed_compile_cache
        program = spec_to_program(meta["program"])
        # AOT serving artifact (core/artifact.py) next to the warm
        # artifact: a respawned replica not only re-warms its tables, it
        # skips the PassManager + trace + XLA compile entirely when the
        # fingerprinted artifact a previous life saved still matches
        compiled = None
        payloads = None
        ameta = None
        aot_dir = self.warm_dir / "aot" if self.warm_dir is not None \
            else None
        self.compile_source = "fresh"
        if aot_dir is not None:
            ameta = art.artifact_meta(
                program, opt_level=meta["opt_level"], vlen=meta["vlen"],
                backend=meta["backend"])
            loaded = art.load_artifact(aot_dir, ameta)
            if loaded is not None:
                compiled, payloads = loaded
                self.compile_source = "artifact"
                seed_compile_cache(
                    art.compile_key_of(program, ameta), compiled)
            else:
                art.note_fresh_compile()
        if compiled is None:
            compiled = compile_program(program, meta["opt_level"],
                                       vlen=meta["vlen"])
        self.executor = ProgramExecutor(
            compiled, depth=2,
            backend=meta["backend"], index_policy=meta["index_policy"])
        if aot_dir is not None:
            self.executor.attach_artifact(aot_dir, ameta, payloads,
                                          self.compile_source)
        # a fresh compile re-saves after the first executed step (AOT
        # executables captured); an artifact boot already has them on disk
        self._aot_saved = self.compile_source == "artifact"
        self.table_keys = {name: _table_key(op) for name, op in program.ops}
        self.tables = {op: {self.table_keys[op]: np.asarray(a)}
                       for op, a in tables.items()}
        self.warm_source = source
        # the artifact carries the CURRENT hot spec: a respawned replica
        # re-warms already knowing the post-swap slab generation
        self.hot_epoch = int(meta.get("hot_epoch", 0))

    def try_warm(self) -> bool:
        """Boot-time re-warm: a complete artifact next to this replica
        replaces the bind RPC — the respawn path never re-ships tables."""
        if self.warm_dir is None:
            return False
        art = read_warm_artifact(self.warm_dir)
        if art is None:
            return False
        meta, tables = art
        self._bind_from(meta, tables, source="artifact")
        return True

    # -- request handlers --------------------------------------------------

    def _handle(self, kind: str, meta: dict, arrays: dict) -> tuple:
        if kind == "ping":
            return {"ok": True, "steps": self.steps, "pid": os.getpid(),
                    "bound": self.executor is not None,
                    "replays": self.replays,
                    "warm_source": self.warm_source,
                    "compile_source": self.compile_source,
                    "hot_epoch": self.hot_epoch}, {}
        if kind == "bind":
            self._bind_from(meta, arrays, source="bind")
            return {"ok": True, "warm_source": self.warm_source}, {}
        if kind == "hot":
            # adaptive slab swap: live replicas learn the new spec epoch
            # without a table re-ship (the artifact was rewritten first)
            self.hot_epoch = int(meta.get("hot_epoch", 0))
            return {"ok": True, "hot_epoch": self.hot_epoch}, {}
        if kind == "update":
            if self.executor is None:
                raise RpcError("update before bind")
            self.tables = {op: {self.table_keys[op]: np.asarray(a)}
                           for op, a in arrays.items()}
            return {"ok": True}, {}
        if kind == "step":
            return self._step(meta, arrays)
        if kind == "shutdown":
            self._stop.set()
            return {"ok": True}, {}
        raise RpcError(f"unknown request kind {kind!r}")

    def _step(self, meta: dict, arrays: dict) -> tuple:
        client, seq = meta["client"], int(meta["seq"])
        last = self._replay.get(client)
        if last is not None:
            if seq == last[0]:          # idempotent replay: cached reply,
                self.replays += 1       # the step does NOT re-execute
                return last[1], last[2]
            if seq < last[0]:
                raise RpcError(f"stale step seq {seq} < {last[0]}")
        if self.faults is not None:
            try:
                self.faults.fire("service_crash", step=self.steps)
            except InjectedFailure:
                # abrupt, not graceful: the kill -9 shape — no reply, no
                # connection teardown handshake, no atexit
                os._exit(137)
        if self.executor is None:
            raise RpcError("step before bind (no warm artifact either)")
        inputs: dict = {op: dict(t) for op, t in self.tables.items()}
        for key, arr in arrays.items():
            op, _, stream = key.partition("/")
            inputs.setdefault(op, {})[stream] = arr
        outs = self.executor.step(inputs)
        if not self._aot_saved:
            # first executed step: the AOT executables of the shapes this
            # deployment actually serves exist now — persist them so the
            # next (re)spawn boots by loading, not compiling.  Best-effort:
            # a failed save must never fail the step.
            self._aot_saved = True
            try:
                self.executor.save_artifact()
            except OSError:
                pass
        rmeta = {"ok": True, "seq": seq, "steps": self.steps}
        rarrays = {op: np.asarray(v) for op, v in outs.items()}
        self._replay[client] = (seq, rmeta, rarrays)
        self.steps += 1
        return rmeta, rarrays

    # -- serve loop --------------------------------------------------------

    def _serve_conn(self, conn: socket.socket) -> None:
        try:
            while not self._stop.is_set():
                try:
                    kind, meta, arrays = recv_msg(conn)
                except (RpcError, OSError):
                    return                    # peer gone: this conn is done
                seq = meta.get("seq")
                try:
                    try:
                        with self._lock:
                            rmeta, rarrays = self._handle(kind, meta,
                                                          arrays)
                        send_msg(conn, "ok", rmeta, rarrays)
                    except EmberFault as e:
                        err = {"error": type(e).__name__, "msg": str(e)}
                        if seq is not None:
                            err["seq"] = seq
                        send_msg(conn, "err", err)
                except OSError:
                    return               # client gone mid-reply: done
        finally:
            conn.close()

    def serve(self, portfile=None, port: int = 0) -> None:
        self.try_warm()
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind((_HOST, port))
        srv.listen(16)
        if portfile is not None:
            portfile = Path(portfile)
            tmp = portfile.with_suffix(".tmp")
            tmp.write_text(f"{srv.getsockname()[1]} {os.getpid()}")
            tmp.rename(portfile)     # atomic: the pool never reads a torn
        srv.settimeout(0.2)          # port file
        try:
            while not self._stop.is_set():
                try:
                    conn, _ = srv.accept()
                except socket.timeout:
                    continue
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                threading.Thread(target=self._serve_conn, args=(conn,),
                                 daemon=True).start()
        finally:
            srv.close()


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--portfile", required=True,
                    help="written atomically as '<port> <pid>' once "
                         "listening (the pool's readiness signal)")
    ap.add_argument("--warm-dir", default=None,
                    help="warm-artifact directory (program.json + table "
                         "checkpoint); a complete artifact re-warms this "
                         "replica at boot instead of a bind RPC")
    ap.add_argument("--crash-at", type=int, nargs="*", default=[],
                    help="1-based step ordinals where the service_crash "
                         "site fires (os._exit — the kill -9 shape)")
    ap.add_argument("--chaos-seed", type=int, default=0)
    args = ap.parse_args(argv)
    faults = None
    if args.crash_at:
        faults = FaultInjector(
            [FaultSpec("service_crash", at=tuple(args.crash_at),
                       times=len(args.crash_at))],
            seed=args.chaos_seed)
    EmbeddingService(warm_dir=args.warm_dir, faults=faults).serve(
        portfile=args.portfile)


# ---------------------------------------------------------------------------
# Client side: replica pool with heartbeats, breaker, failover, respawn
# ---------------------------------------------------------------------------

class StepFuture:
    """One in-flight step request.  Holds its own payload so a transport
    failure can resend it verbatim (same seq → idempotent) to a peer."""

    __slots__ = ("pool", "seq", "meta", "arrays", "replica", "value",
                 "error", "done")

    def __init__(self, pool, seq: int, meta: dict, arrays: dict):
        self.pool = pool
        self.seq = seq
        self.meta = meta
        self.arrays = arrays
        self.replica = None
        self.value = None
        self.error: Optional[BaseException] = None
        self.done = False

    def wait(self) -> dict:
        while not self.done:
            self.pool._pump(self.replica)
        if self.error is not None:
            raise self.error
        return self.value


class _Replica:
    def __init__(self, idx: int, portfile: Path):
        self.idx = idx
        self.portfile = portfile
        self.proc: Optional[subprocess.Popen] = None
        self.port: Optional[int] = None
        self.state = "starting"          # starting | live | dead
        self.client: Optional[RpcClient] = None    # data plane
        self.hb: Optional[RpcClient] = None        # control plane
        self.failures = 0                # consecutive data-plane failures
        self.misses = 0                  # consecutive missed heartbeats
        self.spawns = 0
        self.t_dead: Optional[float] = None
        self.pending: OrderedDict = OrderedDict()  # seq -> StepFuture

    def close_clients(self) -> None:
        for c in (self.client, self.hb):
            if c is not None:
                c.close()
        self.client = self.hb = None


_POOL_IDS = itertools.count(1)


def _holds_tpu() -> bool:
    """Whether this process has initialized a TPU backend (and so holds
    the chip's lock).  Never initializes a backend itself."""
    import jax
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized() and \
        jax.default_backend() == "tpu"


class ServicePool:
    """N embedding-service replicas behind one fault-tolerant dispatch.

    The executor talks to exactly three methods — :meth:`bind`,
    :meth:`update_tables`, :meth:`submit_step` — everything else is the
    robustness machinery described in the module docstring.  Single
    serving thread owns the data plane; the optional heartbeat monitor
    owns the control plane and the respawn path (state flips guarded by
    one lock)."""

    def __init__(self, replicas: int = 2, *, warm_dir=None,
                 rpc_timeout_s: float = 30.0, retries: int = 3,
                 backoff_s: float = 0.05, breaker_failures: int = 2,
                 breaker_misses: int = 2, spawn_attempts: int = 3,
                 spawn_timeout_s: float = 120.0,
                 heartbeat_interval_s: Optional[float] = None,
                 auto_respawn: bool = True, faults=None,
                 crash_at: Optional[dict] = None, chaos_seed: int = 0):
        assert replicas >= 1, replicas
        if _holds_tpu():
            raise RuntimeError(
                "ServicePool spawns replica processes that each need the "
                "accelerator, but this process already holds the TPU (a "
                "TPU belongs to one process); start the pool before this "
                "process touches JAX, or serve in-process")
        self.pool_id = next(_POOL_IDS)
        self._own_dir = warm_dir is None
        self.warm_dir = Path(warm_dir) if warm_dir else \
            Path(tempfile.mkdtemp(prefix="embsvc_"))
        self.rpc_timeout_s = rpc_timeout_s
        self.retries = max(1, int(retries))
        self.backoff_s = backoff_s
        self.breaker_failures = max(1, int(breaker_failures))
        self.breaker_misses = max(1, int(breaker_misses))
        self.spawn_attempts = max(1, int(spawn_attempts))
        self.spawn_timeout_s = spawn_timeout_s
        self.heartbeat_interval_s = heartbeat_interval_s
        self.auto_respawn = auto_respawn
        self.faults = faults             # chaos injector (client sites)
        self.crash_at = dict(crash_at or {})   # replica idx -> ordinals
        self.chaos_seed = chaos_seed
        self.client_id = f"{os.getpid()}-{self.pool_id}"
        self._seq = itertools.count(1)
        self._rr = 0
        self._lock = threading.RLock()
        self._closing = threading.Event()
        self._monitor_thread: Optional[threading.Thread] = None
        self._bind_call: Optional[tuple] = None    # (meta, arrays)
        self._table_version = 0
        self.replicas = [
            _Replica(i, self.warm_dir / f"replica_{i}.port")
            for i in range(replicas)]
        self.pool_stats = {
            "replicas": replicas, "rpc_steps": 0, "retries": 0,
            "failovers": 0, "respawns": 0, "breaker_open": 0,
            "heartbeats": 0, "hb_misses": 0, "replays": 0,
            "hot_publishes": 0,
            "recoveries_s": [], "warm_sources": [], "compile_sources": []}
        for r in self.replicas:
            self._spawn(r)
        self.wait_ready()
        if heartbeat_interval_s is not None:
            self._monitor_thread = threading.Thread(
                target=self._monitor, daemon=True)
            self._monitor_thread.start()

    # -- lifecycle ---------------------------------------------------------

    def _spawn(self, r: _Replica) -> None:
        """(Re)spawn one replica with bounded OSError retry — the
        ``run_with_spawn_retry`` contract: infra failures retry with
        exponential backoff, nothing else does."""
        r.portfile.unlink(missing_ok=True)
        cmd = [sys.executable, "-m", "repro.runtime.embedding_service",
               "--portfile", str(r.portfile),
               "--warm-dir", str(self.warm_dir)]
        if r.spawns == 0 and r.idx in self.crash_at:
            # chaos schedules apply to the FIRST life of a replica only;
            # its respawn must come back clean (or recovery never ends)
            ords = self.crash_at[r.idx]
            cmd += ["--crash-at", *[str(a) for a in ords],
                    "--chaos-seed", str(self.chaos_seed)]
        env = dict(os.environ)
        src = str(Path(__file__).resolve().parents[2])
        pp = env.get("PYTHONPATH", "")
        if src not in pp.split(os.pathsep):
            env["PYTHONPATH"] = f"{src}{os.pathsep}{pp}" if pp else src
        last: Optional[OSError] = None
        for delay in backoff_delays(self.spawn_attempts, self.backoff_s):
            if delay:
                time.sleep(delay)
            try:
                r.proc = subprocess.Popen(cmd, env=env)
                break
            except OSError as e:
                last = e
        else:
            raise last
        r.spawns += 1
        r.state = "starting"
        r.failures = r.misses = 0

    def _ready_port(self, r: _Replica) -> Optional[int]:
        try:
            txt = r.portfile.read_text().split()
            return int(txt[0])
        except (OSError, ValueError, IndexError):
            return None

    def wait_ready(self, timeout_s: Optional[float] = None) -> None:
        """Block until every starting replica is live (port published +
        ping answered).  A child that dies during startup respawns,
        bounded by ``spawn_attempts`` lives."""
        deadline = time.perf_counter() + (timeout_s or self.spawn_timeout_s)
        while time.perf_counter() < deadline:
            starting = [r for r in self.replicas if r.state == "starting"]
            if not starting:
                return
            for r in starting:
                if r.proc is not None and r.proc.poll() is not None:
                    if r.spawns >= self.spawn_attempts:
                        raise ServiceUnavailable(
                            f"replica {r.idx} died {r.spawns}x at startup "
                            f"(rc={r.proc.returncode})")
                    self._spawn(r)
                    continue
                port = self._ready_port(r)
                if port is not None and self._probe(r, port):
                    continue
            time.sleep(0.02)
        raise ServiceUnavailable(
            f"{sum(r.state != 'live' for r in self.replicas)} replica(s) "
            f"not ready within {timeout_s or self.spawn_timeout_s}s")

    def _probe(self, r: _Replica, port: int) -> bool:
        """Ping a (re)started replica; on success it (re)joins rotation."""
        try:
            hb = RpcClient(_HOST, port, timeout_s=self.rpc_timeout_s)
            meta, _ = hb.call("ping")
        except TRANSPORT_FAULTS:
            return False
        with self._lock:
            r.port = port
            if r.hb is not None:
                r.hb.close()
            r.hb = hb
            was_dead = r.state == "dead"
            r.state = "live"
            r.failures = r.misses = 0
            if was_dead and r.t_dead is not None:
                self.pool_stats["recoveries_s"].append(
                    time.perf_counter() - r.t_dead)
                r.t_dead = None
            self.pool_stats["warm_sources"].append(meta["warm_source"])
            self.pool_stats["compile_sources"].append(
                meta.get("compile_source", "none"))
        # a replica revived from the warm artifact is already bound; one
        # that came back BEFORE any bind happened just waits for it
        return True

    def close(self) -> None:
        self._closing.set()
        if self._monitor_thread is not None:
            self._monitor_thread.join(timeout=5.0)
        for r in self.replicas:
            try:
                if r.hb is not None:
                    r.hb.call("shutdown", deadline_s=1.0)
            except TRANSPORT_FAULTS:
                pass
            r.close_clients()
            if r.proc is not None and r.proc.poll() is None:
                r.proc.terminate()
        for r in self.replicas:
            if r.proc is not None:
                try:
                    r.proc.wait(timeout=5.0)
                except subprocess.TimeoutExpired:
                    r.proc.kill()
                    r.proc.wait()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def kill_replica(self, idx: int) -> None:
        """SIGKILL one replica — the chaos/bench hook (no cleanup, no
        goodbye: exactly what the failover path must absorb)."""
        r = self.replicas[idx]
        if r.proc is not None and r.proc.poll() is None:
            os.kill(r.proc.pid, signal.SIGKILL)

    # -- heartbeat monitor + circuit breaker -------------------------------

    def _monitor(self) -> None:
        while not self._closing.wait(self.heartbeat_interval_s):
            try:
                self.heartbeat_once()
            except Exception:            # noqa: BLE001 — the monitor must
                pass                     # survive anything transient

    def heartbeat_once(self) -> None:
        """One liveness pass over the pool: probe live replicas, revive
        dark ones.  Callable directly (tests drive it deterministically
        without the thread)."""
        for r in self.replicas:
            if self._closing.is_set():
                return
            if r.state != "live":
                self._try_revive(r)
                continue
            try:
                if self.faults is not None:
                    self.faults.fire("heartbeat", replica=r.idx)
                with self._lock:         # one call at a time on r.hb
                    if r.hb is None:
                        r.hb = RpcClient(_HOST, r.port,
                                         timeout_s=self.rpc_timeout_s)
                    r.hb.call("ping")
                r.misses = 0
                self.pool_stats["heartbeats"] += 1
            except TRANSPORT_FAULTS:
                r.misses += 1
                self.pool_stats["hb_misses"] += 1
                if r.hb is not None:
                    r.hb.close()
                    r.hb = None
                if r.misses >= self.breaker_misses:
                    self._open_circuit(r, reason="heartbeat loss")

    def _open_circuit(self, r: _Replica, reason: str) -> None:
        """Mark a replica dark and (optionally) start its respawn.  Data
        plane state (pending futures) is NOT touched here — only the
        serving thread reroutes, when it observes the failure itself."""
        with self._lock:
            if r.state == "dead":
                return
            r.state = "dead"
            r.t_dead = time.perf_counter()
            self.pool_stats["breaker_open"] += 1
        if self.auto_respawn:
            self.respawn(r.idx)

    def respawn(self, idx: int) -> None:
        """Respawn a dark replica's process; it rejoins rotation when a
        later :meth:`heartbeat_once`/:meth:`_try_revive` probe succeeds
        against its re-warmed process."""
        r = self.replicas[idx]
        if r.proc is not None and r.proc.poll() is None:
            r.proc.kill()
            r.proc.wait()
        self._spawn(r)
        r.state = "dead"                 # dark until a probe passes
        self.pool_stats["respawns"] += 1

    def _try_revive(self, r: _Replica) -> None:
        if r.proc is None or r.proc.poll() is not None:
            if self.auto_respawn:
                self.respawn(r)
            return
        port = self._ready_port(r)
        if port is not None:
            self._probe(r, port)

    # -- data plane: bind / update / steps ---------------------------------

    def _bind_meta(self, program, tables, *, opt_level, vlen, backend,
                   index_policy, hot_spec=None) -> dict:
        return {"program": program_to_spec(program), "opt_level": opt_level,
                "vlen": vlen, "backend": backend,
                "index_policy": index_policy,
                "table_ops": sorted(tables),
                "hot_spec": ({n: sorted(int(i) for i in ids)
                              for n, ids in dict(hot_spec).items()}
                             if hot_spec else None),
                "hot_epoch": 0}

    def bind(self, program, tables: dict, **bind_kw) -> None:
        """Ship program + tables to every live replica — but FIRST publish
        the warm artifact, so any replica that dies from this moment on
        re-warms from checkpoint instead of needing a re-bind."""
        meta = self._bind_meta(program, tables, **bind_kw)
        arrays = {op: np.asarray(a) for op, a in tables.items()}
        self._table_version += 1
        write_warm_artifact(self.warm_dir, meta, arrays,
                            self._table_version)
        self._bind_call = (meta, arrays)
        self._broadcast("bind", meta, arrays)

    def update_tables(self, tables: dict) -> None:
        """Refresh the service-side tables (artifact first, same reason).
        Dark replicas pick the new version up from the artifact when they
        re-warm."""
        if self._bind_call is None:
            raise RpcError("update_tables before bind")
        meta, _ = self._bind_call
        arrays = {op: np.asarray(a) for op, a in tables.items()}
        self._table_version += 1
        write_warm_artifact(self.warm_dir, meta, arrays,
                            self._table_version)
        self._bind_call = (meta, arrays)
        self._broadcast("update", {}, arrays)

    def publish_hot_spec(self, hot_rows: dict) -> None:
        """Propagate an adaptive hot-slab swap: rewrite the warm artifact's
        ``program.json`` with the new spec + bumped epoch (atomic rename;
        the table checkpoint is untouched — a swap re-ranks, it never
        re-ships rows), then best-effort notify live replicas.  An all-dark
        pool is tolerated: the artifact alone guarantees that any replica
        respawned from this moment re-warms with the *current* slab."""
        if self._bind_call is None:
            raise RpcError("publish_hot_spec before bind")
        meta, arrays = self._bind_call
        meta = dict(meta)
        meta["hot_spec"] = {n: sorted(int(i) for i in ids)
                            for n, ids in dict(hot_rows).items()}
        meta["hot_epoch"] = int(meta.get("hot_epoch", 0)) + 1
        warm_dir = Path(self.warm_dir)
        warm_dir.mkdir(parents=True, exist_ok=True)
        # the republished meta must keep referencing the committed table
        # step it was bound with (a swap re-ranks, it never re-ships rows)
        meta["table_step"] = int(self._table_version)
        _write_program_meta(warm_dir, meta)
        self._bind_call = (meta, arrays)
        self.pool_stats["hot_publishes"] += 1
        try:
            self._broadcast("hot", {"hot_epoch": meta["hot_epoch"]}, {})
        except ServiceUnavailable:
            pass    # dark pool: replicas pick the spec up on re-warm

    def _broadcast(self, kind: str, meta: dict, arrays: dict) -> None:
        sent = 0
        for r in self.replicas:
            if r.state != "live":
                continue
            try:
                if r.hb is None:
                    r.hb = RpcClient(_HOST, r.port,
                                     timeout_s=self.rpc_timeout_s)
                if self.faults is not None:
                    self.faults.fire("rpc_send", kind=kind)
                # the heartbeat thread calls on the same connection
                with self._lock:
                    r.hb.call(kind, meta, arrays,
                              deadline_s=max(self.rpc_timeout_s, 60.0))
                sent += 1
            except TRANSPORT_FAULTS:
                # a replica that missed the broadcast re-warms from the
                # artifact after its circuit opens
                self._mark_failure(r)
        if not sent:
            raise ServiceUnavailable(f"no live replica accepted {kind!r}")

    def _next_live(self) -> Optional[_Replica]:
        n = len(self.replicas)
        for k in range(n):
            r = self.replicas[(self._rr + k) % n]
            if r.state == "live":
                self._rr = (self._rr + k + 1) % n
                return r
        return None

    def _ensure_client(self, r: _Replica) -> RpcClient:
        if r.client is None:
            r.client = RpcClient(_HOST, r.port,
                                 timeout_s=self.rpc_timeout_s)
        return r.client

    def _mark_failure(self, r: _Replica) -> None:
        r.failures += 1
        if r.client is not None:
            r.client.close()
            r.client = None
        if r.failures >= self.breaker_failures or (
                r.proc is not None and r.proc.poll() is not None):
            self._open_circuit(r, reason="data-plane failure")

    def submit_step(self, streams: dict) -> StepFuture:
        """Send one step request (monotone seq) to the next live replica;
        returns a :class:`StepFuture` resolved at :meth:`StepFuture.wait`.
        Raises :class:`ServiceUnavailable` only after the bounded
        exponential-backoff retry found no replica to accept the send."""
        seq = next(self._seq)
        fut = StepFuture(self, seq,
                         {"client": self.client_id, "seq": seq}, streams)
        self._send_future(fut)
        self.pool_stats["rpc_steps"] += 1
        return fut

    def _send_future(self, fut: StepFuture) -> None:
        last: Optional[BaseException] = None
        for k, delay in enumerate(
                backoff_delays(self.retries, self.backoff_s)):
            if delay:
                time.sleep(delay)
                self.pool_stats["retries"] += 1
            r = self._next_live()
            if r is None:
                break
            try:
                client = self._ensure_client(r)
                send_msg(client.sock, "step", fut.meta, fut.arrays,
                         faults=self.faults)
                fut.replica = r
                r.pending[fut.seq] = fut
                r.failures = 0
                return
            except TRANSPORT_FAULTS as e:
                last = e
                self._mark_failure(r)
        raise ServiceUnavailable(
            f"no live embedding-service replica accepted step "
            f"{fut.seq} after {self.retries} attempt(s)"
            + (f" (last: {type(last).__name__}: {last})" if last else ""))

    def _pump(self, r: _Replica) -> None:
        """Receive ONE frame on a replica's data connection and resolve
        the matching pending future.  A transport failure here fails the
        replica over: every pending step (payloads retained) resends to a
        live peer — same seq, so a step the dead replica already executed
        replays idempotently if it ever comes back."""
        if r is None:
            raise ServiceUnavailable("step future lost its replica")
        try:
            kind, meta, arrays = recv_msg(
                r.client.sock, deadline_s=self.rpc_timeout_s,
                faults=self.faults)
        except TRANSPORT_FAULTS as e:
            self._failover(r, e)
            return
        fut = r.pending.pop(meta.get("seq"), None)
        if fut is None:
            return                       # stale frame (already rerouted)
        if kind == "err":
            name = meta.get("error", "RpcError")
            cls = FAULT_TYPES.get(name, RpcError)
            try:
                fut.error = cls(meta.get("msg", ""))
            except TypeError:
                fut.error = EmberFault(
                    f"{name}: {meta.get('msg', '')}")
        else:
            fut.value = arrays
            if meta.get("steps", 0) != meta.get("seq"):
                # the service's step counter trailing the seq means some
                # seq was answered from the replay cache somewhere
                self.pool_stats["replays"] = max(
                    self.pool_stats["replays"], 0)
        fut.done = True

    def _failover(self, r: _Replica, cause: BaseException) -> None:
        self._mark_failure(r)
        if r.state == "live":
            # breaker still closed (single transient failure): the wire
            # died but the replica may be fine — reroute pendings anyway,
            # the reconnect happens on the next send
            pass
        pendings = list(r.pending.values())
        r.pending.clear()
        for fut in pendings:
            fut.replica = None
            try:
                self._send_future(fut)
                self.pool_stats["failovers"] += 1
            except ServiceUnavailable as e:
                fut.error = e
                fut.done = True

    # -- observability -----------------------------------------------------

    def stats(self) -> dict:
        s = dict(self.pool_stats)
        s["recoveries_s"] = list(self.pool_stats["recoveries_s"])
        s["warm_sources"] = list(self.pool_stats["warm_sources"])
        s["compile_sources"] = list(self.pool_stats["compile_sources"])
        s["states"] = [r.state for r in self.replicas]
        s["spawns"] = [r.spawns for r in self.replicas]
        return s


if __name__ == "__main__":
    main()
