"""AOT serving artifact: boot by loading, not compiling (ROADMAP item 5).

Ember's premise is that the expensive analysis happens once at compile
time — but a fresh *process* still re-pays the whole PassManager + trace +
XLA compile before its first request.  This module makes the compiled
program a durable on-disk artifact so a restarted server (or a respawned
disaggregated replica) reaches its first token by **loading**:

    <artifact_dir>/current/
        meta.json        # format + runtime fingerprint + compile identity
        compile.pkl      # pickled ProgramCompileResult (IR + AccessPlans)
        aot.pkl          # {kernel-call key -> serialized XLA executable}
    <artifact_dir>/current.COMMITTED   # ckpt commit-marker protocol

Publication reuses :func:`repro.checkpoint.ckpt.publish_dir` — the same
retire-marker → rename → fsync sequence checkpoints use, so a crash
mid-save leaves either the previous committed artifact or a torn state
that :func:`load_artifact` detects and rejects (never a half-read).

Loading is fingerprint-gated: the artifact is accepted only when the
jax/jaxlib versions, backend platform, device fingerprint and format
version all match the running process AND the compile identity (program
signature hash, opt_level, vlen, fusion budget, hot spec) matches what
the caller is about to compile.  Any mismatch increments a reject
counter (:func:`artifact_stats`) and falls back to a fresh compile —
a stale artifact can cost time, never numerics.

The lowered executables ride along as ``jax.experimental
.serialize_executable`` payloads inside :class:`AotCache`: per kernel
call-site key, the cache deserializes the stored executable (~ms)
instead of tracing + XLA-compiling (~100s of ms); a payload that fails
to deserialize (version skew the fingerprint could not see) falls back
to a live ``fn.lower(...).compile()`` for that key alone.  Call sites
inside a live jax trace (shard_map bodies) cannot host an AOT-compiled
callable and keep the plain jit path — for
them the artifact still saves the PassManager re-run via the hydrated
compile cache, and the docs call the residual trace-on-load out.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import shutil
from pathlib import Path
from typing import Optional

import numpy as np

from .access_plan import canonical_hot
from .cost_model import FusionBudget
from .pipeline import ProgramCompileResult, compile_cache_key

__all__ = ["AotCache", "artifact_meta", "artifact_stats",
           "load_artifact", "reset_artifact_stats",
           "runtime_fingerprint", "save_artifact"]

#: bump on any incompatible change to the on-disk layout
FORMAT_VERSION = 1

_STATS = {"saves": 0, "loads": 0, "fresh_compiles": 0, "rejects": {},
          "aot_deserialized": 0, "aot_compiled": 0, "aot_fallbacks": 0}


def artifact_stats() -> dict:
    """Process-wide load/save/reject counters (reject keyed by reason —
    the runbook's fresh-compile-fallback observability)."""
    s = dict(_STATS)
    s["rejects"] = dict(_STATS["rejects"])
    return s


def reset_artifact_stats() -> None:
    _STATS.update({"saves": 0, "loads": 0, "fresh_compiles": 0,
                   "rejects": {}, "aot_deserialized": 0, "aot_compiled": 0,
                   "aot_fallbacks": 0})


def _reject(reason: str) -> None:
    _STATS["rejects"][reason] = _STATS["rejects"].get(reason, 0) + 1


def note_fresh_compile() -> None:
    """An artifact_dir caller that ended up compiling (missing/rejected
    artifact) — the counter the version-skew runbook row watches."""
    _STATS["fresh_compiles"] += 1


def runtime_fingerprint() -> dict:
    """What must match for a serialized executable to be trustworthy on
    this process: jax/jaxlib versions (tracing + XLA serialization
    compatibility) and the device topology it was lowered for."""
    import jax
    import jaxlib
    devs = jax.devices()
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "backend": jax.default_backend(),
            "device_kinds": sorted({d.device_kind for d in devs}),
            "device_count": len(devs)}


# ---------------------------------------------------------------------------
# AotCache: per-kernel-call memo of lowered executables
# ---------------------------------------------------------------------------

def _placement(a) -> str:
    """Picklable identity of an argument's sharding ('' for a host array,
    which lands on the default device)."""
    import jax
    s = getattr(a, "sharding", None)
    if s is None:
        return ""
    if isinstance(s, jax.sharding.NamedSharding):
        return (f"named{s.mesh.axis_names}{s.mesh.device_ids.tolist()}"
                f"{s.spec}")
    return f"{type(s).__name__}{sorted(d.id for d in s.device_set)}"


def _execution_devices(leaves) -> list:
    """The devices, in mesh order, that an executable running these
    arguments spans: a mesh's devices, else the devices the arguments sit
    on (host arrays: the default device).  A deserialized executable must
    be loaded onto exactly these — by default it spans every device."""
    import jax
    shardings = [s for s in (getattr(a, "sharding", None) for a in leaves)
                 if s is not None]
    for s in shardings:
        if isinstance(s, jax.sharding.NamedSharding):
            return list(s.mesh.devices.flat)
    if shardings:
        return sorted(set().union(*(s.device_set for s in shardings)),
                      key=lambda d: d.id)
    return jax.devices()[:1]


class AotCache:
    """Memoizes ``fn.lower(*args, **static).compile()`` per call-site key
    and hydrates lazily from serialized payloads loaded off an artifact.

    A key is (kernel name, sorted static kwargs, abstract signature of
    the array arguments: shape, dtype and placement) — exactly what jit
    specializes on; an executable compiled for one sharding cannot run
    arguments laid out by another — so the cache
    holds one executable per kernel specialization, the same population a
    warm in-process jit cache would.  ``payloads()`` exports every held
    executable back to serialized form for :func:`save_artifact`.
    """

    def __init__(self, payloads: Optional[dict] = None):
        self._compiled: dict = {}
        self._blobs: dict = dict(payloads or {})
        self.stats = {"hits": 0, "loads": 0, "compiles": 0, "fallbacks": 0}

    @staticmethod
    def _sig(args: tuple, kwargs: dict) -> tuple:
        import jax
        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
        return (str(treedef),
                tuple((tuple(np.shape(a)),
                       np.dtype(getattr(a, "dtype",
                                        np.asarray(a).dtype)).str,
                       _placement(a))
                      for a in leaves))

    def call(self, name: str, fn, static: dict, *args, **kwargs):
        """Run ``fn`` (a jit object) AOT: deserialize or lower+compile the
        executable for this specialization once, then invoke it directly —
        static kwargs are baked into the executable, only arrays cross."""
        key = (name, tuple(sorted(static.items())),
               self._sig(args, kwargs))
        exe = self._compiled.get(key)
        if exe is None:
            exe = self._hydrate(key, args, kwargs)
        if exe is None:
            exe = fn.lower(*args, **kwargs, **static).compile()
            self._compiled[key] = exe
            self.stats["compiles"] += 1
            _STATS["aot_compiled"] += 1
        else:
            self.stats["hits"] += 1
        return exe(*args, **kwargs)

    def _hydrate(self, key, args: tuple, kwargs: dict):
        blob = self._blobs.get(key)
        if blob is None:
            return None
        try:
            import jax
            from jax.experimental import serialize_executable as se
            devices = _execution_devices(jax.tree_util.tree_leaves(
                (args, kwargs)))
            exe = se.deserialize_and_load(*pickle.loads(blob),
                                          execution_devices=devices)
        except Exception:   # noqa: BLE001 — any skew → live compile
            self.stats["fallbacks"] += 1
            _STATS["aot_fallbacks"] += 1
            del self._blobs[key]
            return None
        self._compiled[key] = exe
        self.stats["loads"] += 1
        _STATS["aot_deserialized"] += 1
        return exe

    def payloads(self) -> dict:
        """Serialize every resident executable (plus still-cold loaded
        blobs) for :func:`save_artifact`.  Unserializable executables are
        skipped — the artifact stays loadable, those keys re-trace."""
        out = dict(self._blobs)
        from jax.experimental import serialize_executable as se
        for key, exe in self._compiled.items():
            if key in out:
                continue
            try:
                out[key] = pickle.dumps(se.serialize(exe))
            except Exception:   # noqa: BLE001
                pass
        return out


# ---------------------------------------------------------------------------
# Save / load
# ---------------------------------------------------------------------------

def artifact_meta(program, *, opt_level: str, vlen: int = 128,
                  budget: Optional[FusionBudget] = None, hot_rows=None,
                  backend: str = "pallas") -> dict:
    """The identity an artifact is saved under and validated against at
    load: the compile-cache key rendered JSON-stable.  ``backend`` is
    informational — the compile payload is backend-agnostic IR; AOT blobs
    self-select by their call keys."""
    budget = budget or FusionBudget()
    sig = hashlib.sha256(repr(program.signature()).encode()).hexdigest()
    return {"identity": {"signature_sha": sig,
                         "opt_level": opt_level,
                         "vlen": vlen,
                         "budget": repr(budget),
                         "hot_spec": _jsonable(canonical_hot(hot_rows))},
            "backend": backend,
            "program": program.name}


def _jsonable(x):
    return json.loads(json.dumps(x))


def compile_key_of(program, meta: dict, *,
                   budget: Optional[FusionBudget] = None,
                   hot_rows=None) -> tuple:
    """The compile-cache key matching an artifact's identity (used to
    seed :mod:`repro.core.pipeline`'s cache after a successful load)."""
    ident = meta["identity"]
    return compile_cache_key(program, ident["opt_level"],
                             vlen=ident["vlen"], budget=budget,
                             hot_rows=hot_rows)


def save_artifact(artifact_dir, compiled: ProgramCompileResult, *,
                  meta: dict, aot_payloads: Optional[dict] = None) -> Path:
    """Atomically publish ``<artifact_dir>/current`` (ckpt commit-marker
    protocol).  Re-saving overwrites — last writer wins, and a loader
    racing the publish window sees a torn state and compiles fresh."""
    import dataclasses

    from ..checkpoint.ckpt import publish_dir
    artifact_dir = Path(artifact_dir)
    artifact_dir.mkdir(parents=True, exist_ok=True)
    tmp = artifact_dir / f".tmp_current_{os.getpid()}"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir()
    full = {"format": FORMAT_VERSION, "fingerprint": runtime_fingerprint(),
            **meta}
    # a cache-hit flag inside the payload would lie on the next process
    payload = dataclasses.replace(compiled, cache_hit=False)
    _write_fsync(tmp / "meta.json", json.dumps(full, indent=1).encode())
    _write_fsync(tmp / "compile.pkl", pickle.dumps(payload))
    _write_fsync(tmp / "aot.pkl", pickle.dumps(dict(aot_payloads or {})))
    publish_dir(artifact_dir, tmp, artifact_dir / "current",
                artifact_dir / "current.COMMITTED")
    _STATS["saves"] += 1
    return artifact_dir / "current"


def _write_fsync(path: Path, data: bytes) -> None:
    with open(path, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())


def load_artifact(artifact_dir, meta: dict) -> Optional[tuple]:
    """``(ProgramCompileResult, aot_payloads)`` when a committed artifact
    matches ``meta`` (from :func:`artifact_meta`) on this runtime, else
    None with the reject reason counted in :func:`artifact_stats`:

    * ``fingerprint`` — jax/jaxlib/platform/device skew (rolling upgrade)
    * ``identity``    — different program/opt_level/vlen/budget/hot spec
    * ``format``      — on-disk layout generation changed
    * ``torn``        — crash mid-publish (or a racing saver); the commit
      marker and directory disagree
    * ``unpickle``    — compile payload does not deserialize here
    """
    d = Path(artifact_dir) / "current"
    marker = Path(artifact_dir) / "current.COMMITTED"
    if not marker.exists():
        return None                       # no artifact yet: not a reject
    try:
        raw = json.loads((d / "meta.json").read_text())
    except (OSError, json.JSONDecodeError):
        _reject("torn")
        return None
    if raw.get("format") != FORMAT_VERSION:
        _reject("format")
        return None
    if raw.get("fingerprint") != runtime_fingerprint():
        _reject("fingerprint")
        return None
    if raw.get("identity") != _jsonable(meta["identity"]):
        _reject("identity")
        return None
    try:
        compiled = pickle.loads((d / "compile.pkl").read_bytes())
        payloads = pickle.loads((d / "aot.pkl").read_bytes())
    except OSError:
        _reject("torn")
        return None
    except Exception:   # noqa: BLE001 — version-skewed pickle, bad bytes
        _reject("unpickle")
        return None
    if not isinstance(compiled, ProgramCompileResult):
        _reject("unpickle")
        return None
    _STATS["loads"] += 1
    return compiled, dict(payloads)
