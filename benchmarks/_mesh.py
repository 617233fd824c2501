"""Forced multi-device CPU mesh plumbing shared by the sharded benches and
the 2-device tests.

Three callers used to hand-roll the same two tricks (``bench_sharded``,
``bench_locality``, ``tests/test_sharded_executor.py``):

* **respawn, don't mutate** — forcing
  ``--xla_force_host_platform_device_count`` only works before jax is
  imported, and writing it into ``os.environ`` leaks into every later jax
  import of the calling process (a harness running several benchmarks
  would silently see fake devices).  :func:`respawn_with_devices` re-execs
  the current script in a child whose *copied* environment carries the
  flag; :func:`forced_device_env` is the reusable environment builder.
* **skip, don't fail** — a host whose environment cannot honor the forced
  count (flag already pinned, non-CPU platform) should report and skip.
  Children verify with :func:`require_devices` and print
  ``MESH_SKIP <have> <want>`` so the parent can tell "environment can't"
  from "code broke" (``tests/conftest.py`` turns it into a pytest skip).
* **retry transient spawns** — a loaded CI host can transiently fail the
  fork/exec itself (``OSError``: EAGAIN, resource limits) or OOM-kill the
  child before it runs a line.  :func:`run_with_spawn_retry` retries
  exactly those infra failures with exponential backoff; an ordinary
  nonzero exit (a real test failure) is NEVER retried — it must surface
  on the first run.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

MESH_SKIP = "MESH_SKIP"


def run_with_spawn_retry(cmd, *, attempts: int = 3, backoff_s: float = 0.5,
                         sleep=time.sleep, **kw):
    """``subprocess.run`` with bounded retry on *spawn/infra* failures
    only: an ``OSError`` raised by the spawn itself, or a child killed by
    a signal (negative returncode — the OOM-killer / a stray SIGKILL,
    not a test outcome).  Ordinary nonzero exits return immediately.
    Returns the last ``CompletedProcess`` (or re-raises the last
    ``OSError`` when every attempt failed to spawn)."""
    last_exc = None
    result = None
    for k in range(attempts):
        if k:
            sleep(backoff_s * (2 ** (k - 1)))
        try:
            result = subprocess.run(cmd, **kw)
        except OSError as e:
            last_exc = e
            continue
        if result.returncode >= 0:
            return result
    if result is not None:
        return result
    raise last_exc


def forced_device_env(n: int, base: dict = None) -> dict:
    """A copy of ``base`` (default ``os.environ``) whose ``XLA_FLAGS``
    forces an ``n``-device CPU platform — for a *child* process only; the
    caller's environment is never touched.  ``JAX_PLATFORMS=cpu`` keeps
    the child off an attached TPU, which belongs to one process."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={n} {flags}".strip()
    return env


def respawn_with_devices(n: int) -> int:
    """Run this script again in a child process with an n-device CPU
    platform forced via its (copied) environment; returns the exit code.
    The forced ``XLA_FLAGS`` / device count never leak into the calling
    process's environment or its later jax import.  Transient spawn
    failures (fork/exec errors, a signal-killed child) retry with backoff
    — see :func:`run_with_spawn_retry`."""
    return run_with_spawn_retry(
        [sys.executable, sys.argv[0], *sys.argv[1:], "--no-respawn"],
        env=forced_device_env(n)).returncode


def require_devices(n: int) -> bool:
    """In a (re)spawned child: do we actually see ``n`` devices?  Prints
    the ``MESH_SKIP`` sentinel when the forced count was not honored so
    the parent can skip instead of fail."""
    import jax
    have = len(jax.devices())
    if have < n:
        print(f"{MESH_SKIP} {have} {n}", flush=True)
        return False
    return True
