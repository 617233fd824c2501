"""What every cell shares: finding its files by name, the chip check, the
compile cache, spans, the measured window, the profiler and the result.

A cell's driver (``chipbench/drivers/<driver>.py``) exposes
``run(run: Run) -> dict`` with ``attempted``, ``failed`` and ``checks``
(a list of :class:`Check`), and fills ``run.facts`` with what the metric
readers (``chipbench/metrics/<metric>.py``, each ``read(facts)``) need.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from chipbench import trace as tr

#: seconds of the measured window that a ``--trace 1`` run records
TRACE_SECONDS = 5.0
SPAN_PREFIX = tr.SPAN_PREFIX
TRACED_SPAN = tr.WINDOW_SPAN


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Check:
    """One number compared with its limit; it passes at or below it."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def correct(checks: list) -> bool:
    """A run is correct when it compared something and every number is at
    or below its limit."""
    return bool(checks) and all(c.ok for c in checks)


def check_line(checks: list) -> dict:
    return {c.name: {"value": float(c.value), "limit": float(c.limit)}
            for c in checks}


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


_MODULES: dict = {}


def load_module(path: Path):
    """Import a file that is named after a cell, a driver or a metric
    (names may hold ``.`` and ``-``, so they are not module names)."""
    path = Path(path)
    if path in _MODULES:
        return _MODULES[path]
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    name = "chipbench_file_" + "".join(
        c if c.isalnum() else "_" for c in path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _MODULES[path] = mod
    return mod


def _find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


@dataclasses.dataclass
class CellSpec:
    root: Path
    bench: dict
    workload: dict
    config: dict
    traffic: dict

    @property
    def name(self) -> str:
        return self.workload["name"]

    def metrics(self, kind: str) -> list:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.bench[kind]
                if "workloads" not in m or self.name in m["workloads"]]


def cell_spec(root: Path, workload: str) -> CellSpec:
    root = Path(root)
    bench = load_json(root / "BENCHMARK.json")
    wl = _find(bench["workloads"], workload, "workload")
    ce = _find(bench["configs"], wl["config"], "config")
    return CellSpec(root, bench, wl, load_json(root / ce["file"]),
                    load_json(root / "chipbench" / "traffic"
                              / f"{wl['traffic']}.json"))


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else the fixed ``<checkout>/.jax_cache``.  Every program is kept,
    however quick its compile, so a second run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(chips: int) -> list:
    import jax
    devs = jax.devices()
    if any(d.platform != "tpu" for d in devs):
        raise NoChip(f"JAX's devices are {devs[0].platform!r}, not TPUs; "
                     f"the benchmark measures on the chip only")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    return devs[:chips]


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling while active."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def take(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def memory_peak(devices) -> int:
    """``peak_bytes_in_use`` of the fullest chip (never reset: the peak of
    the whole process so far)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def seed_key(seed: int):
    """A JAX key from any whole-number seed (wider than 32 bits too)."""
    import jax
    state = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(state, np.uint32))


def seed_rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *map(int, tags)])


class Spans:
    """Host spans ``(name, t0, t1)`` on ``time.perf_counter``; while the
    profiler records they are written into its trace too."""

    def __init__(self):
        self.records: list = []
        self.tracing = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        name = SPAN_PREFIX + name
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if self.tracing:
                ann.__exit__(None, None, None)
            self.records.append((name, t0, t1))

    def durations(self, name: str, lo: float, hi: float) -> np.ndarray:
        """Durations of the spans ``name`` that lie inside [lo, hi]."""
        name = SPAN_PREFIX + name
        return np.array([t1 - t0 for n, t0, t1 in self.records
                         if n == name and t0 >= lo and t1 <= hi])


class Run:
    """One run of one cell: what the driver is given, and the window."""

    def __init__(self, spec: CellSpec, seed: int, seconds: float,
                 trace: bool, devices: list, t0: float):
        self.spec = spec
        self.root = spec.root
        self.config = spec.config
        self.traffic = spec.traffic
        self.chips = spec.workload["chips"]
        self.seed = seed
        self.seconds = float(seconds)
        self.trace = trace
        self.devices = devices
        self.t0 = t0
        self.clock = CompileClock()
        self.spans = Spans()
        self.facts: dict = {"chips": self.chips}
        self.trace_dir = self.root / ".bench_trace" / spec.name
        self._traced = None
        self._trace_end = None
        #: also read the control (``chipbench/control.py``), never in a
        #: benchmark run
        self.control = False

    def key(self):
        return seed_key(self.seed)

    def rng(self, *tags: int) -> np.random.Generator:
        return seed_rng(self.seed, *tags)

    def open_window(self) -> float:
        """End of set-up: everything the window uses is compiled and warm."""
        import jax
        self.facts["compile_s"] = self.clock.take()
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.trace_dir))
            self.spans.tracing = True
            self._traced = jax.profiler.TraceAnnotation(TRACED_SPAN)
            self._traced.__enter__()
        t = time.perf_counter()
        self.facts["setup_s"] = t - self.t0
        self.facts["t_open"] = t
        self._trace_end = t + min(TRACE_SECONDS, self.seconds)
        self.facts["traced"] = (t, None)
        return t

    def poll(self, now: float) -> None:
        """Called between units of work: ends the traced part on time."""
        if self._traced is not None and now >= self._trace_end:
            self._stop_trace(now)

    def _stop_trace(self, now: float) -> None:
        import jax
        self._traced.__exit__(None, None, None)
        self._traced = None
        self.spans.tracing = False
        self.facts["traced"] = (self.facts["t_open"], now)
        jax.profiler.stop_trace()
        # writing the trace out takes seconds in which no work is done
        self.facts["paused"] = (now, time.perf_counter())

    def close_window(self) -> float:
        t = time.perf_counter()
        self.facts["t_close"] = t
        self.facts["window_s"] = t - self.facts["t_open"]
        self.facts["window_compile_s"] = self.clock.take()
        if self._traced is not None:
            self._stop_trace(t)
        a, b = self.facts.get("paused", (t, t))
        # the window without the profiler's stop, for rates of work
        self.facts["measured_s"] = t - self.facts["t_open"] - \
            max(0.0, min(b, t) - a)
        return t

    def reduce_trace(self) -> None:
        if not self.trace:
            return
        events = tr.load(self.trace_dir, device="tpu")
        for plane, lines in sorted(events.layout.items()):
            print(f"trace plane {plane}: {lines}", file=sys.stderr)
        self.facts["trace"] = tr.reduce(events)
        shutil.rmtree(self.trace_dir, ignore_errors=True)


def _device_line(devices, peak: int, summary) -> dict:
    d = devices[0]
    out = {"platform": d.platform, "kind": d.device_kind,
           "count": len(devices), "memory_peak_bytes": int(peak)}
    if summary is not None:
        out["busy_s"] = summary.busy_s
        out["window_s"] = summary.window_s
    return out


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, t0: float) -> int:
    """Run the cell on the chips this process finds; returns the exit
    code (3, and no result, without them)."""
    spec = cell_spec(root, workload)
    enable_compile_cache(root)
    try:
        devices = require_chips(spec.workload["chips"])
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    return run_spec(spec, seed, seconds, trace, devices=devices, t0=t0)


def drive(spec: CellSpec, seed: int, seconds: float, trace: bool, *,
          devices: list, t0: float, control: bool = False):
    """Set up, measure and check one run; returns the run and what its
    driver returned."""
    run = Run(spec, seed, seconds, trace, devices, t0)
    run.control = control
    driver = load_module(Path(spec.root) / "chipbench" / "drivers"
                         / f"{spec.config['driver']}.py")
    return run, driver.run(run)


def run_spec(spec: CellSpec, seed: int, seconds: float, trace: bool, *,
             devices: list, t0: float) -> int:
    """Run a cell on the given devices and print its result (the tests
    call this on the CPU, with a cell cut to a tiny size)."""
    root = spec.root
    run, out = drive(spec, seed, seconds, trace, devices=devices, t0=t0)
    f = run.facts
    print(f"timing: setup {f['setup_s']:.3f} s, window {f['window_s']:.3f} "
          f"s, check {time.perf_counter() - f['t_close']:.3f} s, compiles "
          f"in the window {f['window_compile_s']:.3f} s", flush=True)
    run.reduce_trace()
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.metrics(kind):
        reader = load_module(Path(root) / "chipbench" / "metrics"
                             / f"{m['name']}.py")
        value = reader.read(run.facts)
        if value is None:
            if kind == "end_to_end":
                raise RuntimeError(f"{spec.name}: end-to-end metric "
                                   f"{m['name']} found nothing to read")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    summary = run.facts.get("trace")
    checks = out["checks"]
    line = {"correct": correct(checks),
            "attempted": int(out["attempted"]),
            "failed": int(out["failed"]),
            "metrics": metrics,
            "device": _device_line(devices, out["memory_peak_bytes"],
                                   summary)}
    if summary is not None:
        line["breakdown"] = {"device_ops": summary.device_ops,
                             "idle_gaps": summary.idle_gaps}
    line["checks"] = check_line(checks)
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
