"""The program's spans beside the benchmark's: the trace reduction names an
idle gap by an ``ember.*`` span nested in a ``bench.*`` one, and a traced
CPU rehearsal of each cell, with the recorder switched on for the window,
records one program span inside each benchmark span and writes its
annotations into the profiler's trace."""
import sys
import time
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import harness, peaks  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from chipbench.tests.test_chipbench_cells import tiny  # noqa: E402
from repro import tracing  # noqa: E402

#: the program span inside each benchmark span, by cell
OUTER = {"dlrm-v2.zipf": ("bench.submit", "submit"),
         "stablelm-3b.chat": ("bench.wave", "wave")}


def test_gap_named_by_the_program_span_inside_the_benchmark_span():
    ev = tr.Events(
        devices={"/device:TPU:0": [(0, 10, "sls_pallas.1"),
                                   (35, 50, "sls_pallas.2"),
                                   (60, 100, "sls_pallas.3")]},
        spans=[("bench.traced", 0, 100), ("bench.submit", 8, 95),
               ("ember.submit", 9, 94), ("ember.submit.put", 20, 30)])
    idle = dict(tr.reduce(ev).idle_gaps)
    # 10..35 (middle 22) lies in the put, 50..60 in the submit around it
    assert idle == {"ember.submit.put": pytest.approx(25e-9),
                    "ember.submit": pytest.approx(10e-9)}


@pytest.fixture
def recorder(monkeypatch, tmp_path):
    """The recorder on for each run's window, annotating while the
    profiler records; the records land in ``facts["program_spans"]``."""
    monkeypatch.setitem(peaks.PEAKS, "cpu",
                        peaks.Peaks(1e12, 1e11, "CPU rehearsal stand-in"))
    opened = harness.Run.open_window
    stopped = harness.Run._stop_trace
    closed = harness.Run.close_window

    def open_window(self):
        self.trace_dir = tmp_path / self.spec.name
        t = opened(self)
        tracing.enable(annotate=self.trace)
        return t

    def stop_trace(self, now):
        tracing.set_annotate(False)
        stopped(self, now)

    def close_window(self):
        t = closed(self)
        tracing.disable()
        self.facts["program_spans"] = tracing.take()
        return t

    monkeypatch.setattr(harness.Run, "open_window", open_window)
    monkeypatch.setattr(harness.Run, "_stop_trace", stop_trace)
    monkeypatch.setattr(harness.Run, "close_window", close_window)
    yield
    tracing.disable()
    tracing.take()


def _host_annotations(trace_dir) -> set:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(tr.find_file(trace_dir)))
    return {ev.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(tracing.PREFIX)}


@pytest.mark.parametrize("workload", sorted(OUTER))
def test_traced_rehearsal_records_program_spans(workload, recorder):
    run, out = harness.drive(tiny(workload), 2 ** 33 + 5, 1.0, True,
                             devices=jax.devices()[:1],
                             t0=time.perf_counter())
    f = run.facts
    lo, hi = f["t_open"], f["t_close"]
    bench_name, name = OUTER[workload]
    spans = f["program_spans"]
    assert spans and all(lo <= s.t0 <= s.t1 <= hi for s in spans)
    assert all(s.cpu >= 0 for s in spans)
    outer = [s for s in spans if s.name == name]
    bench = [(t0, t1) for n, t0, t1 in run.spans.records
             if n == bench_name and lo <= t0 and t1 <= hi]
    # one program span inside each benchmark span, over the same time
    assert len(outer) == len(bench) > 0
    assert all(a <= s.t0 <= s.t1 <= b for s, (a, b) in zip(outer, bench))
    wall = sum(s.t1 - s.t0 for s in outer)
    assert 0.95 * sum(b - a for a, b in bench) <= wall
    children = {s.name for s in spans if s.parent == name}
    if name == "wave":
        assert children == {"wave.admit", "wave.dispatch", "wave.sync",
                            "wave.emit"}
        sync = {s.key: s.t1 - s.t0 for s in spans if s.name == "wave.sync"}
        assert all(sync.get(s.key, 0.0) <= s.t1 - s.t0 for s in outer)
    else:
        assert children == {"submit.wait", "submit.harden",
                            "submit.marshal", "submit.put",
                            "submit.dispatch", "submit.split"}
    assert out["checks"] and all(c.ok for c in out["checks"])
    # while the profiler recorded, the spans were written into its trace
    assert f"{tracing.PREFIX}{name}" in _host_annotations(run.trace_dir)
