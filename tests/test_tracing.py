"""Program spans (``repro.tracing``): off costs nothing and records
nothing; on, spans nest with their parent and key; the executor's
``submit`` and the server's ``step`` record their layer boundaries; and
the server's wave-time EWMA times the wave through its argmax sync."""
import time

import jax
import numpy as np
import pytest

from repro import tracing
from repro.core.executor import ProgramExecutor
from repro.core.ops import EmbeddingOp, EmbeddingProgram, make_program_inputs
from repro.core.pipeline import compile_program
from repro.runtime.server import DecodeServer, Request

from test_server import EchoLM

SUBMIT_CHILDREN = ["submit.wait", "submit.harden", "submit.marshal",
                   "submit.put", "submit.put", "submit.dispatch",
                   "submit.split"]
WAVE_CHILDREN = ["wave.admit", "wave.dispatch", "wave.sync", "wave.emit"]


@pytest.fixture(autouse=True)
def recorder_off():
    tracing.disable()
    tracing.take()
    yield
    tracing.disable()
    tracing.take()


class Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps the names."""

    def __init__(self):
        self.names = []

    def __call__(self, name):
        self.names.append(name)
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _fused_sls():
    prog = EmbeddingProgram("pooled", (
        ("t0", EmbeddingOp("sls", 6, 40, 8, avg_lookups=3)),
        ("t1", EmbeddingOp("sls", 6, 25, 8, avg_lookups=2)),
    ))
    ex = ProgramExecutor(compile_program(prog, "O3", vlen=4,
                                         use_cache=False))
    return ex, make_program_inputs(prog, seed=0)


def _echo_server(**kw):
    return DecodeServer(EchoLM(), {}, batch_slots=1, max_len=32,
                        prefill_chunk=4, **kw)


def _by_key(spans, key):
    return [s.name for s in sorted((s for s in spans if s.key == key),
                                   key=lambda s: s.t0)]


def test_off_records_nothing_and_builds_no_annotation(monkeypatch):
    def refuse(name):
        raise AssertionError(f"annotation {name!r} built while off")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    with tracing.span("outer", 1):
        with tracing.span("inner"):
            pass
    ex, ins = _fused_sls()
    ex.step(ins)
    srv = _echo_server()
    srv.submit(Request(prompt=np.asarray([3, 4], np.int32),
                       max_new_tokens=2))
    srv.run_until_drained()
    assert tracing.take() == []


def test_spans_nest_with_parent_key_and_cpu():
    tracing.enable()
    with tracing.span("outer", 7):
        with tracing.span("inner"):
            time.sleep(0.02)
        with tracing.span("other", 9):
            sum(range(10000))
    spans = {s.name: s for s in tracing.take()}
    assert set(spans) == {"outer", "inner", "other"}
    outer, inner, other = spans["outer"], spans["inner"], spans["other"]
    assert outer.parent is None and outer.key == 7
    assert inner.parent == "outer" and inner.key == 7   # key inherited
    assert other.parent == "outer" and other.key == 9
    assert outer.t0 <= inner.t0 < inner.t1 <= other.t0 < other.t1 \
        <= outer.t1
    assert other.cpu > 0
    # the sleep is wall time the thread did not spend on the CPU
    for s in (outer, inner):
        assert 0 <= s.cpu <= s.t1 - s.t0 - 0.015


def test_take_empties_and_disable_stops_recording():
    tracing.enable()
    with tracing.span("a"):
        pass
    assert [s.name for s in tracing.take()] == ["a"]
    assert tracing.take() == []
    tracing.disable()
    with tracing.span("b"):
        pass
    assert tracing.take() == []


def test_annotations_follow_the_switch(monkeypatch):
    ann = Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    tracing.enable(annotate=True)
    with tracing.span("wave", 0):
        with tracing.span("wave.sync"):
            pass
    tracing.set_annotate(False)
    with tracing.span("wave", 1):
        pass
    assert ann.names == ["ember.wave", "ember.wave.sync"]
    assert [s.name for s in tracing.take()] == ["wave.sync", "wave",
                                                "wave"]


def test_submit_spans_in_order_under_the_step_index():
    ex, ins = _fused_sls()
    ex.step(ins)                    # binds the tables
    tracing.enable()
    ex.step(ins)
    spans = tracing.take()
    assert _by_key(spans, 1) == ["submit"] + SUBMIT_CHILDREN + ["result"]
    parents = {s.name: s.parent for s in spans}
    assert all(parents[n] == "submit" for n in SUBMIT_CHILDREN)
    assert parents["submit"] is None and parents["result"] is None
    sub = next(s for s in spans if s.name == "submit")
    assert all(sub.t0 <= s.t0 <= s.t1 <= sub.t1 for s in spans
               if s.parent == "submit")


def test_wave_spans_for_a_prefill_and_a_decode_wave():
    srv = _echo_server()
    req = Request(prompt=np.asarray([3, 4, 5], np.int32), max_new_tokens=2)
    srv.submit(req)
    tracing.enable()
    srv.step()                      # wave 0: prefill, emits the first token
    srv.step()                      # wave 1: decode, emits the second
    spans = tracing.take()
    assert req.done and req.out == [6, 7]
    for key in (0, 1):
        assert _by_key(spans, key) == ["wave"] + WAVE_CHILDREN
    assert {s.parent for s in spans if s.name != "wave"} == {"wave"}
    assert srv.serve_stats["prefill_waves"] == 1
    assert srv.serve_stats["decode_waves"] == 1


class SlowLogits(np.ndarray):
    """Logits that take ``DELAY`` seconds to read, as a wave still running
    on the device does."""
    DELAY = 0.2

    def __getitem__(self, k):
        time.sleep(self.DELAY)
        return np.asarray(self).__getitem__(k)


def test_wave_ewma_times_the_wave_through_its_sync():
    srv = _echo_server()
    inner = srv._wave

    def wave(params, tokens, lens, caches):
        logits, caches = inner(params, tokens, lens, caches)
        return np.asarray(logits).view(SlowLogits), caches
    srv._wave = wave
    srv.submit(Request(prompt=np.asarray([3], np.int32), max_new_tokens=1))
    tracing.enable()
    srv.step()
    sync = next(s for s in tracing.take() if s.name == "wave.sync")
    assert sync.t1 - sync.t0 >= SlowLogits.DELAY
    assert srv._ewma_wave_s >= SlowLogits.DELAY
