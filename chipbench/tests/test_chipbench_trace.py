"""The trace reduction: exact on hand-made events, and on a small trace
recorded on the CPU (``data/cpu_trace.xplane.pb``: three ``bench.step``
spans around a jitted matmul, each followed by a 5 ms ``bench.host``
sleep, inside ``bench.traced``)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import trace as tr  # noqa: E402

DATA = Path(__file__).resolve().parent / "data" / "cpu_trace.xplane.pb"


def test_union_merges_overlaps_and_clips():
    ivs = [(5, 10, "a"), (8, 12, "b"), (20, 30, "c"), (0, 3, "d")]
    assert tr.union(ivs, 2, 25) == [(2, 3), (5, 12), (20, 25)]


def test_gaps_cover_the_rest_of_the_window():
    busy = [(2, 3), (5, 12), (20, 25)]
    assert tr.gaps(busy, 0, 30) == [(0, 2), (3, 5), (12, 20), (25, 30)]


def test_gap_named_by_innermost_span():
    spans = [("bench.traced", 0, 100), ("bench.wave", 10, 50),
             ("bench.submit", 20, 30)]
    assert tr.name_by_span(spans, [25, 40, 70], "no-span") == \
        ["bench.submit", "bench.wave", "no-span"]


def test_reduce_hand_made_two_chips():
    ev = tr.Events(
        devices={"/device:TPU:0": [(10, 40, "sls_pallas.3"),
                                   (30, 50, "fusion.1")],
                 "/device:TPU:1": [(0, 20, "all-to-all.2"),
                                   (60, 90, "sls_pallas.7")]},
        spans=[("bench.traced", 0, 100), ("bench.submit", 50, 100)])
    s = tr.reduce(ev)
    assert s.window_s == pytest.approx(100e-9)
    # chip 0 busy 10..50 (40), chip 1 busy 0..20 and 60..90 (50)
    assert s.busy_s == pytest.approx(45e-9)
    assert s.time_of("sls") == pytest.approx(60e-9)
    assert s.time_of(tr.COLLECTIVE) == pytest.approx(20e-9)
    assert s.device_ops[0] == ["sls_pallas", pytest.approx(30e-9)]
    idle = dict(s.idle_gaps)
    # chip 0: 0..10 no-span, 50..100 submit; chip 1: 20..60 (mid 40)
    # no-span, 90..100 submit; per chip
    assert idle["bench.submit"] == pytest.approx(30e-9)
    assert idle["no-span"] == pytest.approx(25e-9)


def test_nested_ops_count_their_own_time():
    ops = [(0, 100, "while.1"), (10, 30, "fusion.2"), (40, 90, "while.3"),
           (50, 60, "copy.4")]
    assert dict(tr.self_times(ops, 0, 100)) == {
        "while.1": 30, "fusion.2": 20, "while.3": 40, "copy.4": 10}
    ev = tr.Events(devices={"/device:TPU:0": ops},
                   spans=[("bench.traced", 0, 100)])
    s = tr.reduce(ev)
    assert s.busy_s == pytest.approx(100e-9)
    assert sum(v for _, v in s.device_ops) == pytest.approx(100e-9)


def test_op_name_drops_numeric_suffixes():
    assert tr.op_name("sls_pallas.12") == "sls_pallas"
    assert tr.op_name("fusion.3.1") == "fusion"
    assert tr.op_name("all-to-all") == "all-to-all"
    assert tr.op_name("%sls_pallas.92 = f32[1024,128]{1,0} custom-call("
                      "s32[1025]{0} %get-tuple-element.82)") == "sls_pallas"


def test_recorded_cpu_trace():
    ev = tr.load(DATA, device="cpu")
    names = [n for n, _, _ in ev.spans]
    assert names.count("bench.step") == 3
    assert names.count("bench.host") == 3
    assert names.count("bench.traced") == 1
    assert ev.devices, "no operations found"
    s = tr.reduce(ev)
    lo, hi = [(a, b) for n, a, b in ev.spans if n == "bench.traced"][0]
    assert s.window_s == pytest.approx((hi - lo) * 1e-9)
    assert 0 < s.busy_s < s.window_s
    assert s.time_of("dot_general") > 0
    idle = dict(s.idle_gaps)
    # the three sleeps leave the device idle for at least 15 ms
    assert idle["bench.host"] >= 0.015
    assert len(s.device_ops) <= 10 and len(s.idle_gaps) <= 10


def test_tpu_view_of_a_cpu_trace_has_no_device():
    ev = tr.load(DATA, device="tpu")
    assert ev.devices == {}
    assert "/host:CPU" in ev.layout
