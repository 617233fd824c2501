"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps two
things: the device's operations per chip, ``(start_ns, end_ns, name)``, and
the benchmark's host spans (``bench.*``, written by
``jax.profiler.TraceAnnotation``), on the same clock.  ``reduce`` turns them
into the traced window's length, the time in which some operation ran on
each chip (the union of its operation intervals), time per operation name,
and the idle time named by the innermost host span around it.

On a TPU the device is each ``/device:TPU:<n>`` plane's ``XLA Ops`` line.
``device="cpu"`` reads XLA's CPU thunks (events with an ``hlo_op`` stat) in
their place: that is only for checking this reduction on a CPU recording.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
TPU_OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"
COLLECTIVE = re.compile(
    r"all-to-all|all_to_all|alltoall|reduce-scatter|reduce_scatter|"
    r"all-reduce|all_reduce|allreduce|all-gather|all_gather|allgather|"
    r"collective-permute|psum", re.I)
_SUFFIX = re.compile(r"(\.\d+)+$")
NS = 1e-9


@dataclasses.dataclass
class Events:
    devices: dict          # plane name -> [(start_ns, end_ns, name)]
    spans: list            # [(name, start_ns, end_ns)] host spans
    layout: dict = dataclasses.field(default_factory=dict)  # plane -> lines


def op_name(name: str) -> str:
    """An operation's name without XLA's numeric suffixes
    (``sls_pallas.12`` and ``sls_pallas.3`` are one kernel).  A TPU trace
    names an op by its HLO text, ``%sls_pallas.92 = f32[...] ...``: the
    instruction's name is the part before `` = ``."""
    if name.startswith("%"):
        name = name[1:].split(" = ", 1)[0]
    return _SUFFIX.sub("", name)


def find_file(path: Path) -> Path:
    path = Path(path)
    if path.is_file():
        return path
    found = sorted(path.glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load(path, device: str = "tpu") -> Events:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(find_file(path)))
    devices, spans, layout = {}, [], {}
    for plane in pd.planes:
        pname = plane.name
        layout[pname] = [line.name for line in plane.lines]
        is_tpu = TPU_PLANE.match(pname) is not None
        is_host = pname.startswith("/host:")
        if not (is_tpu or is_host):
            continue
        for line in plane.lines:
            if is_tpu and device == "tpu" and line.name != TPU_OP_LINE:
                continue
            for ev in line.events:
                name = ev.name
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if is_host and name.startswith(SPAN_PREFIX):
                    spans.append((name, s, e))
                elif is_tpu and device == "tpu":
                    devices.setdefault(pname, []).append((s, e, name))
                elif is_host and device == "cpu" and e > s and \
                        any(k == "hlo_op" for k, _ in ev.stats):
                    devices.setdefault(pname, []).append((s, e, name))
    return Events(devices, spans, layout)


def union(intervals, lo: int, hi: int) -> list:
    """Merged ``(start, end)`` intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: list, lo: int, hi: int) -> list:
    """Idle ``(start, end)`` intervals of [lo, hi] between busy ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def name_by_span(spans: list, points, default: str) -> list:
    """For each time in ``points``, the innermost benchmark span (the one
    that started last) that covers it, else ``default``."""
    import numpy as np
    pts = np.asarray(points, np.int64)
    order = np.argsort(pts)
    sorted_pts = pts[order]
    names = np.full(len(pts), -1, np.int64)
    labels = []
    for name, s, e in sorted(spans, key=lambda x: x[1]):
        if name == WINDOW_SPAN:
            continue
        a, b = np.searchsorted(sorted_pts, [s, e], side="left")
        if b > a:
            if name not in labels:
                labels.append(name)
            names[a:b] = labels.index(name)
    out = [default] * len(pts)
    for k, i in zip(order, names):
        if i >= 0:
            out[k] = labels[i]
    return out


def self_times(ops: list, lo: int, hi: int) -> list:
    """``(name, ns)`` of each op inside [lo, hi], less the time of the ops
    nested in it (a ``while`` holds its body's ops on the same line)."""
    out, stack = [], []
    for s, e, name in sorted(ops, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        d = max(0, min(e, hi) - max(s, lo))
        out.append([name, d])
        if stack and e <= stack[-1][1]:
            out[stack[-1][2]][1] -= d
        stack.append((s, e, len(out) - 1))
    return [tuple(x) for x in out]


@dataclasses.dataclass
class Summary:
    window_s: float        # length of the traced window
    busy_s: float          # union of device op intervals, mean over chips
    chips: int             # device planes found
    op_s: dict             # op name -> own seconds, summed over chips
    device_ops: list       # [[name, seconds per chip]] largest first, <= 10
    idle_gaps: list        # [[host span, idle seconds per chip]], <= 10

    def time_of(self, pattern) -> float:
        """Seconds, summed over chips, of the ops whose name matches."""
        rx = re.compile(pattern) if isinstance(pattern, str) else pattern
        return sum(s for n, s in self.op_s.items() if rx.search(n))


def window_of(ev: Events) -> tuple:
    """The traced window: the ``bench.traced`` span, else the extent of
    everything recorded."""
    for name, s, e in ev.spans:
        if name == WINDOW_SPAN:
            return s, e
    pts = [x for v in ev.devices.values() for s, e, _ in v for x in (s, e)]
    pts += [x for _, s, e in ev.spans for x in (s, e)]
    if not pts:
        raise ValueError("the trace holds no events")
    return min(pts), max(pts)


def reduce(ev: Events, top: int = 10) -> Summary:
    lo, hi = window_of(ev)
    window = (hi - lo) * NS
    chips = len(ev.devices)
    busy_total = 0.0
    op_s: dict = {}
    idle: dict = {}
    for plane in sorted(ev.devices):
        ops = ev.devices[plane]
        busy = union(ops, lo, hi)
        busy_total += sum(e - s for s, e in busy) * NS
        for name, d in self_times(ops, lo, hi):
            if d > 0:
                key = op_name(name)
                op_s[key] = op_s.get(key, 0.0) + d * NS
        idle_iv = gaps(busy, lo, hi)
        who = name_by_span(ev.spans, [(s + e) // 2 for s, e in idle_iv],
                           "no-span")
        for (s, e), name in zip(idle_iv, who):
            idle[name] = idle.get(name, 0.0) + (e - s) * NS
    n = max(chips, 1)
    device_ops = sorted(([k, v / n] for k, v in op_s.items()),
                        key=lambda kv: -kv[1])[:top]
    idle_gaps = sorted(([k, v / n] for k, v in idle.items()),
                       key=lambda kv: -kv[1])[:top]
    return Summary(window, busy_total / n, chips, op_s, device_ops,
                   idle_gaps)
