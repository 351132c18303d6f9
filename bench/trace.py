"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

``load`` turns the trace into plain ``Event`` tuples; the reductions
below work on those, so they are tested on synthetic events as well as on
a recorded trace.

* Device planes are the ``/device:TPU:<n>`` planes (not their SparseCore
  or other sub-planes). A device's busy time is the union of the intervals
  of its ``XLA Ops`` events; where a plane has no such line, of all its
  events.
* Program time: the ``XLA Modules`` line holds one event per execution
  of a jitted program, named ``jit_<function>(<id>)``; ``program_seconds``
  sums them by function name, per device.
* Idle gaps: the stretches of the window in which a device ran nothing,
  each named by the host span (``bench.*``) that overlaps it most.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


_DEVICE = re.compile(r"^/device:TPU:\d+$")
_PROGRAM = re.compile(r"^(?:jit_)?(.+?)(?:\(\d+\))?$")
HOST_SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"      # the host span that bounds the traced window


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> List[Event]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def device_planes(events: Iterable[Event]) -> List[str]:
    return sorted({e.plane for e in events if _DEVICE.match(e.plane)})


def _op_events(events: List[Event], plane: str) -> List[Event]:
    mine = [e for e in events if e.plane == plane]
    ops = [e for e in mine if e.line == "XLA Ops"]
    return ops or mine


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_seconds(events: List[Event], lo: float, hi: float) -> Dict[str, float]:
    """Per device plane: seconds inside [lo, hi] ns in which an op ran."""
    out = {}
    for plane in device_planes(events):
        iv = clip(union((e.start_ns, e.end_ns)
                        for e in _op_events(events, plane)), lo, hi)
        out[plane] = sum(e - s for s, e in iv) / 1e9
    return out


def program_name(event_name: str) -> str:
    return _PROGRAM.match(event_name).group(1)


def program_seconds(events: List[Event]) -> Dict[str, Dict[str, float]]:
    """{program: {device plane: seconds}} from the ``XLA Modules`` lines."""
    out: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float))
    for e in events:
        if e.line == "XLA Modules" and _DEVICE.match(e.plane):
            out[program_name(e.name)][e.plane] += e.dur_ns / 1e9
    return {k: dict(v) for k, v in out.items()}


def program_counts(events: List[Event]) -> Dict[str, Dict[str, int]]:
    """{program: {device plane: executions}}."""
    out: Dict[str, Dict[str, int]] = collections.defaultdict(
        lambda: collections.defaultdict(int))
    for e in events:
        if e.line == "XLA Modules" and _DEVICE.match(e.plane):
            out[program_name(e.name)][e.plane] += 1
    return {k: dict(v) for k, v in out.items()}


def op_name(event_name: str) -> str:
    """An op event's name without the HLO text after it (``%copy.237 =
    f32[...] copy(...)`` -> ``%copy.237``)."""
    return event_name.split(" = ", 1)[0]


def top_ops(events: List[Event], n: int = 10) -> List[List]:
    """The ``n`` device ops with the most time, summed over executions and
    averaged over devices: ``[[name, seconds], ...]``. A loop op (a scanned
    program's ``while``) counts the ops inside it too."""
    planes = device_planes(events)
    tot: Dict[str, float] = collections.defaultdict(float)
    for p in planes:
        for e in _op_events(events, p):
            tot[op_name(e.name)] += e.dur_ns / 1e9
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / max(len(planes), 1)] for k, v in ranked]


def host_spans(events: List[Event]) -> List[Event]:
    return [e for e in events if e.name.startswith(HOST_SPAN_PREFIX)
            and not _DEVICE.match(e.plane)]


def idle_gaps(events: List[Event], lo: float, hi: float,
              n: int = 10) -> List[List]:
    """The ``n`` longest stretches of [lo, hi] in which the first device
    ran nothing, each named by the host span overlapping it most (or
    ``unattributed``): ``[[name, seconds], ...]``."""
    planes = device_planes(events)
    if not planes:
        return []
    busy = clip(union((e.start_ns, e.end_ns)
                      for e in _op_events(events, planes[0])), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    spans = [e for e in host_spans(events) if e.name != WINDOW_SPAN]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, most = "unattributed", 0.0
        for sp in spans:
            ov = min(e, sp.end_ns) - max(s, sp.start_ns)
            if ov > most:
                best, most = sp.name, ov
        named.append([best, (e - s) / 1e9])
    return named


def window_bounds(events: List[Event], name: str) -> Optional[Tuple[float, float]]:
    """[start, end] ns of the host span ``name`` (the traced window)."""
    for e in events:
        if e.name == name:
            return e.start_ns, e.end_ns
    return None


def reduce(events: List[Event], window_span: str = WINDOW_SPAN) -> Dict:
    """Everything the per-layer readers take from a trace."""
    bounds = window_bounds(events, window_span)
    if bounds is None:
        raise ValueError(f"trace has no host span {window_span!r}")
    lo, hi = bounds
    busy = busy_seconds(events, lo, hi)
    return dict(window_s=(hi - lo) / 1e9, busy_s=busy,
                devices=len(busy),
                programs=program_seconds(events),
                program_counts=program_counts(events),
                device_ops=top_ops(events),
                idle_gaps=idle_gaps(events, lo, hi))
