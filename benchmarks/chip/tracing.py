"""Host spans of the benchmark's own calls, and the reduction of a profiler
trace to device busy time, idle share, kernel time and idle gaps.

A span is recorded twice: on the host clock into ``Spans`` (for the
per-layer times) and as a ``jax.profiler.TraceAnnotation`` in the
profiler's own trace, so that idle gaps on the device can be laid against
what the host was doing on the same clock.

The reduction works on plain ``Event`` tuples; ``load`` turns an
``.xplane.pb`` into them.  Device operations are the events of the
``XLA Ops`` line of each ``/device:TPU:n`` plane; host spans are the
events of the host plane whose names are the benchmark's span names.
"""
from __future__ import annotations

import contextlib
import time
from typing import NamedTuple

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
WINDOW = "window"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int


class Spans:
    """Named host spans on ``time.perf_counter`` seconds."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: list = []   # (name, start_s, end_s)

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def durations(self, name: str) -> list:
        return [e - s for n, s, e in self.records if n == name]


def load(path: str) -> list:
    """Every event of an ``.xplane.pb`` file as ``Event`` tuples."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 int(ev.start_ns), int(ev.duration_ns)))
    return out


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def window_of(events) -> tuple:
    """(start, end) of the benchmark's ``window`` span on the host plane."""
    spans = [(e.start_ns, e.start_ns + e.dur_ns) for e in events
             if e.plane == HOST_PLANE and e.name == WINDOW]
    if not spans:
        raise ValueError("the trace holds no 'window' span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def device_ops(events) -> dict:
    """Per device plane, its operation events."""
    out: dict = {}
    for e in events:
        if e.plane.startswith(DEVICE_PREFIX) and e.line == OPS_LINE:
            out.setdefault(e.plane, []).append(e)
    return out


def reduce(events, host_spans=("generate", "ingest", "read", "wait", "idle")
           ) -> dict:
    """Busy and window seconds, kernel seconds by name, top operations and
    the longest idle gaps, each gap named by the host span that covers
    most of it ("none" where no span does; "idle" is the open loop
    sleeping until the next item is due).  Busy is averaged over the
    devices; operations and gaps are of the first device."""
    lo, hi = window_of(events)
    per_dev = device_ops(events)
    if not per_dev:
        raise ValueError("the trace holds no device operations")
    busy_by_dev = {}
    for dev, evs in per_dev.items():
        busy_by_dev[dev] = union(clip(
            [(e.start_ns, e.start_ns + e.dur_ns) for e in evs], lo, hi))
    busy_s = sum(sum(e - s for s, e in b) for b in busy_by_dev.values()) \
        / len(busy_by_dev) / 1e9
    first = sorted(per_dev)[0]
    by_name: dict = {}
    for e in per_dev[first]:
        s, t = max(e.start_ns, lo), min(e.start_ns + e.dur_ns, hi)
        if t > s:
            by_name[e.name] = by_name.get(e.name, 0) + (t - s)
    busy = busy_by_dev[first]
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    host = [(e.name, e.start_ns, e.start_ns + e.dur_ns) for e in events
            if e.plane == HOST_PLANE and e.name in host_spans]
    named = []
    for s, t in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        cover: dict = {}
        for n, hs, he in host:
            ov = min(t, he) - max(s, hs)
            if ov > 0:
                cover[n] = cover.get(n, 0) + ov
        named.append([max(cover, key=cover.get) if cover else "none",
                      (t - s) / 1e9])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_s,
        "op_s": {n: v / 1e9 for n, v in by_name.items()},
        "device_ops": [[n, v / 1e9] for n, v in
                       sorted(by_name.items(), key=lambda x: -x[1])[:10]],
        "idle_gaps": named,
    }


def kernel_s(summary: dict, kernel: str) -> float:
    """Seconds of the operations whose name contains ``kernel``."""
    return sum(v for n, v in summary["op_s"].items() if kernel in n)
