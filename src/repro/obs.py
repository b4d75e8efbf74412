"""The program's spans and counters: one small recorder for the host path.

A span is a named interval on ``time.perf_counter`` seconds with the id of
the span that caused it (its parent) and a small dict of counts.  Spans are
taken per call and per flush -- never per event or per key -- and none reads
a device array or waits for the device, so recording never adds a sync.

Each span also enters a ``jax.profiler.TraceAnnotation`` of the same name:
while a profiler session runs, the spans land on the trace's host plane, on
the same clock as the device's operations; without one the annotation costs
about half a microsecond.

Records go into a fixed-size ring; once it is full the oldest record makes
room for the newest and is counted in ``dropped``.  Spans may be recorded
from any thread: each thread keeps its own innermost open span, and a span
opened on a worker thread names its parent explicitly.

Recording is on by default.  ``disable()`` turns ``span`` into a shared
no-op context manager; ``enable()`` turns it back on.

    from repro import obs
    with obs.span("plane.dispatch", slots=4096):
        ...
    for r in obs.records():      # oldest first
        print(r.name, r.end_s - r.start_s, r.parent, r.counts)
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import NamedTuple

import jax

CAPACITY = 65536


class Record(NamedTuple):
    id: int          # > 0, unique in the process
    parent: int      # id of the span that caused this one; 0 for none
    name: str
    start_s: float   # time.perf_counter
    end_s: float
    counts: dict     # e.g. {"slots": 8192}; empty for most spans


class _Span:
    """One open span; ``Recorder.span`` makes a fresh one per call."""

    __slots__ = ("_rec", "_name", "_parent", "_counts", "_id", "_prev",
                 "_ann", "_t0")

    def __init__(self, rec, name, parent, counts):
        self._rec, self._name, self._parent, self._counts = \
            rec, name, parent, counts

    def __enter__(self):
        rec = self._rec
        local = rec._local
        self._prev = getattr(local, "current", 0)
        if self._parent is None:
            self._parent = self._prev
        self._id = next(rec._ids)
        local.current = self._id
        self._ann = jax.profiler.TraceAnnotation(self._name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self._rec._local.current = self._prev
        self._rec._append(Record(self._id, self._parent, self._name,
                                 self._t0, t1, self._counts))
        return False


class Recorder:
    """A ring of ``capacity`` span records, safe to record into from any
    thread."""

    def __init__(self, capacity: int = CAPACITY):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.enabled = True
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count(1)
        self._noop = contextlib.nullcontext()
        self.reset()

    def reset(self) -> None:
        """Forget every record and the drop count (ids keep counting)."""
        with self._lock:
            self._ring: list = [None] * self.capacity
            self._written = 0
            self._dropped_until = float("-inf")

    def span(self, name: str, parent: int | None = None, **counts):
        """Context manager recording one span.  ``parent`` defaults to this
        thread's innermost open span; ``counts`` are kept with the record."""
        if not self.enabled:
            return self._noop
        return _Span(self, name, parent, counts)

    def current(self) -> int:
        """Id of this thread's innermost open span; 0 where none is open
        (and always 0 while recording is off)."""
        return getattr(self._local, "current", 0)

    def _append(self, record: Record) -> None:
        with self._lock:
            slot = self._written % self.capacity
            old = self._ring[slot]
            if old is not None:
                self._dropped_until = max(self._dropped_until, old.end_s)
            self._ring[slot] = record
            self._written += 1

    def records(self) -> list:
        """The records kept, in the order they ended."""
        with self._lock:
            n, cap = self._written, self.capacity
            if n <= cap:
                return self._ring[:n]
            i = n % cap
            return self._ring[i:] + self._ring[:i]

    @property
    def dropped(self) -> int:
        """Records the ring has overwritten."""
        with self._lock:
            return max(self._written - self.capacity, 0)

    @property
    def dropped_until(self) -> float:
        """The latest end time of a dropped record (-inf where none was):
        every record that ended after it is still kept."""
        with self._lock:
            return self._dropped_until


RECORDER = Recorder()


def span(name: str, parent: int | None = None, **counts):
    """``RECORDER.span``: the program's spans go through here."""
    return RECORDER.span(name, parent, **counts)


def current() -> int:
    return RECORDER.current()


def records() -> list:
    return RECORDER.records()


def dropped() -> int:
    return RECORDER.dropped


def dropped_until() -> float:
    return RECORDER.dropped_until


def reset() -> None:
    RECORDER.reset()


def disable() -> None:
    """Record nothing: every ``span`` is a shared no-op context manager."""
    RECORDER.enabled = False


def enable() -> None:
    RECORDER.enabled = True
