"""The program's own spans (``repro.obs``), clipped to the run's window.

The program records its spans on ``time.perf_counter``, the clock of the
benchmark's own ``Spans``, so the ``window`` span bounds them directly.  A
program without ``repro.obs`` (older than its spans) gives nothing to read.
"""
from __future__ import annotations

import tracing

INGEST = "engine.ingest"
ENQUEUE = ("plane.stage", "plane.dispatch")


def window_records(run):
    """The program's records that lie inside the run's ``window`` span, or
    None where the program keeps none or its ring dropped a record that
    may lie in the window."""
    try:
        from repro import obs
    except ImportError:
        return None
    win = [(s, e) for n, s, e in run["spans"].records if n == tracing.WINDOW]
    if not win:
        return None
    lo, hi = win[-1]
    if obs.dropped_until() >= lo:
        return None
    return [r for r in obs.records() if r.start_s >= lo and r.end_s <= hi]


def ancestor(rec, by_id: dict, name: str):
    """The nearest ancestor of ``rec`` named ``name`` among ``by_id``'s
    records, or None."""
    p = by_id.get(rec.parent)
    while p is not None and p.name != name:
        p = by_id.get(p.parent)
    return p


def per_kevent_us(seconds: float, events: int) -> float:
    return 1e6 * seconds / (events / 1e3)
