"""Time spent handing flushed batches to the runtime, in microseconds per
1,000 live events: the window's ``plane.stage`` (host-to-device copy) and
``plane.dispatch`` (the jitted update call) spans, including any wait on
the runtime's in-flight limit or allocator."""
import program_spans as ps


def read(run):
    recs = ps.window_records(run)
    if recs is None or run["events"] <= 0:
        return None
    spent = [r.end_s - r.start_s for r in recs if r.name in ps.ENQUEUE]
    if not spent:
        return None
    return ps.per_kevent_us(sum(spent), run["events"])
