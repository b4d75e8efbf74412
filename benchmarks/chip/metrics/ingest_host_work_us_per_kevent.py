"""The host's own work inside ``SketchEngine.ingest``, in microseconds per
1,000 live events: the window's ``engine.ingest`` spans minus the part of
each that its ``plane.stage`` and ``plane.dispatch`` descendants cover
(buffering, concatenation, routing by key, compaction)."""
import program_spans as ps
import tracing


def read(run):
    recs = ps.window_records(run)
    if not recs or run["events"] <= 0:
        return None
    ingests = [r for r in recs if r.name == ps.INGEST]
    if not ingests:
        return None
    by_id = {r.id: r for r in recs}
    handed: dict = {}
    for r in recs:
        if r.name in ps.ENQUEUE:
            a = ps.ancestor(r, by_id, ps.INGEST)
            if a is not None:
                handed.setdefault(a.id, []).append((r.start_s, r.end_s))
    own = 0.0
    for r in ingests:
        own += r.end_s - r.start_s
        for s, e in tracing.union(tracing.clip(handed.get(r.id, []),
                                               r.start_s, r.end_s)):
            own -= e - s
    return ps.per_kevent_us(own, run["events"])
