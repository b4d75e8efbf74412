"""Share of the scatter kernel's slots that carried no live event, in
percent: 100 x (1 - live events / the ``slots`` counted by the window's
``plane.dispatch`` spans, the kernel's padded rows x columns)."""
import program_spans as ps


def read(run):
    recs = ps.window_records(run)
    if recs is None:
        return None
    slots = sum(r.counts.get("slots", 0) for r in recs
                if r.name == "plane.dispatch")
    if slots <= 0:
        return None
    return 100.0 * (1.0 - run["events"] / slots)
