"""Median host microseconds of the program's ``plane.collapse`` span over
the window's reads: dispatching the collective all-merge and taking the
first device's copy of its result (``PipelinePlane.state``).  The device's
own time is not in it: the read waits for it later, in the sample."""
import statistics

import program_spans as ps


def read(run):
    recs = ps.window_records(run)
    if recs is None:
        return None
    spent = [r.end_s - r.start_s for r in recs if r.name == "plane.collapse"]
    return 1e6 * statistics.median(spent) if spent else None
