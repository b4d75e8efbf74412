"""Median milliseconds of the collapsing ``state`` read (the shards' merge, waited for), over the reads of the traced run."""
import statistics


def read(run):
    spent = run["spans"].durations("read.state")
    return 1e3 * statistics.median(spent) if spent else None
