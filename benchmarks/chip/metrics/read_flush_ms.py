"""Median milliseconds of the drain before a read (``flush()``, waited for), over the reads of the traced run."""
import statistics


def read(run):
    spent = run["spans"].durations("read.flush")
    return 1e3 * statistics.median(spent) if spent else None
