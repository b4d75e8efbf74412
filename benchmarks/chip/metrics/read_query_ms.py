"""Median milliseconds of ``sample_state`` (query kernel and top-k, result on the host), over the reads of the traced run."""
import statistics


def read(run):
    spent = run["spans"].durations("read.query")
    return 1e3 * statistics.median(spent) if spent else None
