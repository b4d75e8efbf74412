"""Host microseconds inside ``SketchEngine.ingest`` per 1,000 live events
(the data plane's host buffer and dispatch, and on the pipeline plane the
routing by key), from the benchmark's ``ingest`` spans."""


def read(run):
    spent = run["spans"].durations("ingest")
    if not spent or run["events"] <= 0:
        return None
    return 1e6 * sum(spent) / (run["events"] / 1e3)
