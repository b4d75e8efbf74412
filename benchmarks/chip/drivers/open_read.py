"""Open loop: ingest at a fixed event rate, reads at fixed arrival times.

Pool blocks are due when the events before them, at ``events_per_s``,
have been due; reads arrive ``reads_per_s`` times a second on average,
with exponential gaps.  Every seed gets the same set of gaps in another
order, so the work is the same.  Nothing waits for the system: an item
done late is late, and a read is timed from its due time to its result
on the host.  At most ``inflight`` ingests are on the device at once, so
a backlog shows as lateness on the host clock.

Mix parameters: ``pool_blocks``, ``inflight``, ``events_per_s``,
``reads_per_s``, ``read`` ("one_stream": a stream drawn uniformly from
the seed; "all": the whole state).
"""
from __future__ import annotations

import time

import numpy as np


def read_times(rng, seconds: float, per_s: float) -> np.ndarray:
    """``per_s * seconds`` arrival offsets in [0, seconds)."""
    n = max(int(round(per_s * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / per_s
    gaps = rng.permutation(gaps)
    return np.cumsum(gaps) * (seconds / (gaps.sum() + gaps.mean()))


def run(ctx, mix: dict) -> dict:
    import ycsb

    rng = ycsb.rng_for(ctx.seed, 2)
    rate = float(mix["events_per_s"])
    depth = int(mix["inflight"])
    reads = read_times(rng, ctx.seconds, float(mix["reads_per_s"]))
    B = ctx.cell.config["engine"]["num_streams"]
    targets = (rng.integers(0, B, reads.size) if mix["read"] == "one_stream"
               else [None] * reads.size)
    lat, late = [], []
    t0 = time.perf_counter()
    end = t0 + ctx.seconds
    due_events, blocks, r = 0, 0, 0
    while True:
        due_ingest = t0 + due_events / rate
        due_read = t0 + reads[r] if r < reads.size else float("inf")
        due = min(due_ingest, due_read)
        if due >= end:
            break
        now = time.perf_counter()
        if due > now:
            with ctx.spans.span("idle"):
                time.sleep(due - now)
            now = time.perf_counter()
        if due_read <= due_ingest:
            ctx.read(None if targets[r] is None else int(targets[r]))
            lat.append(time.perf_counter() - due_read)
            r += 1
        else:
            late.append(now - due_ingest)
            due_events += ctx.ingest(blocks)
            blocks += 1
            ctx.bound_inflight(depth)
    ctx.settle()
    q = max(len(late) // 4, 1)
    return {"events": due_events, "elapsed_s": time.perf_counter() - t0,
            "attempted": blocks + r, "failed": 0, "read_latency_s": lat,
            "lateness_s": [float(np.mean(late[:q])), float(np.mean(late[-q:]))]
            if late else None}
