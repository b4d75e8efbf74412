"""Closed loop with reads: ``closed_ingest``'s loop, plus one read of the
whole state after every ``read_every_blocks`` ingests.

A read (``Context.read(None)``) drains the data plane, collapses the
shards (``SketchEngine.state``) and samples k keys with the result on the
host, so it waits for every block sent before it.  Between reads at most
``inflight`` ingests are on the device at once.  Reads count in
``attempted``; the window's events are the ingested ones, over the whole
window, reads' time included.

Mix parameters: ``pool_blocks``, ``inflight``, ``pool_seed``,
``read_every_blocks``.  The mix also declares ``reads_per_s`` (above 0)
and ``read``, which are what set-up (``Context.warm``) looks for to
compile the read path before the window; this driver reads by block
count and uses neither.

The record also holds ``collapse_hlo``: the plane's ``collapse_hlo``
method, which a reader of the profile calls after the window to tell the
collapse program's operations apart (None where the plane has none).
"""
from __future__ import annotations

import time


def run(ctx, mix: dict) -> dict:
    depth = int(mix["inflight"])
    every = int(mix["read_every_blocks"])
    t0 = time.perf_counter()
    end = t0 + ctx.seconds
    events = blocks = 0
    lat = []
    while time.perf_counter() < end:
        events += ctx.ingest(blocks)
        blocks += 1
        ctx.bound_inflight(depth)
        if blocks % every == 0:
            t = time.perf_counter()
            ctx.read(None)
            lat.append(time.perf_counter() - t)
    ctx.settle()
    return {"events": events, "elapsed_s": time.perf_counter() - t0,
            "attempted": blocks + len(lat), "failed": 0,
            "read_latency_s": lat,
            "collapse_hlo": getattr(ctx.engine.plane, "collapse_hlo", None)}
