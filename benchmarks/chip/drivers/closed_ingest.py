"""Closed loop: pool blocks back to back through ``ingest``, no reads.

At most ``inflight`` ingests are on the device at once: sized to some
five seconds of the cell's blocks, so that the chip stays fed while the
host stands still.  When the time is up nothing more is sent, and the
window ends when every block submitted in it has been applied: all of
that work counts, over all of that time.

Mix parameters: ``pool_blocks``, ``inflight``, ``pool_seed``.
"""
from __future__ import annotations

import time


def run(ctx, mix: dict) -> dict:
    depth = int(mix["inflight"])
    t0 = time.perf_counter()
    end = t0 + ctx.seconds
    events = blocks = 0
    while time.perf_counter() < end:
        events += ctx.ingest(blocks)
        blocks += 1
        ctx.bound_inflight(depth)
    ctx.settle()
    return {"events": events, "elapsed_s": time.perf_counter() - t0,
            "attempted": blocks, "failed": 0}
