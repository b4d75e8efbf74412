"""Least bytes and operations of the kernels, from operation shapes only.

The counts describe what the operation has to move, not how the one-hot
kernels happen to do it, so a faster implementation of the same operation
reads a higher roofline share and never a lower count.
"""
from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def scatter_bytes(events: int, streams: int, rows: int, width: int) -> int:
    """One scatter of ``events`` live (key, value) pairs into ``streams``
    tables: read each event's key and value (8 B), and read and write each
    table cell it touches once (8 B); at most every cell is touched."""
    return 8 * events + 8 * min(events * rows, streams * rows * width)


def query_bytes(keys: int, rows: int) -> int:
    """One query of ``keys`` keys (over all streams): read each key (4 B),
    one bucket per row and key (4 B), and write one read per row and key
    (4 B)."""
    return 4 * keys + 8 * keys * rows


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def roofline_pct(nbytes: float, flops: float, kernel_s: float,
                 peak: dict) -> float | None:
    """Least time over measured kernel time, in percent; None without time."""
    if kernel_s <= 0 or (nbytes <= 0 and flops <= 0):
        return None
    least = max(nbytes / peak["hbm_bytes_per_s"], flops / peak["bf16_flops_per_s"])
    return 100.0 * least / kernel_s
