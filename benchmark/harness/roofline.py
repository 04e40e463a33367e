"""Least time the chip could take for one scan, and the peaks table.

The scan of a group-by query must read every row in its range once
(group id, timestamp offset, value: the device columns) and write its
output grid; it computes a handful of operations a row, so memory
bandwidth bounds it.  `scan_min_bytes` counts those bytes from the
query's shape alone; `least_seconds` divides by the device's peak.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PEAKS, encoding="utf-8") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it "
                       f"to benchmark/peaks.json with its source")
    return table[device_kind]


def scan_min_bytes(rows: int, row_bytes: int, groups: int, buckets: int,
                   grids: int, cell_bytes: int = 4) -> int:
    """Bytes one scan must move: `rows` in range at `row_bytes` each,
    plus `grids` output grids of groups x buckets cells."""
    return rows * row_bytes + groups * buckets * grids * cell_bytes


def least_seconds(nbytes: int, device_kind: str) -> float:
    return nbytes / peaks(device_kind)["hbm_bytes_per_s"]
