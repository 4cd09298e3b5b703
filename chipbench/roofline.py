"""Peaks of the chip and the least time of the bitmap verdict's work.

The peak table ``peaks.json`` is keyed by JAX's ``device_kind`` and names
its source; a device that is not in it is an error, not a default.  No VPU
peak of the v5e is published, so none is used: the verdict's operations
are counted as the bit-plane matrix product that computes the same overlap
bounds (``2 * b`` int8 operations per pair), held against the int8 peak.
"""

from __future__ import annotations

import json
import os
from typing import Optional, Tuple

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks_for(kind: str, path: str = PEAKS_FILE) -> dict:
    with open(path) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in the peak table "
                       f"{path}; known: {sorted(table)}")
    return table[kind]


def verdict_ops(pairs: int, b: int) -> float:
    """Operations of the verdict on ``pairs`` pairs at width ``b``: the
    overlap of two b-bit rows as a product of bit planes, 2 * b int8
    operations (a multiply and an add per bit)."""
    return 2.0 * b * pairs


def bitmap_bytes(rows: int, b: int) -> float:
    """Bytes of ``rows`` b-bit bitmap rows, each read once."""
    return rows * b / 8.0


def least_time(ops: float, nbytes: float, peaks: dict) -> Tuple[float, str]:
    """The larger of the compute and the memory bound, and which it is."""
    compute = ops / peaks["int8_ops_per_s"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return (compute, "int8_ops") if compute >= memory else (memory, "hbm")


def share_percent(least_s: float, kernel_s: Optional[float]) -> Optional[float]:
    """Roofline share in percent, or None where no kernel time was read."""
    if not kernel_s or kernel_s <= 0:
        return None
    return 100.0 * least_s / kernel_s
