"""Pair-set comparison for the correctness check: how many reference pairs
a run left out and how many pairs it reported that the reference does not
hold.  Both must be 0: the system's answer is exact."""

from __future__ import annotations

from typing import Tuple

import numpy as np


def keys(pairs: np.ndarray, width: int) -> np.ndarray:
    """One int64 per pair ``(a, b)``: ``a * width + b`` (``b < width``)."""
    pairs = np.asarray(pairs, dtype=np.int64).reshape(-1, 2)
    return pairs[:, 0] * np.int64(width) + pairs[:, 1]


def missing_extra(got: np.ndarray, want: np.ndarray,
                  width: int) -> Tuple[int, int]:
    """(pairs of ``want`` not in ``got``, pairs of ``got`` not in ``want``);
    a pair reported twice counts once more as extra."""
    g, w = keys(got, width), keys(want, width)
    gu = np.unique(g)
    missing = int(len(np.setdiff1d(w, gu, assume_unique=False)))
    extra = int(len(np.setdiff1d(gu, w))) + int(len(g) - len(gu))
    return missing, extra
