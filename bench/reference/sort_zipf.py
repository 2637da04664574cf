"""Plain reference of a sort of skewed keys: the same keys in nondecreasing
order."""
from __future__ import annotations

import numpy as np


def expected(keys: np.ndarray) -> np.ndarray:
    return np.sort(np.asarray(keys), kind="stable")


def mismatched(got: np.ndarray, want: np.ndarray) -> int:
    """Positions at which ``got`` differs from ``want``; every position
    counts where the lengths differ."""
    got = np.asarray(got)
    if got.shape != want.shape:
        return int(max(got.size, want.size))
    return int(np.count_nonzero(got != want))
