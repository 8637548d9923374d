"""2-D grid: rows x cols nearest to square (rows the largest divisor of n
not above sqrt(n)), row-major node ids, each node joined to its right and
lower neighbours."""
from __future__ import annotations

import math

import numpy as np

from . import canonical

RANDOM = False


def near_square(n: int) -> tuple[int, int]:
    rows = max(math.isqrt(n), 1)
    while n % rows:
        rows -= 1
    return rows, n // rows


def edges(n: int, args: list, rng: np.random.Generator) -> np.ndarray:
    rows, cols = near_square(n)
    i = np.arange(n)
    r, c = np.divmod(i, cols)
    right, down = i[c < cols - 1], i[r < rows - 1]
    return canonical(np.concatenate([right, down]),
                     np.concatenate([right + 1, down + cols]))
