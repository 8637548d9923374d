"""Chain: node i joined to node i + 1."""
from __future__ import annotations

import numpy as np

from . import canonical

RANDOM = False


def edges(n: int, args: list, rng: np.random.Generator) -> np.ndarray:
    i = np.arange(n - 1)
    return canonical(i, i + 1)
