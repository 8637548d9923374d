"""The graph families the reference can draw, one module each.

A topology spec is ``<family>`` or ``<family>:<arg>:...``, as the sweep grid
parses it; ``graphs.family`` finds ``<family>.py`` here by the spec's head,
so a family is added by adding a file. Each module has

* ``RANDOM`` — True where the family is drawn from the configuration's graph
  generator, ``graph_trials`` times per size; False where one graph per size
  is fixed by n;
* ``edges(n, args, rng)`` — the (E, 2) int64 edges, i < j, sorted by (i, j),
  where ``args`` are the spec's fields after the first ``:``. A random
  family consumes ``rng`` exactly as the sweep grid's builder does, so that
  successive draws of a grid come out graph for graph.

Modules use numpy and scipy alone, and ``canonical`` below.
"""
from __future__ import annotations

import numpy as np


def canonical(i: np.ndarray, j: np.ndarray) -> np.ndarray:
    """(E, 2) int64 edges (min, max) of each pair, sorted by (i, j)."""
    lo, hi = np.minimum(i, j), np.maximum(i, j)
    order = np.lexsort((hi, lo))
    return np.stack([lo[order], hi[order]], axis=1).astype(np.int64)
