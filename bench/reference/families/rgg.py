"""Random geometric graph on the unit square with the paper's radius
sqrt(2 log n / n): n uniform points, one ``rng.random((n, 2))`` per try,
redrawn until the graph is connected."""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

RANDOM = True
MAX_TRIES = 200


def edges(n: int, args: list, rng: np.random.Generator) -> np.ndarray:
    r = float(np.sqrt(2.0 * np.log(n) / n))
    for _ in range(MAX_TRIES):
        pts = rng.random((n, 2))
        d2 = ((pts[:, None, :] - pts[None, :, :]) ** 2).sum(-1)
        i, j = np.nonzero(np.triu(d2 <= r * r, k=1))
        e = np.stack([i, j], axis=1).astype(np.int64)
        if _connected(n, e):
            return e
    raise RuntimeError(f"no connected RGG(n={n}) in {MAX_TRIES} draws")


def _connected(n: int, edges: np.ndarray) -> bool:
    adj = sp.coo_matrix((np.ones(len(edges)), (edges[:, 0], edges[:, 1])),
                        shape=(n, n))
    ncomp, _ = sp.csgraph.connected_components(adj, directed=False)
    return ncomp == 1
