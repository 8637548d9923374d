"""Barabási–Albert preferential attachment, drawn as the sweep grid draws it.

``ba`` or ``ba:m``: each node after the first m + 1 joins m distinct earlier
nodes, each picked with probability proportional to its degree (m = 3 where
the spec names none). The convention, draw for draw:

* a seed star: node m joined to nodes 0..m-1;
* a running pool of edge endpoints, which a uniform index turns into a
  degree-proportional pick. It starts as the star's endpoints interleaved,
  (0, m, 1, m, ..., m-1, m), and after node v has its targets it grows by
  those m targets, in the order they were picked, then m copies of v;
* node v, for v = m + 1, ..., n - 1, picks its targets one attempt at a
  time: an attempt is one ``rng.integers(0, fill)`` over the pool's current
  length ``fill``, and an attempt that lands on a node already picked for v
  is rejected, until v has m of them.

The attempts of one node are drawn m at a time (``rng.integers(0, fill,
size=m)``), then one at a time while repeats leave v short: numpy's bounded
integers draw the stream of a sized call as the same number of scalar calls
would, so the generator ends where the attempt-by-attempt loop leaves it,
and the next graph of a grid draws from the same state.
"""
from __future__ import annotations

import numpy as np

from . import canonical

RANDOM = True
DEFAULT_M = 3


def edges(n: int, args: list, rng: np.random.Generator) -> np.ndarray:
    m = int(args[0]) if args else DEFAULT_M
    if m < 1 or n < m + 1:
        raise ValueError(f"ba:{m} needs m >= 1 and n >= m + 1, got n = {n}")
    pool = np.empty(2 * m * (n - m), dtype=np.int64)
    pool[:2 * m:2] = np.arange(m)
    pool[1:2 * m:2] = m
    fill = 2 * m
    for v in range(m + 1, n):
        picked = dict.fromkeys(pool[rng.integers(0, fill, size=m)].tolist())
        while len(picked) < m:
            picked.setdefault(int(pool[rng.integers(0, fill)]))
        pool[fill:fill + m] = list(picked)
        pool[fill + m:fill + 2 * m] = v
        fill += 2 * m
    # the star's edges, then each node's targets read back off the pool
    targets = pool[2 * m:].reshape(n - m - 1, 2 * m)[:, :m].ravel()
    return canonical(np.concatenate([np.full(m, m), np.repeat(np.arange(m + 1, n), m)]),
                     np.concatenate([np.arange(m), targets]))
