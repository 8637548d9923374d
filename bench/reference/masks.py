"""Per-round link schedules as the sweep grid defines them.

A schedule is keyed by (seed, graph), not by cell: every cell on one graph
sees the same draws, whatever its design or algorithm. The stream is
``default_rng([seed, crc32(repr((family, n, draw)))])``; its first (T, E)
uniforms decide the edges (edge k of the canonical (i, j) order is up in
round t iff u[t, k] >= p under ``bernoulli:p``); a static cell keeps every
edge up.
"""
from __future__ import annotations

import zlib

import numpy as np


def graph_rng(seed: int, key: tuple) -> np.random.Generator:
    return np.random.default_rng([int(seed), zlib.crc32(repr(key).encode("utf-8"))])


def edge_bits(dynamics: str, seed: int, key: tuple, rounds: int, num_edges: int) -> np.ndarray:
    """(T, E) bool, True where the link is up."""
    kind, *params = dynamics.split(":")
    if kind == "static":
        return np.ones((rounds, num_edges), dtype=bool)
    if kind == "bernoulli":
        u = graph_rng(seed, key).random((rounds, num_edges))
        return u >= float(params[0])
    raise ValueError(f"no reference schedule for dynamics {dynamics!r}")
