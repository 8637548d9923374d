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

BLOCK_DRAWS = 1 << 22      # uniforms held at once while a schedule is drawn


def graph_rng(seed: int, key: tuple) -> np.random.Generator:
    return np.random.default_rng([int(seed), zlib.crc32(repr(key).encode("utf-8"))])


def edge_bits(dynamics: str, seed: int, key: tuple, rounds: int, num_edges: int) -> np.ndarray:
    """(T, E) bool, True where the link is up."""
    kind, *params = dynamics.split(":")
    if kind == "static":
        return np.ones((rounds, num_edges), dtype=bool)
    if kind == "bernoulli":
        # rows drawn a block at a time, the same stream as one (T, E) draw:
        # a schedule of 1e6 edges over thousands of rounds holds its bits,
        # not 8 bytes a draw
        rng, p = graph_rng(seed, key), float(params[0])
        bits = np.empty((rounds, num_edges), dtype=bool)
        step = max(1, BLOCK_DRAWS // max(num_edges, 1))
        for t in range(0, rounds, step):
            bits[t:t + step] = rng.random((min(step, rounds - t), num_edges)) >= p
        return bits
    raise ValueError(f"no reference schedule for dynamics {dynamics!r}")
