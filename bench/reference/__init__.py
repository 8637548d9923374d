"""Plain float64 references of the benchmark's deployments.

Nothing here imports the program under test (``repro``) or takes anything it
made: graphs, weights, spectra, coefficients and link-loss schedules are
rebuilt from the configuration's stated parameters and the seed, with numpy
and scipy alone.
"""
from __future__ import annotations

import numpy as np

from . import graphs, masks, rounds

__all__ = ["graphs", "masks", "rounds", "block", "run"]


def block(cell: graphs.Cell, x0: np.ndarray, num_rounds: int, mask_seed: int) -> rounds.Block:
    """One cell's rounds: its base weights, its schedule under ``mask_seed``."""
    g = cell.graph
    bits = masks.edge_bits(cell.dynamics, mask_seed, g.key, num_rounds, len(g.edges))
    if cell.algorithm == "accel":
        w = cell.weights
        return rounds.Block(g.edges, w.edge_w, w.edge_w, w.diag, "receiver",
                            cell.coef, bits, x0)
    p_ij, p_ji, diag = graphs.push_sum_arrays(g)
    return rounds.Block(g.edges, p_ij, p_ji, diag, "sender", cell.coef, bits, x0,
                        ratio=True)


def run(cells: list, x0s: list, num_rounds: int, mask_seed: int):
    """Float64 (x_final, mse) of each cell over ``num_rounds`` rounds."""
    return rounds.simulate([block(c, x, num_rounds, mask_seed)
                            for c, x in zip(cells, x0s)], num_rounds)
