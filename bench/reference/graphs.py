"""The deployment's graphs, weights and per-cell coefficients, in float64.

A configuration names topology families, sizes, graph draws, theta designs
and algorithms; ``cells`` turns that into one ``Cell`` per grid cell in the
order the sweep grid stacks them (algorithm outermost, then graph, then
design, then dynamics). Conventions follow the paper (arXiv:0903.3537):

* graph families are modules of ``families/``, found by the head of the
  topology spec (``"ba:10"`` -> ``families/ba.py``), each with its own
  convention; a random family draws from one generator seeded with the
  configuration's graph seed, draw after draw in grid order;
* Metropolis-Hastings weights W_ij = 1 / (1 + max(d_i, d_j)), replaced by the
  lazy (I + W) / 2 where |lambda_N| > lambda_2 (Theorem 1's condition);
* lambda_2 exact (eigvalsh) up to ``EXACT_SPECTRUM_MAX`` nodes; above it the
  spectrum extremes come from 500 deflated power-iteration steps from a
  standard-normal start of seed 0, which is how the sweep grid defines them
  for large graphs;
* two-tap coefficients (a, b, c) = (1 - alpha + alpha t3, alpha t2,
  alpha t1) with Theorem 1's alpha*(lambda_2) (Eq. 14); memoryless is
  (1, 0, 0);
* push-sum: column-stochastic P_ij = 1 / (1 + d_j) on each arc j -> i and
  on the diagonal, run as a (value, mass) pair.
"""
from __future__ import annotations

import dataclasses
import importlib
import math
import re
from pathlib import Path

import numpy as np
import scipy.sparse as sp

from . import families

EXACT_SPECTRUM_MAX = 1024
POWER_ITERS = 500
POWER_TOL = 1e-12
FAMILIES = Path(families.__file__).resolve().parent
FAMILY_NAME = re.compile(r"^[A-Za-z][A-Za-z0-9_]*$")

THETAS = {
    "memoryless": None,
    "ls": (-2.0 / 3.0, 1.0 / 3.0, 4.0 / 3.0),   # Aysal et al. least squares
    "asymptotic": (-0.5, 0.0, 1.5),            # theta(eps = 1/2), Sec. III-B
}


@dataclasses.dataclass(frozen=True)
class Graph:
    family: str
    n: int
    draw: int
    edges: np.ndarray          # (E, 2) int64, i < j, sorted by (i, j)

    @property
    def key(self) -> tuple:
        return (self.family, self.n, self.draw)

    @property
    def degrees(self) -> np.ndarray:
        return (np.bincount(self.edges[:, 0], minlength=self.n)
                + np.bincount(self.edges[:, 1], minlength=self.n))


@dataclasses.dataclass(frozen=True)
class Weights:
    """A symmetric matrix in edge form: W_ij = W_ji = edge_w on each edge."""

    edge_w: np.ndarray         # (E,)
    diag: np.ndarray           # (n,)
    lam2: float
    lam_n: float


@dataclasses.dataclass(frozen=True)
class Cell:
    graph: Graph
    algorithm: str             # "accel" | "push_sum"
    design: str                # theta design, or the algorithm's name
    dynamics: str
    coef: tuple                # (a, b, c)
    weights: Weights | None    # the symmetric base (accel), None for push-sum


def family(spec: str):
    """(module, args) of a topology spec ``<family>[:arg...]``: the module of
    ``families/`` named by its head, and the fields after the first ``:``."""
    head, *args = str(spec).split(":")
    if not FAMILY_NAME.match(head) or not (FAMILIES / f"{head}.py").is_file():
        raise ValueError(f"no reference for topology {spec!r}")
    return importlib.import_module(f"{families.__name__}.{head}"), args


def draw_graphs(topologies, sizes, graph_trials: int, graph_seed: int) -> list[Graph]:
    """Every graph of the grid, in grid order, from one seeded generator."""
    rng = np.random.default_rng(graph_seed)
    out = []
    for spec in topologies:
        fam, args = family(spec)
        for n in sizes:
            for d in range(graph_trials if fam.RANDOM else 1):
                out.append(Graph(spec, int(n), d, fam.edges(int(n), args, rng)))
    return out


def matrix(n: int, edges: np.ndarray, edge_w: np.ndarray, diag: np.ndarray,
           edge_w_rev: np.ndarray | None = None) -> sp.csr_matrix:
    """CSR of W: W[i, j] = edge_w, W[j, i] = edge_w_rev (default edge_w)."""
    rev = edge_w if edge_w_rev is None else edge_w_rev
    i, j = edges[:, 0], edges[:, 1]
    ar = np.arange(n)
    return sp.csr_matrix((np.concatenate([edge_w, rev, diag]),
                          (np.concatenate([i, j, ar]), np.concatenate([j, i, ar]))),
                         shape=(n, n))


def _power_extremes(w: sp.csr_matrix) -> tuple[float, float]:
    """(lambda_2, lambda_N) by deflated power iteration, seed-0 start."""
    n = w.shape[0]
    rng = np.random.default_rng(0)

    def iterate(step):
        v = rng.standard_normal(n)
        prev, val = np.inf, 0.0
        for _ in range(POWER_ITERS):
            v -= v.mean()
            nv = np.linalg.norm(v)
            if nv < 1e-30:
                v = rng.standard_normal(n)
                continue
            v /= nv
            nxt = step(v)
            val = float(v @ nxt)
            v = nxt
            if abs(val - prev) < POWER_TOL:
                break
            prev = val
        return val

    mu = iterate(lambda v: 0.5 * (v + w @ v))      # top of (I + W) / 2
    nu = iterate(lambda v: v - w @ v)              # top of I - W
    return min(2.0 * mu - 1.0, 1.0 - 1e-12), max(1.0 - nu, -1.0)


def mh_weights(g: Graph) -> Weights:
    deg = g.degrees
    i, j = g.edges[:, 0], g.edges[:, 1]
    ew = 1.0 / (1.0 + np.maximum(deg[i], deg[j]))
    diag = 1.0 - np.bincount(i, ew, g.n) - np.bincount(j, ew, g.n)
    if g.n <= EXACT_SPECTRUM_MAX:
        def extremes(ew, diag):
            vals = np.linalg.eigvalsh(matrix(g.n, g.edges, ew, diag).toarray())
            return float(vals[-2]), float(vals[0])
    else:
        def extremes(ew, diag):
            return _power_extremes(matrix(g.n, g.edges, ew, diag))
    lam2, lam_n = extremes(ew, diag)
    if abs(lam_n) > lam2:
        ew, diag = 0.5 * ew, 0.5 * (1.0 + diag)
        if g.n <= EXACT_SPECTRUM_MAX:
            lam2, lam_n = extremes(ew, diag)
        else:
            lam2, lam_n = 0.5 * (1.0 + lam2), 0.5 * (1.0 + lam_n)
    return Weights(ew, diag, lam2, lam_n)


def alpha_star(lam: float, theta: tuple) -> float:
    """Theorem 1 / Eq. (14)."""
    t1, t2, t3 = theta
    den = (t2 + (t3 - 1.0) * lam) ** 2
    if den < 1e-300:
        return 0.0
    rad = max(t1 * t1 + t1 * lam * (t2 + (t3 - 1.0) * lam), 0.0)
    return (-((t3 - 1.0) * lam * lam + t2 * lam + 2.0 * t1)
            - 2.0 * math.sqrt(rad)) / den


def two_tap_coef(design: str, lam2: float) -> tuple:
    theta = THETAS[design]
    if theta is None:
        return (1.0, 0.0, 0.0)
    t1, t2, t3 = theta
    al = alpha_star(lam2, theta)
    return (1.0 - al + al * t3, al * t2, al * t1)


def push_sum_arrays(g: Graph) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(P[i, j], P[j, i], diag) per canonical edge (i, j) of the push matrix."""
    share = 1.0 / (1.0 + g.degrees)
    i, j = g.edges[:, 0], g.edges[:, 1]
    return share[j], share[i], share


def layout(config: dict, cell: dict) -> list[tuple]:
    """(family, n, draw, algorithm, design, dynamics) of every cell, in grid
    order; needs no graph, so it is cheap at any size."""
    out = []
    for algo in cell["algorithms"]:
        for fam in config["topologies"]:
            draws = config["graph_trials"] if family(fam)[0].RANDOM else 1
            for n in config["sizes"]:
                for d in range(draws):
                    designs = config["designs"] if algo == "accel" else (algo,)
                    out += [(fam, int(n), d, algo, des, dyn)
                            for des in designs for dyn in cell["dynamics"]]
    return out


def cells(config: dict, cell: dict) -> list[Cell]:
    """Every cell of the grid, in the order the sweep grid stacks them."""
    graphs = {g.key: g for g in draw_graphs(config["topologies"], config["sizes"],
                                            config["graph_trials"], config["graph_seed"])}
    weights = {}
    out = []
    for fam, n, d, algo, design, dyn in layout(config, cell):
        g = graphs[(fam, n, d)]
        if algo == "accel":
            if g.key not in weights:
                weights[g.key] = mh_weights(g)
            w = weights[g.key]
            out.append(Cell(g, algo, design, dyn, two_tap_coef(design, w.lam2), w))
        elif algo == "push_sum":
            out.append(Cell(g, algo, algo, dyn, (1.0, 0.0, 0.0), None))
        else:
            raise ValueError(f"no reference for algorithm {algo!r}")
    return out
