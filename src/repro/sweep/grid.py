"""Experiment-grid construction: topology ensembles as stacked arrays.

The paper's headline results are *ensemble* claims — Theorems 2-3 bound the
averaging-time gain over families of graphs, and Figs. 3-4 average hundreds
of random-geometric draws per network size. A sweep cell is one

    (topology family, size, graph draw) x (theta design) x (alpha)

configuration; this module materializes the full grid as stacked arrays the
batched engine consumes in one jitted program:

* ``ws``    (G, Nmax, Nmax) — the Metropolis-Hastings weight matrix of every
  cell, zero-padded to the largest network in the grid. Zero padding is
  exact: padded nodes start at 0, receive 0 from W and from both taps, and
  are masked out of the MSE reduction.
* ``x0``    (G, Nmax, F)    — F initial-condition columns per cell (paper
  Section IV inits: one deterministic Slope column, then Spike columns at
  random nodes, or i.i.d. Gaussians).
* ``coefs`` (G, 3)          — the fused-round coefficients
  (1 - alpha + alpha*theta3, alpha*theta2, alpha*theta1); memoryless cells
  are the degenerate row (1, 0, 0).
* ``mask`` / ``node_counts`` — per-cell valid-node indicators for padded
  reductions.

Graph draws are shared across the theta/alpha cells of the same (family,
size, draw) triple — gain ratios (Fig. 4) then compare identical ensembles.

**Sparse layout** (``SweepSpec(layout="sparse")``): cells store the canonical
edge list + edge/diagonal weights instead of ``ws`` — O(E) per cell instead
of O(N^2) — and the engine runs the segment-sum round primitive, which is
what makes power-law sweeps at N = 1e5-1e6 fit on one host. Cells with
n <= ``SPARSE_EXACT_SPECTRUM_CUTOFF`` densify *for metadata only* (exact
eigvalsh spectrum, identical coefficients to the dense layout — the
equivalence suite's bit-level anchor); larger cells use power-iteration
extremes and a surrogate spectrum (``_surrogate_spectrum``) for the alpha*,
phi3 and polynomial-filter designs. ``layout="auto"`` picks sparse as soon
as the grid's largest size crosses the cutoff.
"""
from __future__ import annotations

import dataclasses
import inspect
import math
from typing import Callable

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import accel, algorithms, dynamics, metrics, topology, weights
from repro.core.accel import Theta

__all__ = [
    "SweepSpec",
    "ConfigMeta",
    "Ensemble",
    "RoundMasks",
    "build_ensemble",
    "build_round_masks",
    "merge_ensembles",
    "THETA_DESIGNS",
]

# Named predictor designs. ``None`` marks the memoryless baseline
# x(t+1) = W x(t) (alpha = 0), kept in-grid so gains come from one run.
THETA_DESIGNS: dict[str, Callable[[], Theta] | None] = {
    "memoryless": None,
    "ls": accel.theta_ls,
    "asymptotic": lambda: accel.theta_asymptotic(0.5),
}


# Above this size the sparse layout stops densifying for metadata (no exact
# eigvalsh) and "auto" stops choosing the dense layout at all.
SPARSE_EXACT_SPECTRUM_CUTOFF = 1024
SURROGATE_SPECTRUM_POINTS = 64


def _near_square(n: int) -> tuple[int, int]:
    rows = max(int(math.isqrt(n)), 1)
    while n % rows:
        rows -= 1
    return rows, n // rows


def _parse_family(family: str) -> tuple[str, list[str]]:
    """Family specs parse like dynamics specs: ``"ba"`` or ``"ba:5"``."""
    parts = str(family).split(":")
    return parts[0], parts[1:]


def _build_graph(family: str, n: int, rng: np.random.Generator) -> topology.Graph:
    fam, fargs = _parse_family(family)
    if fam == "chain":
        return topology.chain(n)
    if fam == "ring":
        return topology.ring(n)
    if fam == "grid2d":
        return topology.grid2d(*_near_square(n))
    if fam == "torus2d":
        return topology.torus2d(*_near_square(n))
    if fam == "rgg":
        return topology.random_geometric(n, rng)
    if fam == "ba":
        # densified sparse build: both layouts consume identical rng draws,
        # so dense<->sparse equivalence holds on power-law graphs too
        m = int(fargs[0]) if fargs else 3
        return topology.barabasi_albert(n, m, rng).to_dense()
    if fam == "erdos_renyi":
        p = min(1.0, 2.0 * math.log(max(n, 2)) / n)
        for _ in range(200):
            g = topology.erdos_renyi(n, p, rng)
            if topology.is_connected(g.adjacency):
                return g
        raise RuntimeError(f"could not draw a connected G({n}, {p:.3f})")
    if fam == "directed":
        p_extra = float(fargs[0]) if fargs else 0.15
        return topology.random_digraph(n, rng, p_extra=p_extra)
    raise ValueError(
        f"unknown topology family {family!r} (have chain/ring/grid2d/"
        f"torus2d/rgg/ba[:m]/erdos_renyi/directed[:p_extra])")


def _build_sparse_graph(
    family: str, n: int, rng: np.random.Generator
) -> topology.SparseGraph:
    """Edge-list twin of ``_build_graph``; identical rng consumption per draw."""
    fam, fargs = _parse_family(family)
    if fam == "chain":
        return topology.sparse_chain(n)
    if fam == "ring":
        return topology.sparse_ring(n)
    if fam == "grid2d":
        return topology.sparse_grid2d(*_near_square(n))
    if fam == "torus2d":
        return topology.sparse_torus2d(*_near_square(n))
    if fam == "rgg":
        return topology.random_geometric_sparse(n, rng)
    if fam == "ba":
        m = int(fargs[0]) if fargs else 3
        return topology.barabasi_albert(n, m, rng)
    if fam == "erdos_renyi":
        if n > SPARSE_EXACT_SPECTRUM_CUTOFF:
            # O(E) geometric-skip sampler (never touches an (N, N) coin
            # matrix). Its rng consumption differs from the dense sampler's,
            # so CRN coupling across layouts holds only below the cutoff —
            # where this branch densifies anyway.
            p = min(1.0, 2.0 * math.log(max(n, 2)) / n)
            return topology.erdos_renyi_sparse(n, p, rng)
        return topology.SparseGraph.from_graph(_build_graph(family, n, rng))
    if fam == "directed":
        raise ValueError(
            "the 'directed' family is dense-only (its receiver/push weight "
            "builders and complex spectrum metadata need the full matrix); "
            "use layout='dense'")
    raise ValueError(
        f"unknown topology family {family!r} (have chain/ring/grid2d/"
        f"torus2d/rgg/ba[:m]/erdos_renyi/directed[:p_extra])")


def _surrogate_spectrum(
    lam2: float, lam_n: float, k: int = SURROGATE_SPECTRUM_POINTS
) -> np.ndarray:
    """Stand-in spectrum for cells too large to eigensolve.

    Power-iteration extremes, a uniform fill between them, and the trivial
    eigenvalue 1 — sorted ascending like ``eigvalsh``. The consumers
    (alpha*, ``phi3_eigenvalues`` caps, the polynomial-filter Vandermonde
    design) only need the support interval [lam_N, lam_2] plus the top
    eigenvalue, all of which the surrogate carries exactly.
    """
    return np.concatenate([np.linspace(lam_n, lam2, k), [1.0]])


def _design_params(algo, th, al, lam2):
    """design_params dispatch: lam2-aware (adaptive family) or classic 2-arg.

    Aux-carrying algorithms seed their in-scan estimator from the cell's
    nominal lambda_2, so their ``design_params`` takes it as a keyword; the
    original two-argument contract keeps working unchanged.
    """
    try:
        takes = "lam2" in inspect.signature(algo.design_params).parameters
    except (TypeError, ValueError):
        takes = False
    if takes:
        return algo.design_params(th, al, lam2=lam2)
    return algo.design_params(th, al)


def _sparse_tick_rho(algo, lam2, rho_mem, vals, edges, n):
    """tick_rho for a non-densifiable cell; 4-arg fallback for old overrides."""
    try:
        params = inspect.signature(algo.tick_rho).parameters.values()
        takes_edges = any(p.name == "edges" or p.kind is p.VAR_KEYWORD
                          for p in params)
    except (TypeError, ValueError):
        takes_edges = False
    if takes_edges:
        return algo.tick_rho(lam2, rho_mem, None, vals, edges=edges, num_nodes=n)
    return algo.tick_rho(lam2, rho_mem, None, vals)


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """Declarative sweep grid (see module docstring for the cell structure)."""

    topologies: tuple[str, ...] = ("chain", "grid2d", "rgg")
    sizes: tuple[int, ...] = (16, 36, 64)
    designs: tuple[str, ...] = ("memoryless", "asymptotic")
    alphas: tuple[float, ...] | None = None   # None -> alpha*(lambda_2) per cell
    graph_trials: int = 1                     # draws per (family, size); random families only
    num_trials: int = 4                       # F: initial conditions per cell
    init: str = "paper"                       # "paper" (slope+spikes) | "gaussian"
    seed: int = 0
    dynamics: tuple[str, ...] = ("static",)   # topology schedules (core.dynamics)
    algorithms: tuple[str, ...] = ("accel",)  # registry specs (core.algorithms)
    layout: str = "auto"                      # "dense" | "sparse" | "auto"

    def __post_init__(self):
        for d in self.designs:
            if d not in THETA_DESIGNS:
                raise ValueError(f"unknown design {d!r} (have {sorted(THETA_DESIGNS)})")
        for s in self.dynamics:
            dynamics.parse_dynamics(s)        # raises on malformed schedules
        for a in self.algorithms:
            algorithms.get_algorithm(a)       # raises on unknown algorithms
        if self.layout not in ("dense", "sparse", "auto"):
            raise ValueError(
                f"unknown layout {self.layout!r} (have dense/sparse/auto)")

    @property
    def resolved_layout(self) -> str:
        """"auto" -> sparse once any size crosses the dense cutoff."""
        if self.layout != "auto":
            return self.layout
        return ("sparse" if max(self.sizes) > SPARSE_EXACT_SPECTRUM_CUTOFF
                else "dense")


@dataclasses.dataclass(frozen=True)
class ConfigMeta:
    """Host-side metadata for one sweep cell (one row of the stacked arrays)."""

    topology: str
    n: int
    graph_index: int
    design: str
    theta: Theta | None
    alpha: float
    lam2: float
    rho_memoryless: float      # rho(W - J)
    psi: float                 # spectral gap 1 - rho(W - J) (Theorem 2's Psi)
    rho_accel: float           # per-tick contraction of this cell's algorithm
    dynamics: str = "static"   # topology schedule (core.dynamics format)
    algorithm: str = "accel"   # registry spec (core.algorithms format)

    @property
    def gain_asym(self) -> float:
        """tau(W)/tau(accel) — Theorem 3's asymptotic processing gain."""
        if self.rho_accel <= 0.0 or self.rho_memoryless <= 0.0:
            return float("inf")
        return metrics.processing_gain(self.rho_memoryless, self.rho_accel)


@dataclasses.dataclass(frozen=True)
class Ensemble:
    """The stacked grid (see module docstring). Arrays are numpy fp32/fp64.

    Exactly one of the two weight storages is populated: dense grids carry
    ``ws``; sparse grids carry ``edges``/``edge_w``/``diag_w``/``edge_counts``
    (``ws`` is None) — the canonical edge list of every cell padded to the
    grid's largest edge count. Padded edge slots have weight 0 and endpoints
    (0, 0), so they are inert under both the round primitive and the
    mass-preserving mask rule; padded diagonal entries are 0 on nodes whose
    state is pinned at 0 by the init padding.
    """

    ws: np.ndarray | None      # (G, Nmax, Nmax) per-cell base matrices (dense)
    x0: np.ndarray             # (G, Nmax, F)
    coefs: np.ndarray          # (G, C) per-cell algorithm parameter rows
    node_counts: np.ndarray    # (G,) int
    configs: tuple[ConfigMeta, ...]
    algos: tuple[tuple[str, int, int], ...] = ()   # (spec, start, stop) partitions
    edges: np.ndarray | None = None        # (G, Emax, 2) int32, canonical i < j
    edge_w: np.ndarray | None = None       # (G, Emax) f32 base edge weights
    diag_w: np.ndarray | None = None       # (G, Nmax) f32 base diagonal
    edge_counts: np.ndarray | None = None  # (G,) int true edge counts
    # (G, Emax) reverse-orientation weights W[j, i] per canonical (i, j);
    # None when every cell's base is symmetric (push-sum-family cells make
    # it real, symmetric cells then carry a copy of edge_w)
    edge_w_rev: np.ndarray | None = None

    @property
    def is_sparse(self) -> bool:
        return self.ws is None

    @property
    def num_configs(self) -> int:
        return self.x0.shape[0]

    def edge_index(self, i: int) -> np.ndarray:
        """Cell i's canonical (E_i, 2) edge list, layout-independent.

        Both layouts yield the identical array for the same graph (the sparse
        builder stores exactly the ordering ``dynamics.edge_index`` recovers
        from a dense matrix), which is what keeps RoundMasks schedules CRN-
        coupled across layouts.
        """
        if self.is_sparse:
            return np.asarray(self.edges[i, : int(self.edge_counts[i])])
        return dynamics.edge_index(self.ws[i])

    @property
    def layout(self) -> tuple[tuple[str, int, int], ...]:
        """Algorithm partitions along G; () normalizes to one accel partition.

        Cells are grouped contiguously by algorithm (build_ensemble iterates
        the algorithm axis outermost) so the engine can give each partition
        its own carry structure and round body inside ONE jitted scan.
        """
        if self.algos:
            return self.algos
        return (("accel", 0, self.num_configs),)

    @property
    def n_max(self) -> int:
        return self.x0.shape[1]

    def mask(self) -> np.ndarray:
        """(G, Nmax) 1.0 on real nodes, 0.0 on padding."""
        idx = np.arange(self.n_max)[None, :]
        return (idx < self.node_counts[:, None]).astype(np.float32)


def merge_ensembles(*ensembles: Ensemble) -> Ensemble:
    """Concatenate grids along G, re-padding to the largest Nmax.

    Lets callers combine specs with per-family size ranges (e.g. Fig. 3's
    RGG sizes with Fig. 4's chain sizes) into ONE engine run. Trial counts
    (F) must match across the inputs.
    """
    if not ensembles:
        raise ValueError("merge_ensembles needs at least one ensemble")
    fs = {e.x0.shape[2] for e in ensembles}
    if len(fs) > 1:
        raise ValueError(f"trial-axis mismatch across ensembles: {sorted(fs)}")
    if len({e.is_sparse for e in ensembles}) > 1:
        raise ValueError("cannot merge dense and sparse ensembles; rebuild "
                         "with a single SweepSpec layout")
    n_max = max(e.n_max for e in ensembles)

    def grow(a: np.ndarray, axes: tuple[int, ...]) -> np.ndarray:
        pad = [(0, 0)] * a.ndim
        for ax in axes:
            pad[ax] = (0, n_max - a.shape[ax])
        return np.pad(a, pad)

    c_max = max(e.coefs.shape[1] for e in ensembles)
    layout, off = [], 0
    for e in ensembles:
        layout.extend((name, s + off, t + off) for name, s, t in e.layout)
        off += e.num_configs

    if ensembles[0].is_sparse:
        e_max = max(e.edges.shape[1] for e in ensembles)

        def grow_edges(a: np.ndarray) -> np.ndarray:
            pad = [(0, 0), (0, e_max - a.shape[1])] + [(0, 0)] * (a.ndim - 2)
            return np.pad(a, pad)

        if any(e.edge_w_rev is not None for e in ensembles):
            rev_cat = np.concatenate([
                grow_edges(e.edge_w if e.edge_w_rev is None else e.edge_w_rev)
                for e in ensembles
            ])
        else:
            rev_cat = None
        weight_arrays = dict(
            ws=None,
            edges=np.concatenate([grow_edges(e.edges) for e in ensembles]),
            edge_w=np.concatenate([grow_edges(e.edge_w) for e in ensembles]),
            diag_w=np.concatenate([grow(e.diag_w, (1,)) for e in ensembles]),
            edge_counts=np.concatenate([e.edge_counts for e in ensembles]),
            edge_w_rev=rev_cat,
        )
    else:
        weight_arrays = dict(
            ws=np.concatenate([grow(e.ws, (1, 2)) for e in ensembles]))

    return Ensemble(
        x0=np.concatenate([grow(e.x0, (1,)) for e in ensembles]),
        coefs=np.concatenate(
            [np.pad(e.coefs, ((0, 0), (0, c_max - e.coefs.shape[1])))
             for e in ensembles]),
        node_counts=np.concatenate([e.node_counts for e in ensembles]),
        configs=tuple(c for e in ensembles for c in e.configs),
        algos=tuple(layout),
        **weight_arrays,
    )


def _init_block(g: topology.Graph, f: int, kind: str, rng: np.random.Generator) -> np.ndarray:
    n = g.n
    if kind == "gaussian":
        return rng.standard_normal((n, f))
    cols = [metrics.slope_init(g.coords, n)]
    for _ in range(f - 1):
        cols.append(metrics.spike_init(n, node=int(rng.integers(0, n))))
    return np.stack(cols[:f], axis=1)


@dataclasses.dataclass
class _GraphDraw:
    """One graph draw: spectra + whichever weight representation(s) exist.

    ``w`` is the dense base weight matrix — present in the dense layout AND
    for sparse cells small enough to densify for metadata (keeping their
    spectra/coefficients bit-identical to the dense layout). For larger
    sparse cells ``w`` is None and ``vals`` is the surrogate spectrum.
    """

    family: str
    gi: int
    g: object                      # Graph | SparseGraph (.n, .coords for inits)
    w: np.ndarray | None
    vals: np.ndarray
    lam2: float
    rho_mem: float
    edges: np.ndarray | None = None
    edge_w: np.ndarray | None = None
    diag_w: np.ndarray | None = None


def _draw_dense(family: str, gi: int, n: int, rng) -> _GraphDraw:
    g = _build_graph(family, n, rng)
    if isinstance(g, topology.DiGraph):
        # Directed cells: the stored base is the naive row-stochastic
        # receiver matrix (what ``memoryless`` iterates — and provably
        # drifts to the Perron-weighted mixture on). Its spectrum is
        # complex, so the contraction metadata uses the second-largest
        # eigenvalue MODULUS and a surrogate real spectrum on that
        # interval; the push-sum family rebuilds its own column-stochastic
        # base from the same support via ``base_matrix``.
        w = weights.receiver_weights(g)
        ev = np.sort(np.abs(np.linalg.eigvals(w)))
        rho_mem = float(ev[-2])
        vals = _surrogate_spectrum(rho_mem, -rho_mem)
        return _GraphDraw(family, gi, g, w, vals,
                          lam2=rho_mem, rho_mem=rho_mem)
    w = weights.metropolis_hastings(g)
    vals = np.linalg.eigvalsh(w)
    if abs(vals[0]) > vals[-2]:
        # Theorem 1 needs |lambda_N| <= lambda_2; lazy map fixes it.
        w = weights.lazy(w)
        vals = np.linalg.eigvalsh(w)
    return _GraphDraw(family, gi, g, w, vals,
                      lam2=float(vals[-2]),
                      rho_mem=float(max(abs(vals[0]), abs(vals[-2]))))


def _draw_sparse(family: str, gi: int, n: int, rng) -> _GraphDraw:
    sg = _build_sparse_graph(family, n, rng)
    if sg.n <= SPARSE_EXACT_SPECTRUM_CUTOFF:
        # densify for METADATA only: the exact spectrum, lazy decision and
        # edge weights then match the dense layout bit for bit
        w = weights.metropolis_hastings(sg.to_dense())
        vals = np.linalg.eigvalsh(w)
        if abs(vals[0]) > vals[-2]:
            w = weights.lazy(w)
            vals = np.linalg.eigvalsh(w)
        ew = w[sg.edges[:, 0], sg.edges[:, 1]].copy()
        dw = np.diag(w).copy()
        return _GraphDraw(family, gi, sg, w, vals,
                          lam2=float(vals[-2]),
                          rho_mem=float(max(abs(vals[0]), abs(vals[-2]))),
                          edges=sg.edges, edge_w=ew, diag_w=dw)
    ew, dw = weights.metropolis_hastings_edges(sg)
    lam2, lam_n = weights.lambda_extremes_sparse(sg.edges, ew, dw)
    if abs(lam_n) > lam2:
        # lazy map in edge space; eigenvalues transform affinely
        ew, dw = weights.lazy_edges(ew, dw)
        lam2, lam_n = 0.5 * (1.0 + lam2), 0.5 * (1.0 + lam_n)
    vals = _surrogate_spectrum(lam2, lam_n)
    return _GraphDraw(family, gi, sg, None, vals,
                      lam2=float(lam2),
                      rho_mem=float(max(abs(lam_n), abs(lam2))),
                      edges=sg.edges, edge_w=ew, diag_w=dw)


def _base_edge_arrays(
    algo, d: _GraphDraw
) -> tuple[np.ndarray, np.ndarray | None, np.ndarray]:
    """(edge_w, edge_w_rev, diag_w) of this algorithm's BASE matrix, sparse.

    ``edge_w_rev`` is None for symmetric bases (one weight serves both
    orientations of a canonical edge); asymmetric bases (``symmetric_base``
    False — the column-stochastic push-sum family) carry W[j, i] per
    canonical (i, j) so the engine's directed-arrays round sees both.
    """
    if d.w is not None:
        bm = algo.base_matrix(d.w)
        fwd = bm[d.edges[:, 0], d.edges[:, 1]].copy()
        rev = None if algo.symmetric_base \
            else bm[d.edges[:, 1], d.edges[:, 0]].copy()
        return fwd, rev, np.diag(bm).copy()
    out = algo.base_edge_weights(d.edges, d.edge_w, d.diag_w, d.g.n)
    if len(out) == 2:                      # symmetric-base (edge_w, diag_w)
        return out[0], None, out[1]
    return out                             # (fwd, rev, diag)


def build_ensemble(spec: SweepSpec) -> Ensemble:
    """Materialize the sweep grid of ``spec`` as stacked padded arrays."""
    rng = np.random.default_rng(spec.seed)
    random_families = {"rgg", "erdos_renyi", "ba", "directed"}
    sparse = spec.resolved_layout == "sparse"

    graphs: list[_GraphDraw] = []
    for family in spec.topologies:
        fam = _parse_family(family)[0]
        for n in spec.sizes:
            draws = spec.graph_trials if fam in random_families else 1
            for gi in range(draws):
                graphs.append((_draw_sparse if sparse else _draw_dense)(
                    family, gi, n, rng))

    if not graphs:
        raise ValueError("empty sweep grid")
    n_max = max(d.g.n for d in graphs)
    e_max = max(1, max(len(d.edges) for d in graphs)) if sparse else 0
    f = spec.num_trials

    # one init block per graph, drawn in graph order and shared across the
    # design/algorithm/dynamics cells of that graph (common random numbers)
    inits = [_init_block(d.g, f, spec.init, rng) for d in graphs]

    ws, x0s, coefs, counts, metas, layout = [], [], [], [], [], []
    edges_l, edge_w_l, diag_w_l, e_counts = [], [], [], []
    edge_w_rev_l: list[np.ndarray | None] = []

    def add_cell(base, x0, n, params, meta):
        if sparse:
            base_ew, base_rev, base_dw, eix = base
            e = len(eix)
            ep = np.zeros((e_max, 2), dtype=np.int32)
            ep[:e] = eix
            ewp = np.zeros(e_max, dtype=np.float32)
            ewp[:e] = base_ew
            dwp = np.zeros(n_max, dtype=np.float32)
            dwp[:n] = base_dw
            edges_l.append(ep)
            edge_w_l.append(ewp)
            diag_w_l.append(dwp)
            e_counts.append(e)
            if base_rev is None:
                edge_w_rev_l.append(None)
            else:
                rvp = np.zeros(e_max, dtype=np.float32)
                rvp[:e] = base_rev
                edge_w_rev_l.append(rvp)
        else:
            wp = np.zeros((n_max, n_max), dtype=np.float32)
            wp[:n, :n] = base
            ws.append(wp)
        xp0 = np.zeros((n_max, f), dtype=np.float32)
        xp0[:n] = x0
        x0s.append(xp0)
        coefs.append(np.asarray(params, dtype=np.float32))
        counts.append(n)
        metas.append(meta)

    # algorithm axis OUTERMOST: each algorithm's cells form one contiguous
    # G partition (Ensemble.layout), which is what lets the engine scan a
    # mixed-algorithm grid with per-partition carries in one jitted program.
    for algo_spec in spec.algorithms:
        algo = algorithms.get_algorithm(algo_spec)
        start = len(metas)
        for d, x0 in zip(graphs, inits):
            n, vals, lam2, rho_mem = d.g.n, d.vals, d.lam2, d.rho_mem
            if sparse:
                base = (*_base_edge_arrays(algo, d), d.edges)
            else:
                base = algo.base_matrix(d.w)
            if algo.uses_theta:
                for design in spec.designs:
                    maker = THETA_DESIGNS[design]
                    if maker is None:
                        cells = [(None, 0.0)]
                    else:
                        th = maker()
                        alphas = spec.alphas if spec.alphas is not None else (
                            accel.alpha_star(lam2, th),
                        )
                        cells = [(th, float(al)) for al in alphas]
                    for th, al in cells:
                        params = _design_params(algo, th, al, lam2)
                        if th is None:
                            rho_acc = rho_mem
                        else:
                            # exact rho(Phi3[alpha] - J) from the spectrum of W
                            # (equals sqrt(-alpha theta1) only at alpha = alpha*)
                            mus = accel.phi3_eigenvalues(np.sort(vals)[:-1], al, th)
                            rho_acc = float(max(np.abs(mus).max(), abs(al * th.t1)))
                        for dyn in spec.dynamics:
                            add_cell(base, x0, n, params, ConfigMeta(
                                topology=d.family, n=n, graph_index=d.gi,
                                design=design, theta=th, alpha=al, lam2=lam2,
                                rho_memoryless=rho_mem, psi=1.0 - rho_mem,
                                rho_accel=rho_acc, dynamics=dyn,
                                algorithm=algo.spec,
                            ))
            else:
                # theta-free algorithms: one cell per (graph, dynamics) —
                # the design axis does not apply (mirrors how the memoryless
                # design ignores the alpha grid)
                params = algo.cell_params(d.w, vals)
                if d.w is None:
                    rho_tick = _sparse_tick_rho(algo, lam2, rho_mem, vals,
                                                d.edges, n)
                else:
                    rho_tick = algo.tick_rho(lam2, rho_mem, d.w, vals)
                for dyn in spec.dynamics:
                    add_cell(base, x0, n, params, ConfigMeta(
                        topology=d.family, n=n, graph_index=d.gi,
                        design=algo.spec, theta=None, alpha=0.0, lam2=lam2,
                        rho_memoryless=rho_mem, psi=1.0 - rho_mem,
                        rho_accel=rho_tick, dynamics=dyn, algorithm=algo.spec,
                    ))
        layout.append((algo.spec, start, len(metas)))

    c_max = max(1, max(len(c) for c in coefs))
    if sparse:
        # edge_w_rev stacks only when some cell's base is asymmetric; cells
        # of symmetric-base algorithms then reuse their forward weights so
        # one (G, Emax) array serves the whole grid.
        if any(r is not None for r in edge_w_rev_l):
            rev_stack = np.stack([
                r if r is not None else f
                for r, f in zip(edge_w_rev_l, edge_w_l)
            ])
        else:
            rev_stack = None
        weight_arrays = dict(
            ws=None,
            edges=np.stack(edges_l),
            edge_w=np.stack(edge_w_l),
            diag_w=np.stack(diag_w_l),
            edge_counts=np.asarray(e_counts, dtype=np.int64),
            edge_w_rev=rev_stack,
        )
    else:
        weight_arrays = dict(ws=np.stack(ws))
    return Ensemble(
        x0=np.stack(x0s),
        coefs=np.stack([np.pad(c, (0, c_max - len(c))) for c in coefs]),
        node_counts=np.asarray(counts, dtype=np.int64),
        configs=tuple(metas),
        algos=tuple(layout),
        **weight_arrays,
    )


@dataclasses.dataclass(frozen=True)
class RoundMasks:
    """Compressed per-round edge-activity schedules for a whole grid.

    ``bits[t, g, e]`` = 1 iff edge ``idx[g, e]`` of cell g is up in round t.
    Cells are padded to the grid's largest edge count with index (0, 0) and
    bit 1 — the engine's dense expansion overwrites the diagonal with ones,
    so padded slots are inert. uint8 keeps a (T, G, E) schedule ~32x smaller
    than the per-round W matrices it replaces.
    """

    bits: np.ndarray           # (T, G, Emax) uint8, 1 = link up
    idx: np.ndarray            # (G, Emax, 2) int32 edge endpoints (i < j)

    @property
    def num_rounds(self) -> int:
        return self.bits.shape[0]


def build_round_masks(ens: Ensemble, num_iters: int, seed: int = 0) -> RoundMasks | None:
    """Sample every cell's per-round edge schedule for ``num_iters`` rounds.

    Returns None when every cell is static AND no cell's algorithm needs a
    schedule (the engine then takes the cheaper mask-free scan). Sampling is
    keyed by the *graph*, not the cell (``dynamics.graph_rng``): cells
    sharing a (family, size, draw) triple — the same graph crossed with
    different designs, algorithms, or failure probabilities — consume
    identical uniforms, so failure sets are common-random-number coupled and
    nested across p. Schedule-bearing algorithms (``async_pairwise``) then
    post-process the dynamics draw through ``schedule_bits`` (the woken-edge
    one-hot ANDed with the failure bits) using the same stream.

    Host spans on the profiler's clock: ``sweep.masks`` around the call,
    ``sweep.masks.draw`` around each sampled cell's draw (the rest of
    ``sweep.masks`` is the all-ones fill and the copies into ``bits``).
    """
    with TraceAnnotation("sweep.masks"):
        specs = [dynamics.parse_dynamics(c.dynamics) for c in ens.configs]
        algos = [algorithms.get_algorithm(c.algorithm) for c in ens.configs]
        if all(s.is_static for s in specs) and not any(a.needs_schedule for a in algos):
            return None
        g = ens.num_configs
        idx_list = [ens.edge_index(i) for i in range(g)]
        e_max = max(1, max(len(ix) for ix in idx_list))
        bits = np.ones((num_iters, g, e_max), dtype=np.uint8)
        idx = np.zeros((g, e_max, 2), dtype=np.int32)
        for i, (c, s, a, ix) in enumerate(zip(ens.configs, specs, algos, idx_list)):
            e = len(ix)
            idx[i, :e] = ix
            if s.is_static and not a.needs_schedule:
                continue                       # bits already all-ones
            with TraceAnnotation("sweep.masks.draw"):
                rng = dynamics.graph_rng(seed, (c.topology, c.n, c.graph_index))
                cell_bits = dynamics.sample_edge_bits(s, num_iters, ix, c.n, rng)
                cell_bits = a.schedule_bits(cell_bits, ix, c.n, rng)
            bits[:, i, :e] = cell_bits
        return RoundMasks(bits=bits, idx=idx)
