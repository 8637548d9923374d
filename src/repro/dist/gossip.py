"""Pod-level gossip fabric: the paper's optimization applied to pod graphs.

A ``PodFabric`` is the static description of cross-pod consensus for P pods
on a named topology: the Metropolis-Hastings weight matrix W, its spectral
gap, and the paper-optimal two-tap parameters (Theorem 1) for it. The
elastic runtime (``repro.runtime.elastic``) rebuilds a fabric on every graph
edit; the sync-cost model (``benchmarks/sync_cost.py``) reads round counts
off it.

The SPMD execution half lives here too: ``gossip`` / ``accel_gossip`` run a
consensus round *inside* shard_map over a mesh axis (ppermute along the
fabric's graph edges, the accelerated variant carrying the ``(x, x_prev)``
taps across rounds), and ``distributed_lambda2`` is Algorithm 1 run in-mesh —
power iteration with periodic max-consensus normalization, mirroring the
host-side ``repro.core.doi`` network simulation op for op.
``adaptive_accel_gossip`` composes the two: periodic in-mesh re-estimation
feeding a traced Theorem-1 re-solve between gossip segments — the shard_map
mirror of the registry's ``accel_adapt`` time-varying coefficient stream.

The edge structure of W is lowered to a static list of permutations (greedy
matching decomposition of the directed edge set, one ppermute each); per-node
weights are looked up by ``axis_index``, so one code path serves any fabric
topology.
"""
from __future__ import annotations

import dataclasses
import math
from fractions import Fraction

import jax
import jax.numpy as jnp
import numpy as np

from ..core import accel, topology, weights
from ..core.accel import Theta

__all__ = [
    "PodFabric",
    "make_fabric",
    "gossip",
    "accel_gossip",
    "adaptive_accel_gossip",
    "pairwise_gossip",
    "push_sum_gossip",
    "algorithm_gossip",
    "distributed_lambda2",
    "default_doi_iters",
    "edge_permutations",
    "fabric_matvec",
]


@dataclasses.dataclass(frozen=True)
class PodFabric:
    """Pod graph + paper-optimal consensus parameters for it."""

    w: np.ndarray            # (P, P) Metropolis-Hastings weights
    topology: str
    theta: Theta
    lambda2: float           # lambda_2(W)
    alpha: float             # alpha* (Theorem 1)
    rho_accel: float         # rho(Phi3[alpha*] - J)
    rho_memoryless: float    # rho(W - J)

    @property
    def num_pods(self) -> int:
        return self.w.shape[0]

    def _rounds(self, rho: float, eps: float) -> int:
        """First R with rho^R <= eps (1 when the graph mixes exactly)."""
        if rho <= 0.0:
            return 1
        if rho >= 1.0:
            raise ValueError(f"non-contracting fabric (rho={rho})")
        return max(1, math.ceil(math.log(eps) / math.log(rho)))

    def rounds_for(self, eps: float) -> int:
        """Accelerated rounds to reach relative consensus error eps."""
        return self._rounds(self.rho_accel, eps)

    def rounds_for_memoryless(self, eps: float) -> int:
        """Memoryless x(t+1) = W x(t) rounds for the same eps."""
        return self._rounds(self.rho_memoryless, eps)


def _pod_graph(p: int, kind: str) -> topology.Graph:
    if p < 1:
        raise ValueError("need at least one pod")
    if p == 1:
        return topology.Graph(adjacency=np.zeros((1, 1)), name=kind)
    if p == 2:
        return topology.chain(2)
    if kind == "ring":
        return topology.ring(p)
    if kind == "chain":
        return topology.chain(p)
    if kind == "torus":
        side = int(round(math.sqrt(p)))
        if side * side != p:
            raise ValueError(f"torus fabric needs a square pod count, got {p}")
        return topology.torus2d(side)
    raise ValueError(f"unknown fabric topology {kind!r}")


def make_fabric(p: int, kind: str = "ring", theta: Theta | None = None,
                lambda2: float | None = None) -> PodFabric:
    """Build the fabric for ``p`` pods: W, lambda_2, alpha*, rho*.

    Dense O(P^3) eigensolve — P is the pod count (tens), not the node count.
    Passing ``lambda2`` skips the eigensolve and re-solves Theorem 1 from a
    supplied estimate (the O(K) in-mesh ``distributed_lambda2`` / Algorithm 1),
    which is how ``ElasticFabric.resize`` re-optimizes an irregular fabric
    without gathering W; it assumes |lambda_P| <= lambda_2 (the lazy map or a
    regular topology guarantees it), so rho_memoryless = lambda_2 there.
    """
    theta = theta or accel.theta_asymptotic(0.5)
    g = _pod_graph(p, kind)
    if p == 1:
        w = np.ones((1, 1))
        return PodFabric(w=w, topology=kind, theta=theta, lambda2=0.0,
                         alpha=0.0, rho_accel=0.0, rho_memoryless=0.0)
    w = weights.metropolis_hastings(g)
    if lambda2 is None:
        vals = np.linalg.eigvalsh(w)
        if abs(vals[0]) > vals[-2]:
            # Theorem 1 needs |lambda_P| <= lambda_2; the lazy map guarantees it.
            w = weights.lazy(w)
            vals = np.linalg.eigvalsh(w)
        lam2 = float(vals[-2])
        rho_mem = float(max(abs(vals[0]), abs(lam2)))
    else:
        lam2 = float(lambda2)
        rho_mem = lam2
    if lam2 <= 0.0:
        # complete-graph-like mixing: one round is exact, nothing to optimize
        return PodFabric(w=w, topology=kind, theta=theta, lambda2=max(lam2, 0.0),
                         alpha=0.0, rho_accel=0.0, rho_memoryless=rho_mem)
    a_star = accel.alpha_star(lam2, theta)
    return PodFabric(
        w=w, topology=kind, theta=theta, lambda2=lam2, alpha=a_star,
        rho_accel=accel.rho_accel(lam2, theta), rho_memoryless=rho_mem,
    )


# ---------------------------------------------------------------------------
# SPMD execution half: consensus rounds inside shard_map over a mesh axis.
# ---------------------------------------------------------------------------

def edge_permutations(w: np.ndarray) -> list[tuple[tuple[tuple[int, int], ...], np.ndarray]]:
    """Decompose the off-diagonal support of W into ppermute-able matchings.

    Returns ``[(perm, wvec), ...]`` where ``perm`` is a list of (src, dst)
    device pairs with each src/dst used at most once (a valid ``ppermute``
    argument) and ``wvec[dst] = W[dst, src]`` scales what dst receives. The
    greedy matching decomposition needs at most ~max-degree passes and is
    deterministic (edges visited in sorted order), so the lowered program is
    stable across hosts.
    """
    p = w.shape[0]
    remaining = [
        (s, d) for s in range(p) for d in range(p)
        if s != d and w[d, s] != 0.0
    ]
    perms = []
    while remaining:
        used_src: set[int] = set()
        used_dst: set[int] = set()
        perm = []
        for s, d in remaining:
            if s not in used_src and d not in used_dst:
                perm.append((s, d))
                used_src.add(s)
                used_dst.add(d)
        remaining = [e for e in remaining if e not in set(perm)]
        wvec = np.zeros(p, dtype=w.dtype)
        for s, d in perm:
            wvec[d] = w[d, s]
        perms.append((tuple(perm), wvec))
    return perms


def _fma(a: float, b: float, c: float) -> float:
    """Correctly-rounded fused multiply-add a*b + c (one rounding).

    Exact rational arithmetic then round-to-nearest-even via float(Fraction):
    the portable stand-in for ``math.fma`` (3.13+) at the tiny sizes the
    bit-for-bit tests need (P <= 8 components).
    """
    return float(Fraction(a) * Fraction(b) + Fraction(c))


def _sender_weights(perm, w: np.ndarray) -> np.ndarray:
    """wsend[s] = W[d, s] for each (src s, dst d) of one matching, else 0."""
    wsend = np.zeros(w.shape[0], dtype=w.dtype)
    for s, d in perm:
        wsend[s] = w[d, s]
    return wsend


def fabric_matvec(w: np.ndarray, contraction: str = "fma"):
    """Host mirror of ``distributed_lambda2``'s in-mesh matvec — the
    bit-for-bit reference for Algorithm 1 agreement tests
    (``doi.estimate_lambda2(..., matvec=fabric_matvec(w))``).

    Each matching delivers the rounded product W[d, s] * v[s] its sender
    computed; the receiver adds them to its own diagonal term in matching
    order. ``contraction`` selects how that diagonal term is combined:

    * ``"fma"`` — as XLA:CPU emits it: the diagonal product fuses into the
      first addition (``fma(W_ii, v_i, p_0)``, one rounding), later terms
      are plain adds. Emulated with exact rational arithmetic, so the host
      trajectory reproduces the jitted SPMD trajectory bit for bit.
    * ``"none"`` — plain mul-then-add (the reference on backends that do not
      contract).
    """
    if contraction not in ("fma", "none"):
        raise ValueError(f"unknown contraction {contraction!r}")
    diag = np.diag(w).copy()
    sends = [(perm, _sender_weights(perm, w)) for perm, _ in edge_permutations(w)]

    def received(v):
        out = []
        for perm, wsend in sends:
            recv = np.zeros_like(v)
            prod = wsend * v
            for s, d in perm:
                recv[d] = prod[s]
            out.append(recv)
        return out

    def mv(v: np.ndarray) -> np.ndarray:
        terms = received(v)
        if not terms:
            return diag * v
        if contraction == "fma":
            out = np.array([_fma(diag[i], v[i], terms[0][i])
                            for i in range(len(v))])
        else:
            out = diag * v + terms[0]
        for recv in terms[1:]:
            out = out + recv
        return out

    return mv


def _neighbor_sum(x_self, payload, axis_name, idx, diag, perms, live=None):
    """x_w[i] = W[i,i] x_self + sum_j W[i,j] payload_j — one exchange tick.

    ``x_self`` is the node's true state (never quantized); ``payload`` is what
    goes on the wire. One ppermute per matching; nodes outside a matching
    receive zeros and carry a zero weight, so the same program serves every
    fabric topology.

    ``live`` (optional, one 0/1 scalar per matching) marks which matchings
    delivered this round. A dead matching's weight returns to the node's own
    state — the mass-preserving re-weighting of ``repro.core.dynamics`` —
    instead of scaling whatever stale/zero payload ppermute produced, so the
    round's effective W stays doubly stochastic and the pod-mean exact.
    """
    out = diag[idx] * x_self
    for k, (perm, wvec) in enumerate(perms):
        recv = jax.lax.ppermute(payload, axis_name, perm)
        w_k = wvec[idx]
        if live is None:
            out = out + w_k * recv
        else:
            out = out + w_k * (live[k] * recv + (1.0 - live[k]) * x_self)
    return out


def _wire_rounds(x, axis_name, fabric, num_rounds, wire, step, drop_mask=None):
    """Shared driver: carries (state, wire error-feedback) across rounds."""
    idx = jax.lax.axis_index(axis_name)
    diag = jnp.asarray(np.diag(fabric.w), x.dtype)
    perms = [(perm, jnp.asarray(wvec, x.dtype))
             for perm, wvec in edge_permutations(fabric.w)]
    if drop_mask is not None:
        drop_mask = jnp.asarray(drop_mask, x.dtype)
        if drop_mask.shape != (num_rounds, len(perms)):
            raise ValueError(
                f"drop_mask shape {drop_mask.shape} != (num_rounds, num_matchings)"
                f" = ({num_rounds}, {len(perms)})"
            )
    err = jnp.zeros_like(x) if wire is not None else None
    carry = None
    for r in range(num_rounds):
        payload = x
        if wire is not None:
            payload, err = wire.encode_decode(x, err)
        live = None if drop_mask is None else drop_mask[r]
        xw = _neighbor_sum(x, payload, axis_name, idx, diag, perms, live)
        x, carry = step(xw, x, carry)
    return x


def gossip(x, axis_name: str, fabric: PodFabric, num_rounds: int, wire=None,
           drop_mask=None):
    """Memoryless consensus x(t+1) = W x(t), run inside shard_map.

    ``x`` is this pod's block (any shape); ``axis_name`` the mesh axis the
    fabric lives on (one device slot per pod). ``num_rounds`` is static —
    read it off ``fabric.rounds_for_memoryless(eps)``. ``wire`` optionally
    compresses the neighbour payload (error feedback carried across rounds).
    ``drop_mask`` (num_rounds, num_matchings), 1 = delivered: failed
    matchings return their weight to the sender's own state (mass-preserving,
    see ``_neighbor_sum``) so consensus degrades gracefully instead of
    averaging stale ppermute data.
    """
    return _wire_rounds(x, axis_name, fabric, num_rounds, wire,
                        lambda xw, x, carry: (xw, None), drop_mask=drop_mask)


def accel_gossip(x, axis_name: str, fabric: PodFabric, num_rounds: int, wire=None,
                 drop_mask=None):
    """The paper's two-tap accelerated recursion (Eq. 4a-4c), in-mesh.

    Carries the ``(x, x_prev)`` taps across rounds; per round one neighbour
    exchange (same wire cost as memoryless gossip) plus two local FMAs:

        x(t+1) = (1 - alpha + alpha theta3) W x(t)
                 + alpha theta2 x(t) + alpha theta1 x(t-1)

    with (alpha*, theta) read off the fabric (Theorem 1). ``num_rounds``
    comes from ``fabric.rounds_for(eps)`` = ceil(log eps / log rho_accel) —
    ~sqrt of the memoryless round count (Theorem 2). ``drop_mask``
    (num_rounds, num_matchings) injects per-round matching failures with the
    same mass-preserving semantics as ``gossip``; alpha* stays the nominal
    one, mirroring what a real deployment can actually compute.
    """
    t = fabric.theta
    a = 1.0 - fabric.alpha + fabric.alpha * t.t3
    b = fabric.alpha * t.t2
    c = fabric.alpha * t.t1

    def step(xw, x, x_prev):
        x_prev = x if x_prev is None else x_prev
        return a * xw + b * x + c * x_prev, x

    return _wire_rounds(x, axis_name, fabric, num_rounds, wire, step,
                        drop_mask=drop_mask)


def adaptive_accel_gossip(x, axis_name: str, fabric: PodFabric, num_rounds: int,
                          resolve_every: int | None = None,
                          doi_iters: int | None = None,
                          normalize_every: int = 10, v_init=None,
                          wire=None, drop_mask=None):
    """Two-tap gossip with periodic in-mesh re-solve of Theorem 1.

    The SPMD mirror of the registry's ``accel_adapt``: before each segment of
    ``resolve_every`` rounds (default: one leading segment covering the whole
    run) the pods run Algorithm 1 *in-mesh* (``distributed_lambda2``) and
    re-solve alpha* from the fresh estimate as traced scalars — the
    one-program analogue of ``ElasticFabric.refresh_lambda2``, with the
    ``(x, x_prev)`` taps carried straight across segment boundaries (the
    recursion never restarts, only its coefficient stream moves).

    The re-solve applies the same one-sided rule as ``accel_adapt``:
    ``lambda_used = max(fabric.lambda2, lambda2_hat)``. Underestimates are
    the catastrophic direction for alpha* (real-root regime) and the finite-K
    power iteration approaches lambda_2 from below, so the fabric's nominal
    value is a floor; a degraded fabric raises the estimate above it.

    ``v_init`` seeds the (P,) DOI probe; None derives a deterministic
    integer-hash probe (no key threading, reproducible across hosts).
    Estimation ticks run on the intact fabric — ``drop_mask``
    (num_rounds, num_matchings) applies to the consensus rounds only,
    modelling the deployment where re-tuning is a slow control-plane sweep
    while per-round losses hit the data path.
    """
    from ..core.algorithms import _probe_block

    t = fabric.theta
    p = fabric.num_pods
    if p == 1 or num_rounds <= 0:
        return x
    if resolve_every is None:
        resolve_every = num_rounds
    if resolve_every < 1:
        raise ValueError(f"resolve_every must be >= 1, got {resolve_every}")
    idx = jax.lax.axis_index(axis_name)
    diag = jnp.asarray(np.diag(fabric.w), x.dtype)
    perms = [(perm, jnp.asarray(wvec, x.dtype))
             for perm, wvec in edge_permutations(fabric.w)]
    if drop_mask is not None:
        drop_mask = jnp.asarray(drop_mask, x.dtype)
        if drop_mask.shape != (num_rounds, len(perms)):
            raise ValueError(
                f"drop_mask shape {drop_mask.shape} != (num_rounds, num_matchings)"
                f" = ({num_rounds}, {len(perms)})"
            )
    if v_init is None:
        v_init = _probe_block(p, 1)[:, 0].astype(np.float64)
    lam_floor = jnp.asarray(min(max(fabric.lambda2, 0.0), 0.999999), x.dtype)
    err = jnp.zeros_like(x) if wire is not None else None
    x_prev = None
    for start in range(0, num_rounds, resolve_every):
        lam_hat = distributed_lambda2(
            axis_name, p, None, num_iters=doi_iters,
            normalize_every=normalize_every, fabric=fabric,
            v_init=v_init, dtype=x.dtype)
        lam_eff = jnp.clip(jnp.maximum(lam_floor, lam_hat), 0.0, 0.999999)
        al = accel.alpha_star_jnp(lam_eff, t)
        a = 1.0 - al + al * t.t3
        b = al * t.t2
        c = al * t.t1
        for r in range(start, min(start + resolve_every, num_rounds)):
            payload = x
            if wire is not None:
                payload, err = wire.encode_decode(x, err)
            live = None if drop_mask is None else drop_mask[r]
            xw = _neighbor_sum(x, payload, axis_name, idx, diag, perms, live)
            xp = x if x_prev is None else x_prev
            x, x_prev = a * xw + b * x + c * xp, x
    return x


def pairwise_gossip(x, axis_name: str, fabric: PodFabric, num_rounds: int,
                    schedule=None, seed: int = 0):
    """Boyd-style asynchronous randomized pairwise gossip, in-mesh.

    One fabric edge wakes per round: the woken pair swaps states over a
    single two-element ppermute and averages, x_i, x_j <- (x_i + x_j)/2;
    every other pod holds its value (the in-mesh mirror of the registry's
    ``async_pairwise`` engine algorithm — one exchange = one round here too).

    ``schedule`` is the host-sampled (num_rounds,) edge-index sequence; None
    samples it from ``dynamics.graph_rng(seed, ...)`` keyed by the fabric
    topology, so the lowered program is reproducible across hosts (the edge
    list, like ``edge_permutations``, is visited in deterministic sorted
    order). The pod mean is conserved exactly in real arithmetic: every
    round's effective matrix is symmetric doubly stochastic.
    """
    from ..core import dynamics

    w = fabric.w
    p = w.shape[0]
    edges = [(i, j) for i in range(p) for j in range(i + 1, p) if w[i, j] != 0.0]
    if not edges:
        return x
    if schedule is None:
        rng = dynamics.graph_rng(seed, ("pairwise", fabric.topology, p))
        schedule = rng.integers(0, len(edges), size=num_rounds)
    schedule = np.asarray(schedule)
    if schedule.shape != (num_rounds,):
        raise ValueError(
            f"schedule shape {schedule.shape} != (num_rounds,) = ({num_rounds},)")
    idx = jax.lax.axis_index(axis_name)
    for r in range(num_rounds):
        i, j = edges[int(schedule[r])]
        recv = jax.lax.ppermute(x, axis_name, [(i, j), (j, i)])
        awake = (idx == i) | (idx == j)
        x = jnp.where(awake, 0.5 * (x + recv), x)
    return x


def push_sum_gossip(x, axis_name: str, fabric: PodFabric, num_rounds: int,
                    drop_mask=None):
    """Kempe-Dobra-Gehrke push-sum over the fabric's support, in-mesh.

    Each pod carries a (value, mass) pair — the value seeded with its block,
    the mass with 1 — and per round both ride the SAME exchanges under the
    column-stochastic push matrix ``weights.push_sum_weights`` built on the
    fabric's support. The returned estimate is the quotient value/mass.

    ``drop_mask`` (num_rounds, num_matchings), 1 = delivered, uses SENDER
    renormalization: a failed matching's share stays in the sending pod's
    own pair (column sums — total value and total mass — survive every
    failure pattern), unlike ``gossip``'s receiver rule which preserves row
    sums. The quotient therefore still converges to the true mean under
    sustained loss, where the memoryless receiver rule drifts.
    """
    pm = weights.push_sum_weights(fabric.w)
    idx = jax.lax.axis_index(axis_name)
    diag = jnp.asarray(np.diag(pm), x.dtype)
    packs = []
    for perm, wvec in edge_permutations(pm):
        svec = np.zeros(pm.shape[0], dtype=pm.dtype)
        for s, d in perm:
            svec[s] = pm[d, s]           # the share s fails to deliver to d
        packs.append((perm, jnp.asarray(wvec, x.dtype),
                      jnp.asarray(svec, x.dtype)))
    if drop_mask is not None:
        drop_mask = jnp.asarray(drop_mask, x.dtype)
        if drop_mask.shape != (num_rounds, len(packs)):
            raise ValueError(
                f"drop_mask shape {drop_mask.shape} != (num_rounds, "
                f"num_matchings) = ({num_rounds}, {len(packs)})"
            )

    def tick(v, live):
        out = diag[idx] * v
        for k, (perm, wvec, svec) in enumerate(packs):
            recv = jax.lax.ppermute(v, axis_name, perm)
            if live is None:
                out = out + wvec[idx] * recv
            else:
                out = (out + wvec[idx] * live[k] * recv
                       + svec[idx] * (1.0 - live[k]) * v)
        return out

    m = jnp.ones_like(x)
    for r in range(num_rounds):
        live = None if drop_mask is None else drop_mask[r]
        x, m = tick(x, live), tick(m, live)
    safe = jnp.abs(m) > 1e-12
    return jnp.where(safe, x, 0.0) / jnp.where(safe, m, 1.0)


def algorithm_gossip(x, axis_name: str, fabric: PodFabric, num_rounds: int,
                     algorithm: str = "accel", **kwargs):
    """Run ``num_rounds`` of a *registered* consensus algorithm in-mesh.

    Dispatches through the ``repro.core.algorithms`` registry's dist-variant
    hook table — the shard_map mirror of the sweep engine's algorithm axis.
    This module registers the seed variants at import (memoryless ->
    ``gossip``, accel -> ``accel_gossip``, async_pairwise ->
    ``pairwise_gossip``); extra keyword arguments (``wire``, ``drop_mask``,
    ``schedule``) pass through to the variant.
    """
    from ..core.algorithms import dist_variant, get_algorithm

    algo = get_algorithm(algorithm)      # raises on unknown spec
    fn = dist_variant(algo.name)
    if fn is None:
        raise NotImplementedError(
            f"algorithm {algo.spec!r} has no registered dist variant "
            f"(register one via core.algorithms.register_dist_variant)")
    return fn(x, axis_name, fabric, num_rounds, **kwargs)


# Registrations with no shard_map gossip mirror, ON PURPOSE — consumed by
# the static analyzer's mesh-dist-coverage advisory (repro.analysis), so a
# deliberate gap is distinguishable from a forgotten one:
#   accel_m        — the M-tap frontier study runs through the sweep engine
#                    only; its memory-order sweep has no in-mesh use case.
#   poly_filter    — the Chebyshev/polynomial filter needs the full period's
#                    taps resident; the per-round wire protocol here has no
#                    super-iteration framing.
#   ratio_consensus — in-mesh lossy averaging is served by push_sum_gossip;
#                    the ratio variant differs only in engine-side seams.
DIST_EXEMPT = ("accel_m", "poly_filter", "ratio_consensus")


def _register_dist_variants():
    from ..core.algorithms import register_dist_variant

    register_dist_variant("memoryless", gossip)
    register_dist_variant("accel", accel_gossip)
    register_dist_variant("accel_adapt", adaptive_accel_gossip)
    register_dist_variant("async_pairwise", pairwise_gossip)
    register_dist_variant("push_sum", push_sum_gossip)


_register_dist_variants()


def default_doi_iters(fab: PodFabric, dtype, tol: float = 1e-4) -> int:
    """Largest safe K for Algorithm 1 on this fabric at this precision.

    Floating-point rounding re-injects a lambda_1 = 1 (mean) component that
    the W-applications amplify by (1/lambda_2)^K relative to the dominant
    mode, so K cannot grow freely on fast-mixing fabrics: pick the largest K
    whose contamination floor eps_mach * (1/lambda_2)^K stays below ``tol``,
    capped at the paper's K ~ N^2 slow-mixing budget. The dtype is
    canonicalized first: with x64 disabled a float64 request silently runs in
    float32, and K must budget for the eps that will actually round.
    """
    eps_mach = float(jnp.finfo(jax.dtypes.canonicalize_dtype(dtype)).eps)
    k_paper = max(4 * fab.num_pods * fab.num_pods, 8)
    lam2 = fab.lambda2
    if not 0.0 < lam2 < 1.0:
        return 8
    k_cap = int(math.log(tol / eps_mach) / math.log(1.0 / lam2))
    return max(1, min(k_paper, k_cap))


def distributed_lambda2(
    axis_name: str,
    num_pods: int,
    key,
    num_iters: int | None = None,
    normalize_every: int = 10,
    topology_kind: str = "ring",
    fabric: PodFabric | None = None,
    v_init=None,
    dtype=jnp.float32,
):
    """Algorithm 1 (Section III-D) run *inside* a jitted SPMD program.

    Each device holds one component of the iterate; consensus ticks are
    neighbour ppermutes, and the sup-norm normalizations are genuine
    max-consensus (diameter(G) neighbour-max sweeps — every node normalizes by
    the SAME number). Mirrors ``repro.core.doi.estimate_lambda2`` op for op:
    with ``matvec=fabric_matvec(fab.w)`` and the same ``v_init`` the host
    simulation agrees bit-for-bit in float64. Returns the per-device scalar
    lambda2_hat (identical on every device); cost is O(K) ticks, which is what
    lets ``ElasticFabric.resize`` re-solve Theorem 1 after a graph edit
    without gathering W (``make_fabric(..., lambda2=estimate)``).

    ``num_iters=None`` picks K via ``default_doi_iters``: explicit K is
    honoured as-is, but beware the contamination floor it documents —
    K=80 on a lambda_2=1/3 ring returns ~1.0, not lambda_2, at any precision.
    """
    fab = fabric if fabric is not None else make_fabric(num_pods, topology_kind)
    p = fab.num_pods
    # with x64 off a float64 request silently runs in float32; resolve it
    # up front so the K guard and the array dtypes agree
    dtype = jax.dtypes.canonicalize_dtype(dtype)
    if p == 1:
        return jnp.zeros((), dtype)
    if num_iters is None:
        num_iters = default_doi_iters(fab, dtype)
    idx = jax.lax.axis_index(axis_name)
    diag = jnp.asarray(np.diag(fab.w), dtype)
    perms = edge_permutations(fab.w)
    adj = (np.abs(fab.w) > 0).astype(np.float64)
    np.fill_diagonal(adj, 0.0)
    diam = topology.diameter(adj)

    # Senders scale their own value before the exchange (wsend[s] = W[d, s]
    # for each (s, d) of a matching), so every received term is a rounded
    # product and the only mul+add left for a code generator to contract is
    # the diagonal one: the iterate's arithmetic does not hinge on how a
    # backend fuses the accumulation, and ``fabric_matvec`` mirrors it.
    sends = [(perm, jnp.asarray(_sender_weights(perm, fab.w), dtype))
             for perm, _ in perms]

    def matvec(v):
        out = diag[idx] * v
        for perm, wsend in sends:
            out = out + jax.lax.ppermute(wsend[idx] * v, axis_name, perm)
        return out

    def max_consensus(m):
        # |v| >= 0, so the zero fill of off-matching ppermute slots is the
        # identity for max; D sweeps reach exact global agreement.
        for _ in range(diam):
            recvs = [jax.lax.ppermute(m, axis_name, perm) for perm, _ in perms]
            for r in recvs:
                m = jnp.maximum(m, r)
        return m

    v_full = (jnp.asarray(v_init, dtype)
              if v_init is not None else jax.random.normal(key, (p,), dtype))
    v = v_full[idx]
    v = matvec(v) - v           # line 2: exactly zero-mean start
    for k in range(1, num_iters + 1):
        v = matvec(v)
        if k % normalize_every == 0:
            norm = max_consensus(jnp.abs(v))
            v = jnp.where(norm > 0, v / norm, v)
    wv = matvec(v)
    num = max_consensus(jnp.abs(wv))
    den = max_consensus(jnp.abs(v))
    return jnp.where(den > 0, num / den, jnp.zeros_like(den))
