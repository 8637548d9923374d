"""Sparse (edge-list) layout: generators, weights, kernels, and the engine.

The contract under test is the ISSUE's acceptance bar: for every registry
algorithm, on every topology family that exists in both layouts, under both
static and failure-injected dynamics and on both backends, the sparse
engine's trajectories match the dense engine's to f32 roundoff — the two
layouts are storage formats of the SAME experiment, sharing RNG draws,
RoundMasks schedules, and (below the spectrum cutoff) bit-identical
coefficients. On top sit the large-N properties only the sparse path can
reach: mean conservation and finite averaging times at N = 1e5.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import topology, weights
from repro.sweep.engine import run_batch, run_ensemble, run_sweep
from repro.sweep.grid import SweepSpec, build_ensemble, build_round_masks


# ---------------------------------------------------------------------------
# generators (property-based)
# ---------------------------------------------------------------------------


def _assert_canonical(edges: np.ndarray) -> None:
    """Edges are i < j rows, lexsorted, unique — the layout-coupling invariant."""
    assert np.all(edges[:, 0] < edges[:, 1])
    order = np.lexsort((edges[:, 1], edges[:, 0]))
    np.testing.assert_array_equal(order, np.arange(len(edges)))
    assert len(np.unique(edges, axis=0)) == len(edges)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(min_value=8, max_value=120), m=st.integers(1, 4),
       seed=st.integers(0, 2**31 - 1))
def test_barabasi_albert_properties(n, m, seed):
    m = min(m, n - 1)
    g = topology.barabasi_albert(n, m, np.random.default_rng(seed))
    _assert_canonical(g.edges)
    assert topology.edges_are_connected(g.n, g.edges)
    # every non-seed node arrives with exactly m distinct edges (seed-star
    # leaves may stay at degree 1; only post-seed nodes carry the m bound)
    assert g.num_edges == m + (n - m - 1) * m
    if n > m + 1:
        assert g.degrees[m + 1:].min() >= m


@settings(max_examples=8, deadline=None)
@given(n=st.integers(min_value=8, max_value=60), seed=st.integers(0, 2**31 - 1))
def test_sparse_rgg_matches_dense_draw(n, seed):
    # identical rng consumption: the sparse generator must return exactly the
    # dense generator's edge set (this is what couples CRN across layouts)
    gd = topology.random_geometric(n, np.random.default_rng(seed))
    gs = topology.random_geometric_sparse(n, np.random.default_rng(seed))
    _assert_canonical(gs.edges)
    np.testing.assert_array_equal(gs.to_dense().adjacency, gd.adjacency)
    np.testing.assert_allclose(gs.coords, gd.coords)


@settings(max_examples=8, deadline=None)
@given(n=st.integers(min_value=8, max_value=80), seed=st.integers(0, 2**31 - 1))
def test_sparse_mh_weights_doubly_stochastic(n, seed):
    g = topology.barabasi_albert(n, 2, np.random.default_rng(seed))
    edge_w, diag_w = weights.metropolis_hastings_edges(g)
    w = np.zeros((g.n, g.n))
    w[g.edges[:, 0], g.edges[:, 1]] = edge_w
    w += w.T
    w[np.diag_indices(g.n)] = diag_w
    np.testing.assert_allclose(w.sum(axis=1), 1.0, atol=1e-12)
    np.testing.assert_allclose(w, w.T)
    weights.check_consensus_matrix(w)
    # and it is the dense MH matrix of the same graph
    np.testing.assert_allclose(
        w, weights.metropolis_hastings(g.to_dense()), atol=1e-12)


@settings(max_examples=6, deadline=None)
@given(n=st.integers(min_value=20, max_value=250),
       seed=st.integers(0, 2**31 - 1))
def test_erdos_renyi_sparse_properties(n, seed):
    """The O(E) geometric-skip sampler yields canonical, connected draws."""
    p = min(1.0, 2.5 * np.log(n) / n)
    g = topology.erdos_renyi_sparse(n, p, np.random.default_rng(seed))
    _assert_canonical(g.edges)
    assert g.n == n
    assert topology.edges_are_connected(g.n, g.edges)


def test_erdos_renyi_sparse_grid_dispatch_above_cutoff():
    """The grid no longer rejects erdos_renyi in the sparse layout: above the
    densify cutoff it routes to the O(E) sampler and the sweep runs."""
    spec = SweepSpec(topologies=("erdos_renyi",), sizes=(2000,),
                     designs=("asymptotic",), alphas=(1.0,),
                     algorithms=("accel",), num_trials=2, layout="auto",
                     seed=0)
    assert spec.resolved_layout == "sparse"
    res = run_sweep(spec, num_iters=10, trial_chunk=1)
    assert res.ensemble.is_sparse
    assert np.all(np.isfinite(res.mse))
    x0, xf = res.ensemble.x0[0], res.x_final[0]
    assert np.abs(xf.sum(axis=0) - x0.sum(axis=0)).max() / 2000 < 1e-3
    assert np.all(res.mse[0, -1] < res.mse[0, 0])


def test_directed_family_is_dense_only():
    spec = SweepSpec(topologies=("directed",), sizes=(12,),
                     designs=("memoryless",), algorithms=("push_sum",),
                     num_trials=1, layout="sparse")
    with pytest.raises(ValueError, match="dense-only"):
        build_ensemble(spec)


def test_deterministic_sparse_families_match_dense():
    pairs = [
        (topology.sparse_chain(9), topology.chain(9)),
        (topology.sparse_ring(9), topology.ring(9)),
        (topology.sparse_grid2d(3, 4), topology.grid2d(3, 4)),
        (topology.sparse_torus2d(3, 4), topology.torus2d(3, 4)),
    ]
    for gs, gd in pairs:
        _assert_canonical(gs.edges)
        np.testing.assert_array_equal(gs.to_dense().adjacency, gd.adjacency)


# ---------------------------------------------------------------------------
# sparse segment-reduce round vs the dense oracle (both kernels)
# ---------------------------------------------------------------------------


def test_segment_round_kernel_matches_dense_oracle():
    from repro.kernels import ops

    rng = np.random.default_rng(3)
    g = topology.random_geometric_sparse(40, rng)
    edge_w, diag_w = weights.metropolis_hastings_edges(g)
    w = g.to_dense().adjacency * 0.0
    w[g.edges[:, 0], g.edges[:, 1]] = edge_w
    w += w.T
    w[np.diag_indices(g.n)] = diag_w
    x = rng.standard_normal((g.n, 5)).astype(np.float32)
    xp = rng.standard_normal((g.n, 5)).astype(np.float32)
    a, b, c = 1.1, 0.25, -0.35
    nbr, wgt, wrev, slot, diag = ops.build_ell(g.edges, edge_w, diag_w, g.n)

    y = np.asarray(ops.segment_round(nbr, wgt, slot, diag, x, xp, a, b, c))
    ref = a * (w @ x) + b * x + c * xp
    np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-5)

    # masked: dropped edge mass returns to the source diagonal
    bits = (rng.random(g.num_edges) < 0.6).astype(np.float32)
    ym = np.asarray(
        ops.segment_round(nbr, wgt, slot, diag, x, xp, a, b, c, bits=bits))
    m = np.eye(g.n)
    m[g.edges[:, 0], g.edges[:, 1]] = bits
    m[g.edges[:, 1], g.edges[:, 0]] = bits
    wm = w * m
    weff = wm + np.diag((w - wm).sum(axis=1))
    refm = a * (weff @ x) + b * x + c * xp
    np.testing.assert_allclose(ym, refm, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# engine: sparse == dense per registry algorithm / dynamics / backend
# ---------------------------------------------------------------------------

_TOPOLOGIES = ("chain", "grid2d", "rgg")


def _run_both(algos, dynamics, backend, num_trials=3, iters=40):
    results = []
    for layout in ("dense", "sparse"):
        spec = SweepSpec(
            topologies=_TOPOLOGIES, sizes=(12, 20), designs=("asymptotic",),
            alphas=(1.0,), num_trials=num_trials, seed=7, algorithms=algos,
            dynamics=dynamics, layout=layout,
        )
        ens = build_ensemble(spec)
        masks = build_round_masks(ens, iters, seed=7)
        results.append(
            run_ensemble(ens, num_iters=iters, backend=backend,
                         round_masks=masks))
    return results


@pytest.mark.parametrize("algo", ["memoryless", "accel", "poly_filter:4",
                                  "async_pairwise", "push_sum",
                                  "ratio_consensus:0.5"])
@pytest.mark.parametrize("dyn", ["static", "bernoulli:0.1"])
def test_sparse_matches_dense_jax(algo, dyn):
    r_d, r_s = _run_both((algo,), ("static", dyn), "jax")
    np.testing.assert_allclose(r_s.x_final, r_d.x_final, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(r_s.mse, r_d.mse, rtol=1e-4, atol=1e-8)
    # identical metadata below the spectrum cutoff: same cells, same coefs
    np.testing.assert_array_equal(r_s.ensemble.coefs, r_d.ensemble.coefs)


@pytest.mark.parametrize("algos,dyn", [
    (("memoryless", "accel"), ("static",)),
    (("accel", "async_pairwise"), ("static", "bernoulli:0.1")),
    # asymmetric-base family: static rides the ELL kernel with per-direction
    # weights; the lossy cells exercise the sender-renorm jnp fallback
    # inside the same jitted scan
    (("push_sum", "ratio_consensus:0.5"), ("static", "bernoulli:0.1")),
])
def test_sparse_matches_dense_pallas(algos, dyn):
    r_d, r_s = _run_both(algos, dyn, "pallas", iters=25)
    np.testing.assert_allclose(r_s.x_final, r_d.x_final, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(r_s.mse, r_d.mse, rtol=1e-4, atol=1e-8)


def test_sparse_ratio_family_mass_conserved_under_correlated_loss():
    """Sparse-layout ratio family: total value and total mass survive i.i.d.
    AND correlated (block-outage) packet loss, and the displayed quotient
    still lands on the true average."""
    spec = SweepSpec(topologies=("grid2d",), sizes=(20,),
                     designs=("memoryless",),
                     algorithms=("push_sum", "ratio_consensus:0.5"),
                     dynamics=("bernoulli:0.1", "correlated:0.25:4:5"),
                     num_trials=3, seed=9, layout="sparse")
    ens = build_ensemble(spec)
    masks = build_round_masks(ens, 240, seed=9)
    res = run_ensemble(ens, num_iters=240, round_masks=masks,
                       return_taps=True)
    for name, s, e, (sv, mv) in res.taps:
        np.testing.assert_allclose(
            sv.sum(axis=1), ens.x0[s:e].sum(axis=1), atol=2e-3,
            err_msg=f"{name} lost total value")
        np.testing.assert_allclose(
            mv.sum(axis=1), 20.0, atol=2e-3,
            err_msg=f"{name} lost total mass")
    xbar = ens.x0.sum(axis=1, keepdims=True) / 20.0
    assert np.abs(res.x_final - xbar).max() < 1e-3


def test_trial_chunk_matches_unchunked():
    spec = SweepSpec(topologies=("chain", "rgg"), sizes=(12, 20),
                     designs=("asymptotic",), alphas=(1.0,), num_trials=7,
                     seed=3, algorithms=("accel",),
                     dynamics=("static", "bernoulli:0.2"), layout="sparse")
    ens = build_ensemble(spec)
    masks = build_round_masks(ens, 30, seed=3)
    r0 = run_ensemble(ens, num_iters=30, round_masks=masks)
    r1 = run_ensemble(ens, num_iters=30, round_masks=masks, trial_chunk=3)
    np.testing.assert_allclose(r1.mse, r0.mse, rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(r1.x_final, r0.x_final, rtol=1e-6, atol=1e-7)


def test_auto_layout_resolution():
    small = SweepSpec(sizes=(16, 64), layout="auto")
    big = SweepSpec(topologies=("ba:3",), sizes=(16, 5000), layout="auto")
    assert small.resolved_layout == "dense"
    assert big.resolved_layout == "sparse"
    with pytest.raises(ValueError):
        SweepSpec(layout="csr")
    ens = build_ensemble(SweepSpec(
        topologies=("chain",), sizes=(10,), designs=("asymptotic",),
        alphas=(1.0,), num_trials=2, layout="sparse"))
    assert ens.is_sparse and ens.ws is None


def test_run_batch_sparse_requires_edge_arrays():
    with pytest.raises(ValueError, match="sparse mode"):
        run_batch(None, np.zeros((1, 4, 2)), np.zeros((1, 3)), num_iters=1)


# ---------------------------------------------------------------------------
# large N: what only the sparse path can reach
# ---------------------------------------------------------------------------


def test_sparse_large_n_mean_conserved_and_converging():
    n = 100_000
    spec = SweepSpec(topologies=("ba:3",), sizes=(n,), designs=("asymptotic",),
                     alphas=(1.0,), num_trials=2, seed=0,
                     algorithms=("accel",), layout="sparse")
    res = run_sweep(spec, num_iters=25, trial_chunk=1)
    x0, xf = res.ensemble.x0[0], res.x_final[0]
    drift = np.abs(xf.sum(axis=0) - x0.sum(axis=0)) / n
    assert np.max(drift) < 1e-3            # segment-sum rounds conserve mass
    assert np.all(np.isfinite(res.mse))
    # MSE falls monotonically-ish: final well below initial on an expander
    assert np.all(res.mse[0, -1] < 1e-2 * res.mse[0, 0])
    at = res.averaging_times(eps=1e-1)
    assert np.all(at >= 0)                 # finite averaging times at N=1e5


# ---------------------------------------------------------------------------
# slot tiling + sender-renorm ELL kernel
# ---------------------------------------------------------------------------


def _ell_fixture(n, f, g=2, seed=5, bd=8):
    """Batched slot-major ELL operands (tile-padded) + matching dense W, bits."""
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    bm = 128
    n_t = ops._round_up(n, bm)
    gph = topology.random_geometric_sparse(n, rng)
    edge_w, diag_w = weights.metropolis_hastings_edges(gph)
    nbr, wgt, wrev, slot, diag = ops.build_ell(
        gph.edges, edge_w, np.pad(diag_w, (0, n_t - n)), n_t)
    d_pad = ops._round_up(nbr.shape[1], bd) - nbr.shape[1]
    nbr, wgt, wrev, slot = (
        np.pad(a, ((0, 0), (0, d_pad))) for a in (nbr, wgt, wrev, slot))
    nbr, wgt, wrev, slot, diag = ops.ell_slot_major(
        nbr, wgt.astype(np.float32), wrev.astype(np.float32), slot,
        diag.astype(np.float32))
    e = gph.num_edges
    bits = (rng.random((g, e)) < 0.6).astype(np.float32)
    w = np.zeros((n_t, n_t))
    w[gph.edges[:, 0], gph.edges[:, 1]] = edge_w
    w += w.T
    w[np.diag_indices(n)] = diag_w
    stack = lambda a: jnp.asarray(np.stack([a] * g))
    xs = rng.standard_normal((g, n_t, f)).astype(np.float32)
    xps = rng.standard_normal((g, n_t, f)).astype(np.float32)
    coefs = np.stack([[1.1, 0.2, -0.3]] * g).astype(np.float32)
    return dict(
        gph=gph, w=w, n_t=n_t, bits=bits,
        nbrs=stack(nbr), wgts=stack(wgt), wrevs=stack(wrev),
        slots=stack(slot), diags=stack(diag),
        bitsj=jnp.asarray(bits), xs=jnp.asarray(xs), xps=jnp.asarray(xps),
        coefs=jnp.asarray(coefs))


def _segment_call(variant, fx, bd, bf):
    from repro.kernels import ops, segment_round as sk

    kw = dict(bm=128, bd=bd, bf=bf, interpret=ops.use_interpret())
    if variant == "plain":
        return sk.segment_round_batched_pallas(
            fx["nbrs"], fx["wgts"], fx["diags"], fx["xs"], fx["xps"],
            fx["coefs"], **kw)
    if variant == "masked":
        return sk.segment_round_masked_batched_pallas(
            fx["nbrs"], fx["wgts"], fx["slots"], fx["diags"], fx["bitsj"],
            fx["xs"], fx["xps"], fx["coefs"], **kw)
    return sk.segment_round_sender_masked_batched_pallas(
        fx["nbrs"], fx["wgts"], fx["wrevs"], fx["slots"], fx["diags"],
        fx["bitsj"], fx["xs"], fx["xps"], fx["coefs"], **kw)


@pytest.mark.parametrize("variant", ["plain", "masked", "sender"])
def test_segment_round_slot_tiles_agree(variant):
    """Splitting the slot reduction over bd-wide grid steps (bd = 1, 2, 4
    against the whole padded degree in one step) computes the same round,
    for the plain, receiver-masked and sender-masked batched kernels."""
    fx = _ell_fixture(200, 64)
    d = fx["nbrs"].shape[1]
    y_one = np.asarray(_segment_call(variant, fx, d, 64))
    for bd in (1, 2, 4):
        np.testing.assert_allclose(
            np.asarray(_segment_call(variant, fx, bd, 64)), y_one,
            rtol=1e-6, atol=1e-6, err_msg=f"bd={bd}")


def test_sender_masked_segment_matches_dense_column_renorm():
    """Sparse sender-renorm kernel == dense column-renorm oracle: dropped
    mass W_ji of a dead edge returns to sender i's diagonal."""
    fx = _ell_fixture(120, 32)
    gph, w, n_t = fx["gph"], fx["w"], fx["n_t"]
    y = _segment_call("sender", fx, 8, 32)
    for i in range(fx["bits"].shape[0]):
        m = np.eye(n_t)
        m[gph.edges[:, 0], gph.edges[:, 1]] = fx["bits"][i]
        m[gph.edges[:, 1], gph.edges[:, 0]] = fx["bits"][i]
        wm = w * m
        weff = wm + np.diag((w - wm).sum(axis=0))
        x_, xp_ = np.asarray(fx["xs"][i]), np.asarray(fx["xps"][i])
        y_ref = 1.1 * (weff @ x_) + 0.2 * x_ - 0.3 * xp_
        np.testing.assert_allclose(
            np.asarray(y[i]), y_ref, rtol=1e-4, atol=1e-4)


def test_build_ell_wrev_is_transposed_weight():
    """wrev[i, d] = W[nbr[i, d], i]: the weight of the reverse direction,
    asymmetric bases included; zero on padding slots."""
    from repro.kernels import ops

    rng = np.random.default_rng(9)
    gph = topology.random_geometric_sparse(60, rng)
    e_fwd = rng.uniform(0.1, 1.0, gph.num_edges)
    e_bwd = rng.uniform(0.1, 1.0, gph.num_edges)
    diag = rng.uniform(0.1, 1.0, gph.n)
    nbr, wgt, wrev, slot, dg = ops.build_ell(
        gph.edges, e_fwd, diag, gph.n, edge_w_rev=e_bwd)
    w = np.zeros((gph.n, gph.n))
    w[gph.edges[:, 0], gph.edges[:, 1]] = e_fwd   # W[i, j]: j -> i weight
    w[gph.edges[:, 1], gph.edges[:, 0]] = e_bwd
    for i in range(gph.n):
        for d in range(nbr.shape[1]):
            if wgt[i, d] == 0.0:
                assert wrev[i, d] == 0.0
            else:
                np.testing.assert_allclose(wgt[i, d], w[i, nbr[i, d]])
                np.testing.assert_allclose(wrev[i, d], w[nbr[i, d], i])
