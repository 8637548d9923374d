"""Property tests for the adaptive/M-tap layer (time-varying coefficients).

Three groups:

1. **Host/traced twin agreement** — ``accel.alpha_star_jnp`` must match the
   host ``accel.alpha_star`` to f64 roundoff across the (lambda_2, theta)
   plane; the in-scan re-solve of ``accel_adapt`` is only trustworthy if the
   twins agree everywhere the estimator can wander.
2. **M-tap frontier algebra** — ``m_tap_weights(2, .)`` is exactly Theorem 1
   with the asymptotic design; the M >= 3 true-interval design achieves its
   advertised rate on the *discrete* chain spectrum and is locally optimal
   there (a direct search over genuine 3-tap weights cannot beat it —
   Golub-Varga saturation, checked numerically, not assumed).
3. **Aux-carry semantics in the engine** — an ``accel_adapt`` cell whose
   nominal floor is seeded WRONG (far below the true lambda_2) must still
   reach a sustained averaging time: the in-scan estimator has to lift
   lam_hat above the bad floor and change alpha mid-run inside the one
   jitted scan. Mean conservation is asserted with the aux slots present.
   This is also where ``accel_adapt`` gets its TIGHT trajectory conformance
   (static regime, floor pins the coefficient stream) — the registry-wide
   conformance bound in tests/test_algorithms.py is deliberately loose for
   this algorithm because heavy-masking regimes are Lyapunov-divergent
   across backends.
"""
import numpy as np
import pytest

from repro.core import accel, algorithms, topology, weights
from repro.runtime.elastic import ElasticFabric
from repro.sweep import engine, grid


def _chain_interval(n):
    w = weights.metropolis_hastings(topology.chain(n))
    vals = np.linalg.eigvalsh(w)
    return w, float(vals[0]), float(vals[-2])


# ---------------------------------------------------------------------------
# 1. alpha* twins across the (lambda_2, theta) plane.
# ---------------------------------------------------------------------------

def test_alpha_star_jnp_matches_host_to_f64_roundoff():
    import jax
    import jax.numpy as jnp

    lams = np.linspace(0.0, 0.999999, 251)
    thetas = [accel.theta_ls()] + [
        accel.theta_asymptotic(e) for e in (0.05, 0.5, 2.0)
    ]
    with jax.enable_x64(True):
        for th in thetas:
            host = np.array([accel.alpha_star(lam, th) for lam in lams])
            twin = np.asarray(
                accel.alpha_star_jnp(jnp.asarray(lams, jnp.float64), th)
            )
            np.testing.assert_allclose(twin, host, rtol=1e-12, atol=1e-12)
            # tuple form (what the round body passes) == Theta form
            tup = np.asarray(accel.alpha_star_jnp(
                jnp.asarray(lams, jnp.float64), th.as_tuple))
            np.testing.assert_array_equal(tup, twin)


def test_alpha_star_jnp_f32_cutoff_is_graceful():
    # memoryless design theta = (0, 0, 1): den == 0, the traced twin must
    # return exactly 0.0 (not nan) in the engine's own dtype
    import jax.numpy as jnp

    out = accel.alpha_star_jnp(jnp.float32(0.7), (0.0, 0.0, 1.0))
    assert float(out) == 0.0


# ---------------------------------------------------------------------------
# 2. M-tap frontier algebra.
# ---------------------------------------------------------------------------

def test_m2_weights_are_exactly_theorem1():
    th = accel.theta_asymptotic(0.5)
    for lam2 in (0.3, 0.9, 0.9872, 0.999):
        wts, rho = accel.m_tap_weights(2, lam2)
        al = accel.alpha_star(lam2, th)
        expect = (1.0 - al + al * th.t3, al * th.t2, al * th.t1)
        np.testing.assert_allclose(wts, expect, rtol=1e-9, atol=1e-12)
        np.testing.assert_allclose(rho, accel.rho_accel(lam2, th), rtol=1e-9)
        # and the symmetric interval reduction agrees
        a, b, c, rho_i = accel.two_tap_interval_weights(-lam2, lam2)
        np.testing.assert_allclose((a, b, c, rho_i), (*expect, rho), rtol=1e-9,
                                   atol=1e-12)


def _rho_on_spectrum(wts, eigvals):
    """Exact asymptotic rate of an M-tap recursion on a discrete spectrum:
    max over non-consensus eigenvalues of the companion-polynomial root
    magnitudes of  mu^M = (a lam + b) mu^{M-1} + sum_m c_m mu^{M-1-m}."""
    a, b, cs = wts[0], wts[1], wts[2:]
    worst = 0.0
    for lam in eigvals:
        poly = np.concatenate(([1.0, -(a * lam + b)], -np.asarray(cs)))
        worst = max(worst, float(np.abs(np.roots(poly)).max()))
    return worst


def test_m3_design_rate_is_exact_on_chain_spectrum():
    _, lam_n, lam2 = _chain_interval(16)
    wts, rho = accel.m_tap_weights(3, lam2, lam_n)
    vals = np.linalg.eigvalsh(weights.metropolis_hastings(topology.chain(16)))
    got = _rho_on_spectrum(wts, vals[:-1])  # drop the consensus eigenvalue
    np.testing.assert_allclose(got, rho, rtol=1e-7)
    # the true-interval rate strictly beats the symmetric Theorem-1 rate
    assert rho < accel.m_tap_weights(2, lam2)[1] - 1e-3


def test_m3_saturation_direct_search_cannot_beat_two_taps():
    """Golub-Varga saturation on the discrete chain spectrum: perturbing the
    analytic weights over GENUINE 3-tap space (c2 != 0), holding the
    consensus fixed point (sum of weights == 1), never improves the rate."""
    _, lam_n, lam2 = _chain_interval(16)
    wts, rho = accel.m_tap_weights(3, lam2, lam_n)
    assert wts[3] == 0.0  # the analytic optimum puts zero weight on tap 3
    vals = np.linalg.eigvalsh(weights.metropolis_hastings(topology.chain(16)))
    spectrum = vals[:-1]
    rng = np.random.default_rng(0)
    best = np.inf
    for scale in (1e-3, 1e-2, 5e-2):
        for _ in range(120):
            d = rng.normal(size=4) * scale
            d -= d.mean()  # keep sum(weights) == 1: consensus stays fixed
            best = min(best, _rho_on_spectrum(wts + d, spectrum))
    assert best >= rho - 1e-6


def test_interval_and_bound_validation():
    with pytest.raises(ValueError):
        accel.two_tap_interval_weights(0.9, 0.2)
    with pytest.raises(ValueError):
        accel.two_tap_interval_weights(-1.0, 0.5)
    with pytest.raises(ValueError):
        accel.m_tap_weights(1, 0.9)
    with pytest.raises(ValueError):
        accel.averaging_time_lower_bound(0.0, -0.3, 0.9)
    with pytest.raises(ValueError):
        accel.averaging_time_lower_bound(1e-3, 0.9, 0.2)
    with pytest.raises(ValueError):
        algorithms.get_algorithm("accel_adapt:1.5")  # eta outside [0, 1]


def test_lower_bound_chain16_and_monotonicity():
    _, lam_n, lam2 = _chain_interval(16)
    t = accel.averaging_time_lower_bound(1e-4, lam_n, lam2)
    assert t == 51  # the floor fig_adaptive's mtap rows are measured against
    assert accel.averaging_time_lower_bound(1e-6, lam_n, lam2) > t
    # tighter interval -> weaker lower bound
    assert accel.averaging_time_lower_bound(1e-4, lam_n, 0.9) < t


# ---------------------------------------------------------------------------
# 3. Aux-carry semantics in the engine.
# ---------------------------------------------------------------------------

def _adaptive_cell(seed=3):
    spec = grid.SweepSpec(
        topologies=("chain",), sizes=(12,), designs=("asymptotic",),
        num_trials=2, algorithms=("accel_adapt",), dynamics=("static",),
        seed=seed,
    )
    return grid.build_ensemble(spec)


def test_adaptive_recovers_from_wrong_nominal_floor():
    ens = _adaptive_cell()
    baseline = engine.run_ensemble(ens, num_iters=400, backend="jax")
    t_good = baseline.averaging_times(eps=1e-3, sustained=True)
    assert (t_good >= 0).all()

    # Sabotage the nominal floor: halve lam2_nom in the param row. Tick 0
    # runs a badly detuned alpha*; the ONLY way to a sustained time is the
    # in-scan estimator lifting lam_hat above the wrong floor — i.e. the
    # coefficient row genuinely changes mid-run inside the jitted scan.
    ens_bad = _adaptive_cell()
    ens_bad.coefs[:, 0] *= 0.5
    res = engine.run_ensemble(ens_bad, num_iters=400, backend="jax")
    t_bad = res.averaging_times(eps=1e-3, sustained=True)
    assert (t_bad >= 0).all()
    # adaptation recovers most of the tuning: no worse than 3x the
    # correctly-seeded run (a frozen wrong alpha would not converge this
    # fast — the chain's detuned rho is far from the tuned one)
    assert (t_bad <= 3 * t_good).all()


def test_mean_conserved_with_aux_slots_present():
    ens = _adaptive_cell(seed=7)
    res = engine.run_ensemble(ens, num_iters=60, backend="jax",
                              return_taps=True)
    mask = ens.mask()[:, :, None]
    m0 = (ens.x0 * mask).sum(axis=1) / mask.sum(axis=1)
    mf = (res.x_final * mask).sum(axis=1) / mask.sum(axis=1)
    np.testing.assert_allclose(mf, m0, atol=2e-5)
    # the taps view exposes exactly num_taps slots — estimator state
    # (probe block, lam_hat, mask) never leaks into the displayed carry
    (spec_name, _, _, taps), = res.taps
    assert spec_name == "accel_adapt"
    assert len(taps) == algorithms.get_algorithm("accel_adapt").num_taps


def test_adaptive_static_matches_accel_tightly():
    """The TIGHT trajectory check the registry-wide conformance suite cannot
    make: on a static graph the floor pins the coefficient stream to the
    nominal alpha*, so accel_adapt must track plain accel to f32 noise
    (the in-scan f32 re-solve differs from the host-precomputed coefficient
    row only in the last ulp)."""
    spec = grid.SweepSpec(
        topologies=("chain",), sizes=(12,), designs=("asymptotic",),
        num_trials=2, algorithms=("accel", "accel_adapt"), seed=11,
    )
    ens = grid.build_ensemble(spec)
    res = engine.run_ensemble(ens, num_iters=120, backend="jax")
    (i_accel,) = res.cells(algorithm="accel")
    (i_adapt,) = res.cells(algorithm="accel_adapt")
    np.testing.assert_allclose(res.mse[i_adapt], res.mse[i_accel],
                               rtol=1e-4, atol=5e-7)


def test_refresh_lambda2_floors_and_counts():
    ef = ElasticFabric(topology="ring")
    fab0 = ef.bootstrap([0, 1, 2, 3])
    # estimate below nominal: floored — same tuning, but the re-tune is
    # counted (the control plane did act on fresh information)
    fab1 = ef.refresh_lambda2(0.5 * fab0.lambda2)
    assert fab1.lambda2 == pytest.approx(fab0.lambda2)
    assert fab1.alpha == pytest.approx(fab0.alpha)
    assert ef.retune_count == 1 and ef.resize_count == 0
    # degradation: estimate above nominal re-solves Theorem 1 upward,
    # without touching the member list
    lam_up = 0.5 * (fab0.lambda2 + 1.0)
    fab2 = ef.refresh_lambda2(lam_up)
    assert fab2.lambda2 == pytest.approx(lam_up)
    assert fab2.alpha > fab0.alpha
    assert ef.members == [0, 1, 2, 3] and ef.resize_count == 0
    with pytest.raises(RuntimeError):
        ElasticFabric().refresh_lambda2(0.5)
