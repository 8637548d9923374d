"""Sweep engine: ensemble-vs-simulate agreement, one-compilation contract,
Theorem-2 bound across the chain family, and the Fig. 4 gain trend."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import accel, simulator
from repro.sweep import (
    SweepSpec,
    build_ensemble,
    merge_ensembles,
    run_ensemble,
    run_sweep,
    trace_count,
)


@pytest.fixture(scope="module")
def grid_result():
    """One heterogeneous grid (3 families x 3 designs, mixed sizes), run once."""
    spec = SweepSpec(
        topologies=("chain", "grid2d", "rgg"),
        sizes=(12, 20),
        designs=("memoryless", "ls", "asymptotic"),
        num_trials=3,
        seed=7,
    )
    tc0 = trace_count()
    res = run_sweep(spec, num_iters=120, backend="jax")
    return res, trace_count() - tc0


def test_single_compilation_for_full_grid(grid_result):
    """>=3 topology families x >=3 theta designs -> ONE jitted program."""
    res, compiles = grid_result
    assert res.ensemble.num_configs == 2 * 3 * 3  # sizes x families x designs
    assert compiles == 1


def test_ensemble_matches_per_graph_simulate(grid_result):
    """Every cell of the vmapped ensemble == its standalone simulate() run.

    Cells with n=12 are zero-padded to the grid's Nmax=20 inside the batch,
    so this also proves padding exactness. jax backend on both sides: the
    arithmetic must agree bit-for-bit-ish (same fused scan, G=1 vs G=18).
    """
    res, _ = grid_result
    for i, c in enumerate(res.configs):
        n = c.n
        w = res.ensemble.ws[i][:n, :n]
        x0 = res.ensemble.x0[i][:n]
        r = simulator.simulate(
            w, x0, 120,
            alpha=c.alpha, theta=c.theta,
            backend="jax",
        )
        np.testing.assert_allclose(res.mse[i], r.mse, rtol=1e-5, atol=1e-9)
        np.testing.assert_allclose(res.x_final[i][:n], r.x_final, rtol=1e-4, atol=1e-6)
        # padded nodes never acquire signal
        assert np.all(res.x_final[i][n:] == 0.0)


def test_ensemble_matches_numpy_float64(grid_result):
    """fp32 engine vs float64 numpy semantics on early iterations."""
    res, _ = grid_result
    for i in np.random.default_rng(0).choice(len(res.configs), 4, replace=False):
        c = res.configs[i]
        n = c.n
        r = simulator.simulate(
            res.ensemble.ws[i][:n, :n], res.ensemble.x0[i][:n], 40,
            alpha=c.alpha, theta=c.theta, backend="numpy",
        )
        np.testing.assert_allclose(res.mse[i][:41], r.mse, rtol=1e-3, atol=1e-6)


def test_pallas_sweep_matches_jax_sweep():
    spec = SweepSpec(topologies=("chain", "rgg"), sizes=(14,),
                     designs=("memoryless", "asymptotic"), num_trials=2, seed=3)
    r_jax = run_sweep(spec, num_iters=60, backend="jax")
    r_pal = run_sweep(spec, num_iters=60, backend="pallas")
    np.testing.assert_allclose(r_pal.mse, r_jax.mse, rtol=1e-4, atol=1e-8)
    np.testing.assert_allclose(r_pal.x_final, r_jax.x_final, rtol=1e-4, atol=1e-6)


def test_theorem2_bound_across_chain_family():
    """rho(Phi3[alpha*]-J) <= 1 - sqrt(Psi) for every chain cell (Theorem 2)."""
    spec = SweepSpec(topologies=("chain",), sizes=(10, 24, 48, 96),
                     designs=("asymptotic",), num_trials=1, seed=0)
    ens = build_ensemble(spec)
    assert len(ens.configs) == 4
    for c in ens.configs:
        assert c.psi > 0.0
        assert c.rho_accel <= accel.rho_accel_bound(c.psi) + 1e-12, (
            f"chain n={c.n}: rho={c.rho_accel} > bound {accel.rho_accel_bound(c.psi)}"
        )
        # and the closed form used by the grid metadata matches accel.rho_accel
        np.testing.assert_allclose(
            c.rho_accel, accel.rho_accel(c.lam2, c.theta), atol=1e-9
        )


def test_chain_gain_trend_factor_n():
    """Fig. 4 / Theorem 3: measured gain on chains grows ~linearly with N."""
    spec = SweepSpec(topologies=("chain",), sizes=(10, 20, 40),
                     designs=("memoryless", "asymptotic"),
                     num_trials=1, init="paper", seed=0)
    ens = build_ensemble(spec)
    res = run_ensemble(ens, num_iters=4500, backend="jax")
    times = res.averaging_times(eps=1e-3)[:, 0]
    gains = {}
    for n in (10, 20, 40):
        [i] = res.cells(topology="chain", n=n, design="memoryless")
        [j] = res.cells(topology="chain", n=n, design="asymptotic")
        assert times[i] > 0 and times[j] > 0, f"n={n} did not converge in cap"
        gains[n] = times[i] / times[j]
        theory = res.configs[j].gain_asym
        assert 0.4 * theory < gains[n] < 2.5 * theory
    # doubling N should grow the gain markedly (~2x asymptotically)
    assert gains[20] / gains[10] > 1.5
    assert gains[40] / gains[20] > 1.5


def test_merge_ensembles_repads():
    e1 = build_ensemble(SweepSpec(topologies=("chain",), sizes=(8,),
                                  designs=("memoryless",), num_trials=2, seed=0))
    e2 = build_ensemble(SweepSpec(topologies=("ring",), sizes=(15,),
                                  designs=("memoryless",), num_trials=2, seed=0))
    m = merge_ensembles(e1, e2)
    assert m.n_max == 15 and m.num_configs == 2
    assert m.ws.shape == (2, 15, 15)
    np.testing.assert_allclose(m.ws[0][:8, :8], e1.ws[0])
    assert np.all(m.ws[0][8:] == 0.0) and np.all(m.ws[0][:, 8:] == 0.0)
    assert list(m.node_counts) == [8, 15]


def test_grid_axis_shards_across_devices():
    """G axis over the mesh 'data' axis, incl. pad-to-divisibility (G=3 on 4
    devices). Subprocess: XLA_FLAGS must precede jax init."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(root, "src")
    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.core import simulator
        from repro.sweep import SweepSpec, run_sweep
        assert jax.device_count() == 4
        spec = SweepSpec(topologies=("chain",), sizes=(8, 10, 12),
                         designs=("memoryless",), num_trials=2, seed=0)
        res = run_sweep(spec, num_iters=50, backend="jax")   # G=3, padded to 4
        assert res.mse.shape == (3, 51, 2)
        c = res.configs[1]; n = c.n
        r = simulator.simulate(res.ensemble.ws[1][:n, :n], res.ensemble.x0[1][:n],
                               50, backend="jax")
        err = float(np.abs(r.mse - res.mse[1]).max())
        assert err < 1e-6, err
        print("OK sharded", err)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env, cwd=root)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "OK sharded" in r.stdout


def test_run_batch_rejects_unknown_backend(rng):
    ws = rng.standard_normal((1, 4, 4))
    x0 = rng.standard_normal((1, 4, 2))
    with pytest.raises(ValueError, match="backend"):
        from repro.sweep import run_batch
        run_batch(ws, x0, np.ones((1, 3)), num_iters=3, backend="tensorflow")


def test_spec_rejects_unknown_design():
    with pytest.raises(ValueError, match="design"):
        SweepSpec(designs=("memoryless", "chebyshev"))


def test_shard_rows_deal_every_partition_evenly():
    """Under a mesh each algorithm partition is padded to a multiple of the
    'data' axis with its last cell and dealt out in equal chunks, so every
    device's block holds the same local partition layout."""
    from repro.sweep.engine import _shard_rows

    rows, part_rows, local = _shard_rows((("accel", 0, 5), ("push_sum", 5, 7)), 4)
    assert local == (("accel", 0, 2), ("push_sum", 2, 3))
    assert part_rows[0].tolist() == [0, 1, 2, 3, 4, 4, 4, 4]
    assert part_rows[1].tolist() == [5, 6, 6, 6]
    blocks = rows.reshape(4, 3)           # device d owns row block d
    assert blocks.tolist() == [[0, 1, 5], [2, 3, 6], [4, 4, 6], [4, 4, 6]]
    assert set(rows.tolist()) == set(range(7))

