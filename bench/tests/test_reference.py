"""The float64 reference agrees with the sweep engine, and the comparison
passes the engine's answers at tiny sizes on both layouts and backends."""
import dataclasses

import numpy as np
import pytest

from bench import check, harness, reference, registry

SMALL = dict(topologies=["chain", "grid2d", "rgg"], sizes=[16, 25], graph_trials=2,
             graph_seed=3, num_trials=4)


def small_cell(name, rounds, **cfg):
    cell = registry.workload(name)
    cell["config_data"].update(cfg)
    cell["num_iters"] = rounds
    return cell


def program(cell, seed, backend, layout=None):
    from repro.sweep import build_ensemble, build_round_masks, run_ensemble

    spec = harness.sweep_spec(cell)
    if layout:
        spec = dataclasses.replace(spec, layout=layout)
    ens = build_ensemble(spec)
    lay = reference.graphs.layout(cell["config_data"], cell)
    x0 = harness.initial_conditions(lay, ens.n_max, ens.x0.shape[2], seed, 0)
    ens = dataclasses.replace(ens, x0=x0)
    masks = build_round_masks(ens, cell["num_iters"], seed=harness.mask_seed(seed, 0))
    res = run_ensemble(ens, num_iters=cell["num_iters"], backend=backend,
                       round_masks=masks)
    return ens, lay, x0, res


def gaps_against_reference(cell, seed, backend, layout=None):
    ens, lay, x0, res = program(cell, seed, backend, layout)
    cells = reference.graphs.cells(cell["config_data"], cell)
    assert [(c.graph.family, c.graph.n, c.graph.draw, c.algorithm, c.design, c.dynamics)
            for c in cells] == [(m.topology, m.n, m.graph_index, m.algorithm, m.design,
                                 m.dynamics) for m in ens.configs]
    for c, row in zip(cells, ens.coefs):
        if c.algorithm == "accel":
            np.testing.assert_allclose(c.coef, row[:3], rtol=1e-6, atol=1e-7)
    x0s = [x0[i, :c.graph.n].astype(np.float64) for i, c in enumerate(cells)]
    xr, mr = reference.run(cells, x0s, cell["num_iters"], harness.mask_seed(seed, 0))
    g = check.Gaps()
    for i, c in enumerate(cells):
        g.merge(check.compare(res.x_final[i, :c.graph.n], res.mse[i], x0s[i], xr[i], mr[i]))
    return g


@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("layout", ["dense", "sparse"])
def test_engine_passes_the_comparison(backend, layout):
    cell = small_cell("sensor_field.lossy", 40, **SMALL)
    if layout == "sparse":
        cell["algorithms"] = ["accel"]
    g = gaps_against_reference(cell, 2**31 + 5, backend, layout)
    limits = cell["check"]["limits"]
    assert g.overflow_mismatch == 0
    assert g.x_gap < limits["x_gap"] / 10 and g.mse_gap < limits["mse_gap"] / 10


def test_large_grid_spectrum_matches_the_sweep_grid():
    """Above 1024 nodes both sides estimate lambda_2 by power iteration."""
    from repro.sweep import build_ensemble

    cell = small_cell("sensor_field.static", 30, topologies=["grid2d"], sizes=[2500],
                      designs=["memoryless", "asymptotic"], num_trials=4, layout="sparse")
    ens = build_ensemble(harness.sweep_spec(cell))
    cells = reference.graphs.cells(cell["config_data"], cell)
    for c, meta, row in zip(cells, ens.configs, ens.coefs):
        assert abs(c.weights.lam2 - meta.lam2) < 1e-12
        np.testing.assert_allclose(c.coef, row[:3], rtol=1e-6, atol=1e-7)
    g = gaps_against_reference(cell, 11, "pallas")
    assert g.x_gap < 1e-5 and g.mse_gap < 1e-5


def test_schedule_matches_the_sweep_grid():
    from repro.sweep import build_ensemble, build_round_masks

    cell = small_cell("sensor_field.lossy", 25, **SMALL)
    ens = build_ensemble(harness.sweep_spec(cell))
    masks = build_round_masks(ens, 25, seed=123456789012)
    for i, c in enumerate(reference.graphs.cells(cell["config_data"], cell)):
        e = len(c.graph.edges)
        np.testing.assert_array_equal(masks.idx[i, :e], c.graph.edges)
        bits = reference.masks.edge_bits(c.dynamics, 123456789012, c.graph.key, 25, e)
        np.testing.assert_array_equal(masks.bits[:, i, :e].astype(bool), bits)


def test_diverging_cells_are_compared_in_kind():
    """Two-tap designs tuned for the static chain diverge under loss, in
    float64 too; the program has to diverge with them."""
    cell = small_cell("sensor_field.lossy", 2000, topologies=["chain"], sizes=[196],
                      designs=["asymptotic"], num_trials=2)
    cell["algorithms"], cell["dynamics"] = ["accel"], ["bernoulli:0.1"]
    ens, lay, x0, res = program(cell, 7, "jax")
    (c,) = reference.graphs.cells(cell["config_data"], cell)
    x0s = [x0[0, :196].astype(np.float64)]
    xr, mr = reference.run([c], x0s, 2000, harness.mask_seed(7, 0))
    assert mr[0][-1].max() > 1e30            # outgrows float32 in float64
    g = check.compare(res.x_final[0, :196], res.mse[0], x0s[0], xr[0], mr[0])
    assert g.overflow_mismatch == 0 and g.mse_gap < cell["check"]["limits"]["mse_gap"]
    # a program that stays bounded where the reference overflows is caught
    tame = check.compare(np.zeros((196, 2)), np.ones((2001, 2)), x0s[0], xr[0], mr[0])
    assert tame.overflow_mismatch > 0
