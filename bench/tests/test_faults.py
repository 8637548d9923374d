"""A run whose timed path is broken underneath comes out not correct.

The harness is driven as ``run.py`` drives it, minus the look for a chip,
on a small copy of each cell on the CPU, with ``repro.sweep.run_ensemble``
broken in one way at a time. The one-chip cells have no exchange between
chips, so that fault has nothing to break here.
"""
import dataclasses

import numpy as np
import pytest

from bench import harness, registry


def small(name):
    cell = registry.workload(name)
    cell["config_data"].update(sizes=[16, 25], graph_trials=2, num_trials=8)
    cell["num_iters"] = 150
    cell["backend"] = "jax"        # the kernels are not what these faults break
    return cell


def unchanged(res, ens):
    """Every round returns its state unchanged."""
    x0 = ens.x0
    n = ens.node_counts
    xbar = np.stack([x0[i, :n[i]].mean(axis=0) for i in range(len(n))])
    mse0 = np.stack([((x0[i, :n[i]] - xbar[i]) ** 2).mean(axis=0) for i in range(len(n))])
    mse = np.repeat(mse0[:, None, :], res.mse.shape[1], axis=1)
    return dataclasses.replace(res, x_final=x0.copy(), mse=mse)


def half_left_out(res, ens):
    """Only the first half of the grid is run; the rest repeats its answers."""
    g = res.x_final.shape[0]
    h = g // 2
    x, m = res.x_final.copy(), res.mse.copy()
    x[h:], m[h:] = x[:g - h], m[:g - h]
    return dataclasses.replace(res, x_final=x, mse=m)


def altered(res, ens):
    """One answer per cell and column comes out wrong where it is produced."""
    x = res.x_final.copy()
    x[:, 0, :] += 0.05
    return dataclasses.replace(res, x_final=x)


def columns_wrong(res, ens):
    """The second half of the initial-condition columns (one tile of F) is
    read from the wrong tile: it repeats the first half's answers."""
    h = res.x_final.shape[2] // 2
    x, m = res.x_final.copy(), res.mse.copy()
    x[:, :, h:2 * h], m[:, :, h:2 * h] = x[:, :, :h], m[:, :, :h]
    return dataclasses.replace(res, x_final=x, mse=m)


def tail_floored(res, ens):
    """An error of 1e-5 of the start is added to the MSE: it stalls there,
    where the reference goes on down."""
    m = res.mse + 1e-5 * res.mse[:, :1, :]
    return dataclasses.replace(res, mse=m)


def run_with(monkeypatch, name, fault):
    from repro import sweep

    real = sweep.run_ensemble

    def broken(ens, **kw):
        res = real(ens, **kw)
        return res if fault is None else fault(res, ens)

    monkeypatch.setattr(sweep, "run_ensemble", broken)
    return harness.run(small(name), 2**31 + 77, 0.01, compile_cache=False)


CELLS = registry.workload_names()


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(monkeypatch, name):
    out = run_with(monkeypatch, name, None)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1


@pytest.mark.parametrize("fault", [unchanged, half_left_out, altered, columns_wrong,
                                   tail_floored])
@pytest.mark.parametrize("name", CELLS)
def test_broken_run_is_not_correct(monkeypatch, name, fault):
    out = run_with(monkeypatch, name, fault)
    assert not out["correct"], out["checks"]
