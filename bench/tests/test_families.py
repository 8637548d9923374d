"""Graph families are files of ``bench/reference/families``: the Barabási–
Albert family draws the sweep grid's graphs edge for edge, their schedules,
spectra and coefficients, and the move to files changed no graph."""
import functools
import hashlib

import numpy as np
import pytest

from bench import harness, reference, registry
from bench.tests.test_reference import SMALL, gaps_against_reference, small_cell

BA = dict(topologies=["ba:3", "ba:10"], sizes=[200, 2000], graph_trials=2, graph_seed=6,
          designs=["memoryless"], num_trials=2)
SCHEDULE_SEED = 2**33 + 17


def edge_digest(graphs) -> str:
    h = hashlib.sha256()
    for g in graphs:
        h.update(repr(g.key).encode())
        h.update(np.ascontiguousarray(g.edges, dtype="<i8").tobytes())
    return h.hexdigest()


def schedule_digest(graphs) -> str:
    h = hashlib.sha256()
    for g in graphs:
        bits = reference.masks.edge_bits("bernoulli:0.1", SCHEDULE_SEED, g.key, 8, len(g.edges))
        h.update(np.packbits(bits).tobytes())
    return h.hexdigest()


def draw(cfg):
    return reference.graphs.draw_graphs(cfg["topologies"], cfg["sizes"],
                                        cfg["graph_trials"], cfg["graph_seed"])


# sha256 of every graph's key and edges, and of 8 rounds of each graph's
# Bernoulli(0.1) schedule, as the reference drew them with its families
# written inline in graphs.py
PINNED = {
    "sensor_field": ("def55eefe18c05d8fcae2d63262b9113a0e34275653b2d41b7daeabedc51eca4",
                     "02fe315958b45b40853da88b1a30c58b96eaaf021bcff80c4a463f8992df0d89"),
    "SMALL": ("f77273fe56dd20be369f5d5dfde332f58f700dc0735af101375d5ba489dfdcb7",
              "ec25e6afd177e1c0c38b40cd275d675d5e6682477b4e772e77aeaa14fe510038"),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_moving_the_families_to_files_changed_no_graph(name):
    cfg = registry.config("sensor_field") if name == "sensor_field" else SMALL
    graphs = draw(cfg)
    assert (edge_digest(graphs), schedule_digest(graphs)) == PINNED[name]


def test_an_unknown_family_still_raises():
    for spec in ("torus2d", "ba/../chain", "_private"):
        with pytest.raises(ValueError, match="no reference for topology"):
            reference.graphs.draw_graphs([spec], [16], 1, 0)


@functools.cache
def ba_grid(layout):
    from repro.sweep import build_ensemble

    cell = small_cell("sensor_field.lossy", 10, **BA, layout=layout)
    cell["algorithms"], cell["dynamics"] = ["accel"], ["bernoulli:0.1"]
    return build_ensemble(harness.sweep_spec(cell)), cell


@pytest.mark.parametrize("layout", ["dense", "sparse"])
def test_ba_graphs_are_the_sweep_grids(layout):
    """Eight graphs from one generator, m in {3, 10}, N in {200, 2000}, two
    draws: a draw that consumed one number more or less shifts all after it."""
    ens, cell = ba_grid(layout)
    graphs = draw(cell["config_data"])
    assert [(c.topology, c.n, c.graph_index) for c in ens.configs] == \
        [g.key for g in graphs]
    for i, g in enumerate(graphs):
        np.testing.assert_array_equal(ens.edge_index(i), g.edges)
    m = [int(g.family.split(":")[1]) for g in graphs]
    assert [len(g.edges) for g in graphs] == [k * (g.n - k) for k, g in zip(m, graphs)]


@pytest.mark.parametrize("layout", ["dense", "sparse"])
def test_ba_schedules_are_the_sweep_grids(layout):
    from repro.sweep import build_round_masks

    ens, cell = ba_grid(layout)
    masks = build_round_masks(ens, 10, seed=SCHEDULE_SEED)
    for i, c in enumerate(reference.graphs.cells(cell["config_data"], cell)):
        e = len(c.graph.edges)
        np.testing.assert_array_equal(masks.idx[i, :e], c.graph.edges)
        bits = reference.masks.edge_bits(c.dynamics, SCHEDULE_SEED, c.graph.key, 10, e)
        np.testing.assert_array_equal(masks.bits[:, i, :e].astype(bool), bits)


def test_ba_spectrum_matches_the_sweep_grid():
    """Above 1024 nodes both sides estimate lambda_2 by power iteration."""
    from repro.sweep import build_ensemble

    cell = small_cell("sensor_field.static", 30, topologies=["ba:10"], sizes=[2000],
                      graph_trials=1, designs=["memoryless", "ls", "asymptotic"],
                      num_trials=2, layout="sparse")
    ens = build_ensemble(harness.sweep_spec(cell))
    cells = reference.graphs.cells(cell["config_data"], cell)
    assert len(cells) == 3
    for c, meta, row in zip(cells, ens.configs, ens.coefs):
        assert abs(c.weights.lam2 - meta.lam2) < 1e-12
        np.testing.assert_allclose(c.coef, row[:3], rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_sparse_ba_cell_passes_the_comparison(backend):
    cell = small_cell("sensor_field.lossy", 40, topologies=["ba:3"], sizes=[60, 90],
                      graph_trials=2, graph_seed=8, designs=["memoryless", "asymptotic"],
                      num_trials=4, layout="sparse")
    cell["algorithms"], cell["dynamics"] = ["accel", "push_sum"], ["bernoulli:0.1"]
    g = gaps_against_reference(cell, 2**31 + 9, backend)
    limits = cell["check"]["limits"]
    assert g.overflow_mismatch == 0
    assert g.x_gap < limits["x_gap"] / 10 and g.mse_gap < limits["mse_gap"] / 10


def test_a_schedule_drawn_in_blocks_is_the_one_draw(monkeypatch):
    """Rows drawn a block at a time are the stream of one (T, E) draw."""
    key = ("ba:3", 500, 1)
    want = reference.masks.graph_rng(5, key).random((9, 1497)) >= 0.25
    monkeypatch.setattr(reference.masks, "BLOCK_DRAWS", 4000)      # two rows a block
    np.testing.assert_array_equal(
        reference.masks.edge_bits("bernoulli:0.25", 5, key, 9, 1497), want)
