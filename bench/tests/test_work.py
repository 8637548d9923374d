"""``roofline.round``'s count depends on the graphs alone: not on the
weight layout, the backend, or padding."""
import dataclasses

import numpy as np
import pytest

from bench import harness, registry, work


def small(name, **cfg):
    cell = registry.workload(name)
    cell["config_data"].update(cfg)
    return cell


CFG = dict(topologies=["chain", "grid2d", "rgg"], sizes=[36, 49], graph_trials=2,
           graph_seed=4, num_trials=8)


def ensemble(cell, layout):
    from repro.sweep import build_ensemble

    return build_ensemble(dataclasses.replace(harness.sweep_spec(cell), layout=layout))


def shapes_from_ensemble(ens):
    """The count's inputs read off a built sweep grid, in either layout."""
    return [work.CellShape(int(ens.node_counts[i]), len(ens.edge_index(i)), c.algorithm,
                           work.design_taps(c.design) if c.algorithm == "accel"
                           else (False, False, False),
                           c.dynamics != "static")
            for i, c in enumerate(ens.configs)]


def count(shapes, cell, onchip=float("inf")):
    return work.sweep_work(shapes, cell["config_data"]["num_trials"], cell["num_iters"],
                           onchip)


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_count_is_the_same_in_both_layouts_and_backends(backend):
    """The pallas backend pads N and F to its tiles before the scan; the
    count read off the grid it launches is the count of the real graphs."""
    from repro.sweep import engine

    cell = small("sensor_field.lossy", **CFG)
    cell["algorithms"] = ["accel"]      # the sparse layout runs the two-tap family
    want = count(work.shapes_from_reference(cell["config_data"], cell), cell)
    for layout in ("dense", "sparse"):
        ens = ensemble(cell, layout)
        if backend == "pallas" and layout == "dense":
            ws, x0, _tiles, n, _f = engine._prep_pallas_dense(ens.ws, ens.x0)
            assert n > ens.n_max
            ens = dataclasses.replace(ens, ws=ws, x0=x0)
        assert count(shapes_from_ensemble(ens), cell) == want


def test_count_ignores_padding():
    from repro.sweep import build_ensemble, merge_ensembles

    cell = small("sensor_field.lossy", **CFG)
    ens = ensemble(cell, "dense")
    big = build_ensemble(dataclasses.replace(harness.sweep_spec(cell), sizes=(100,),
                                             topologies=("chain",)))
    merged = merge_ensembles(ens, big)          # pads every cell to 100 nodes
    assert merged.n_max > ens.n_max
    head = shapes_from_ensemble(merged)[:ens.num_configs]
    assert count(head, cell) == count(shapes_from_ensemble(ens), cell)


def test_count_by_hand_and_state_traffic():
    """One memoryless chain cell, 10 nodes, 9 edges, F = 2, T = 3."""
    shape = work.CellShape(10, 9, "accel", (False, False, False), False)
    flops, bytes_ = work.sweep_work([shape], 2, 3, float("inf"))
    nnz = 10 + 18
    assert flops == 3 * (2 * nnz * 2 + 3 * 10 * 2)
    assert bytes_ == 4 * nnz + 4 * (2 * 10 * 2 + 4 * 2)
    # a cell whose state does not fit on chip moves it every round, and its
    # nonzeros (value and column index) are read every round in place of once
    _, streamed = work.sweep_work([shape], 2, 3, 10.0)
    assert streamed == bytes_ + 3 * (2 * 10 * 2 * 4) + 3 * 8 * nnz - 4 * nnz


def test_count_by_hand_with_the_graph_off_chip():
    """A lossy push-sum cell, 10 nodes, 12 edges, F = 2, T = 5, whose 34
    nonzeros (8 bytes each: 272) pass an on-chip memory of 200 bytes while
    its state (2 x 10 x 2 float32: 160) fits."""
    shape = work.CellShape(10, 12, "push_sum", (False, False, False), True)
    flops, bytes_ = work.sweep_work([shape], 2, 5, 200.0)
    nnz = 10 + 24
    per_round = 2 * 2 * nnz * 2 + 2 * 12 * 2 + 10 * 2 + 3 * 10 * 2
    assert flops == 5 * per_round
    bits = 12 * 5 / 8
    x0_x_mse = 4 * (2 * 10 * 2 + 6 * 2)
    assert bytes_ == 5 * 8 * nnz + bits + x0_x_mse
    # on a chip that holds the nonzeros they are read once, as float32 values
    assert work.sweep_work([shape], 2, 5, 272.0) == (flops, 4 * nnz + bits + x0_x_mse)


# (flops, bytes) of one sweep of each cell at its own sizes on a TPU v5 lite,
# as counted before graphs too large for on-chip memory were counted apart
SENSOR_FIELD_WORK = {
    "sensor_field.lossy": (345553700000.0, 290401096.0),
    "sensor_field.static": (107636736000.0, 103624536.0),
}


@pytest.mark.parametrize("name", sorted(SENSOR_FIELD_WORK))
def test_sensor_field_counts_are_unchanged(name):
    cell = registry.workload(name)
    onchip = work.peaks("TPU v5 lite")["onchip_bytes"]
    shapes = work.shapes_from_reference(cell["config_data"], cell)
    assert count(shapes, cell, onchip) == SENSOR_FIELD_WORK[name]


def test_peaks_table_refuses_an_unknown_chip():
    assert work.peaks("TPU v5 lite")["peak_flops"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("TPU v99")


def test_roofline_names_its_bound():
    pk = work.peaks("TPU v5 lite")
    assert work.roofline(197e12, 1.0, pk) == (1.0, "compute")
    t, bound = work.roofline(1.0, 819e9, pk)
    assert bound == "memory" and np.isclose(t, 1.0)
