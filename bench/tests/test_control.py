"""The control, one precision below what each configuration states, fails
the comparison that the program passes (``bench.control``)."""
import dataclasses

import pytest

from bench import check, control, registry


def cell_at(name, **cfg):
    cell = registry.workload(name)
    cell["config_data"].update(cfg)
    return cell


def fails(cell, gaps):
    ok, _ = check.verdict(dataclasses.asdict(gaps),
                          dict(cell["check"]["limits"], overflow_mismatch=0))
    return not ok


@pytest.mark.parametrize("seed", [1, 2**31 + 9])
@pytest.mark.parametrize("name", registry.workload_names())
def test_dense_control_at_high_fails(name, seed):
    """Three bf16 passes per product, as Precision.HIGH on the MXU, over the
    cell's 2000 rounds on its chains (where the accelerated designs are most
    sensitive to rounding)."""
    cell = cell_at(name, topologies=["chain"])
    assert cell["control"] == "high" and cell["num_iters"] == 2000
    assert fails(cell, control.readings(cell, seed))


def test_control_in_bf16_fails_on_a_large_grid():
    """The control for plain float32 rounds, on a grid above the exact-spectrum
    size, where both sides estimate lambda_2 by power iteration."""
    cell = cell_at("sensor_field.static", topologies=["grid2d"], sizes=[2500],
                   designs=["memoryless", "asymptotic"], layout="sparse")
    assert fails(cell, control.readings(cell, 3, "bf16"))


def test_control_at_float32_passes():
    """The same rounds with whole float32 products stay inside the limits:
    what fails the control is its precision, not the harness."""
    cell = cell_at("sensor_field.lossy", topologies=["chain"])
    assert not fails(cell, control.readings(cell, 5, "f32"))
