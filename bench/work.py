"""The least work a sweep needs, and the least time a chip could take for it.

Counted from the real graphs of the configuration (node count n, edge count
E, the F initial conditions, T rounds), never from padded or launched
shapes, so no layout, backend or tile choice can change it:

* mixing: 2 flops per nonzero of W (n + 2E) per column, per round; push-sum
  mixes a value and a mass state;
* taps: one multiply for a != 1, a multiply-add for each of b != 0, c != 0;
* renormalising a lossy round: 2 flops per edge per round;
* push-sum's display: a division per node and column, per round;
* the MSE: 3 flops per node and column, per round.

Bytes are each input and output of the sweep moved once: W's nonzeros
(float32 values), one bit per edge and round of a lossy schedule, x0 and
x_final (float32), the MSE trajectory (float32). A cell whose carried state
(taps x n x F float32) does not fit the chip's on-chip memory
(``peaks.json``) reads and writes it every round besides. A cell whose
nonzeros (a float32 value and an int32 column index each) do not fit it
reads them every round in place of once.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
F32 = 4
I32 = 4


@dataclasses.dataclass(frozen=True)
class CellShape:
    """What the count needs of one cell: sizes and which coefficients act."""

    n: int
    edges: int
    algorithm: str             # "accel" | "push_sum"
    taps: tuple                # (a != 1, b != 0, c != 0)
    lossy: bool


def peaks(device_kind: str) -> dict:
    with open(PEAKS) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} (have {sorted(table)})")
    return table[device_kind]


def design_taps(design: str) -> tuple:
    """Which of (a != 1, b != 0, c != 0) a two-tap design has."""
    return {"memoryless": (False, False, False), "ls": (True, True, True),
            "asymptotic": (True, False, True)}.get(design, (False, False, False))


def sweep_work(cells: list[CellShape], f: int, rounds: int, onchip_bytes: float):
    """(flops, bytes) of one sweep over ``cells`` with F columns, T rounds."""
    flops = bytes_ = 0.0
    for c in cells:
        nnz = c.n + 2 * c.edges
        states = 2 if c.algorithm == "push_sum" else 1
        a, b, cc = c.taps
        per_round = states * 2 * nnz * f
        per_round += (a + 2 * b + 2 * cc) * c.n * f
        per_round += 2 * c.edges * c.lossy * states
        per_round += c.n * f * (c.algorithm == "push_sum")
        per_round += 3 * c.n * f
        flops += per_round * rounds
        graph = (F32 + I32) * nnz
        bytes_ += rounds * graph if graph > onchip_bytes else F32 * nnz
        bytes_ += c.edges * rounds / 8 if c.lossy else 0
        bytes_ += F32 * (2 * c.n * f + (rounds + 1) * f)
        carried = (1 + cc) * states if c.algorithm == "accel" else states
        state = carried * c.n * f * F32
        if state > onchip_bytes:
            # read every carried tap, write the new state, each round
            bytes_ += rounds * (state + states * c.n * f * F32)
    return flops, bytes_


def roofline(flops: float, bytes_: float, pk: dict) -> tuple[float, str]:
    """(least seconds, which term bounds them)."""
    t_c, t_m = flops / pk["peak_flops"], bytes_ / pk["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def shapes_from_reference(config: dict, cell: dict) -> list[CellShape]:
    """The count's inputs from the configuration itself (no program)."""
    from .reference import graphs

    drawn = {g.key: g for g in graphs.draw_graphs(
        config["topologies"], config["sizes"], config["graph_trials"],
        config["graph_seed"])}
    return [CellShape(n, len(drawn[(fam, n, d)].edges), algo,
                      design_taps(des) if algo == "accel" else (False, False, False),
                      dyn != "static")
            for fam, n, d, algo, des, dyn in graphs.layout(config, cell)]
