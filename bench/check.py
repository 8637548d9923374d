"""The comparison that decides ``correct``.

After the window, a sample of the sweeps' answers, drawn from the seed, is
held to the float64 reference (``bench.reference``): of every sweep, or of
as many sweeps as the cell's check names, a few cells of each (topology,
algorithm, dynamics) group and a few initial-condition columns.

The numbers, each against the limit in the cell's file:

* ``x_gap`` — the largest |x_final - reference| of a sampled column, over
  the larger of max |x0| and max |reference x_final|;
* ``mse_gap`` — the largest gap of sqrt(MSE) over all rounds, over the
  larger of sqrt(MSE(0)) and the reference's sqrt(MSE(t));
* ``tail_gap`` — the largest |log10 MSE(t) - log10 reference MSE(t)| over
  the rounds where the reference's MSE(t) is at least ``TAIL_FLOOR`` times
  its MSE(0): the converged tail, which ``mse_gap`` measures against the
  start and so cannot see. A program whose MSE carries an error of 1e-5 of
  the start, and so stalls there where the reference goes on down, reads
  log10(2) = 0.3 here and only 3.2e-3 in ``mse_gap``;
* ``overflow_mismatch`` — sampled columns whose reference outgrows float32
  (a two-tap design that diverges under loss) where the program stays
  finite and small, or the reverse; the limit is 0;
* ``window_compiles`` — programs compiled inside the window; the limit is 0.

Rows of a column are compared only while the reference stays inside float32
range (``F32_SAFE``): past it only the overflow is compared.
"""
from __future__ import annotations

import dataclasses

import numpy as np

F32_SAFE = 1e30
# The engine's float32 trajectories floor near 1e-8 of MSE(0); the tail is
# read well above that floor, where a sound run still tracks the reference.
TAIL_FLOOR = 1e-5


def sample(layout: list[tuple], num_cols: int, seed: int, sweep: int,
           per_group: int, columns: int) -> tuple[np.ndarray, np.ndarray]:
    """(cells, columns) of one sweep to hold to the reference."""
    rng = np.random.default_rng([int(seed), int(sweep), 0xC4EC])
    groups: dict[tuple, list[int]] = {}
    for i, (fam, _n, _d, algo, _des, dyn) in enumerate(layout):
        groups.setdefault((fam, algo, dyn), []).append(i)
    picks = [int(i) for key in sorted(groups)
             for i in rng.choice(groups[key], min(per_group, len(groups[key])),
                                 replace=False)]
    cols = np.sort(rng.choice(num_cols, min(columns, num_cols), replace=False))
    return np.asarray(sorted(picks)), cols


def sweeps(done: int, seed: int, most: int | None) -> list[int]:
    """The sweeps of a run to hold to the reference: every one, or ``most``
    of them drawn from the seed where the cell's check names a number."""
    if most is None or done <= most:
        return list(range(done))
    rng = np.random.default_rng([int(seed), 0x5EE9])
    return sorted(int(k) for k in rng.choice(done, most, replace=False))


@dataclasses.dataclass
class Gaps:
    x_gap: float = 0.0
    mse_gap: float = 0.0
    tail_gap: float = 0.0
    overflow_mismatch: int = 0

    def merge(self, other: "Gaps") -> None:
        self.x_gap = max(self.x_gap, other.x_gap)
        self.mse_gap = max(self.mse_gap, other.mse_gap)
        self.tail_gap = max(self.tail_gap, other.tail_gap)
        self.overflow_mismatch += other.overflow_mismatch


def _nanmax(a: np.ndarray) -> float:
    """max that treats NaN as infinitely far off."""
    a = np.where(np.isnan(a), np.inf, a)
    return float(a.max()) if a.size else 0.0


def _log_gap(prog: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """|log10 prog - log10 ref|, infinite where the program's value is not a
    positive finite number."""
    good = np.isfinite(prog) & (prog > 0)
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.where(good, np.abs(np.log10(np.where(good, prog, 1.0)) - np.log10(ref)),
                        np.inf)


def compare(x_p, mse_p, x0, x_r, mse_r) -> Gaps:
    """One cell's sampled columns: program (x_p (n, k), mse_p (T+1, k))
    against the reference (x_r, mse_r) from the same x0 (n, k)."""
    g = Gaps()
    x_p, mse_p = np.asarray(x_p, np.float64), np.asarray(mse_p, np.float64)
    for q in range(x0.shape[1]):
        ref_max = float(np.abs(x_r[:, q]).max())
        if ref_max < F32_SAFE and np.isfinite(ref_max):
            scale = max(float(np.abs(x0[:, q]).max()), ref_max)
            g.x_gap = max(g.x_gap, _nanmax(np.abs(x_p[:, q] - x_r[:, q])) / scale)
        else:
            prog_max = _nanmax(np.abs(x_p[:, q]))
            g.overflow_mismatch += int(prog_max < F32_SAFE)
        rows = mse_r[:, q] < F32_SAFE
        root_r = np.sqrt(mse_r[rows, q])
        scale = np.maximum(np.sqrt(mse_r[0, q]), root_r)
        gap = np.abs(np.sqrt(mse_p[rows, q]) - root_r) / np.maximum(scale, 1e-300)
        g.mse_gap = max(g.mse_gap, _nanmax(gap))
        tail = rows & (mse_r[:, q] >= TAIL_FLOOR * mse_r[0, q]) & (mse_r[:, q] > 0)
        g.tail_gap = max(g.tail_gap, _nanmax(_log_gap(mse_p[tail, q], mse_r[tail, q])))
        if not rows.all():
            # past float32 range the program has to have overflowed as well
            tail = mse_p[~rows, q]
            g.overflow_mismatch += int(np.all(np.isfinite(tail) & (tail < F32_SAFE)))
    return g


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """{name: {"value", "limit"}} and whether every number is within its limit."""
    out, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name, 0)
        out[name] = {"value": value, "limit": limit}
        ok &= bool(value <= limit)
    return ok, out
