"""Float64 rounds of many cells at once, each with its own link schedule.

Every cell is one diagonal block of a sparse matrix whose entries are
rewritten each round: for an edge (i, j) that is up, W_ij and W_ji; for one
that is down, the dropped weight returns to a diagonal entry, the
receiver's for the two-tap family (row sums survive) and the sender's for
push-sum (column sums survive). Two-tap cells then take
x' = a W_eff x + b x + c x_prev; push-sum cells multiply a value block and
a mass block (started at 1) by the same W_eff and display value / mass.
The MSE of a cell is the mean over its nodes of (display - x0 average)^2.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import scipy.sparse as sp

MASS_FLOOR = 1e-12


@dataclasses.dataclass
class Block:
    """One cell's rounds: weights in edge form, schedule, coefficients, x0."""

    edges: np.ndarray      # (E, 2) canonical i < j
    w_ij: np.ndarray       # (E,) weight of arc j -> i (row i, column j)
    w_ji: np.ndarray       # (E,) weight of arc i -> j
    diag: np.ndarray       # (n,)
    renorm: str            # "receiver" | "sender"
    coef: tuple            # (a, b, c)
    bits: np.ndarray       # (T, E) bool, True = up
    x0: np.ndarray         # (n, k)
    ratio: bool = False    # push-sum (value, mass) pair

    @property
    def n(self) -> int:
        return len(self.diag)


@dataclasses.dataclass
class System:
    """Blocks assembled into one state of ``total`` rows and k columns."""

    total: int
    rows: np.ndarray       # (A,) receiving row of each arc
    cols: np.ndarray       # (A,) sending row of each arc
    arc_w: np.ndarray      # (A,) base weight of each arc
    drop_to: np.ndarray    # (A,) diagonal that takes the arc's weight when down
    arc_e: np.ndarray      # (A,) column of ``bits`` that the arc follows
    diag: np.ndarray       # (total,)
    coef: np.ndarray       # (total, 3)
    x0: np.ndarray         # (total, k) values, and 1 in mass rows
    bits: np.ndarray       # (T, E_total) bool
    disp_rows: np.ndarray  # rows displayed, block after block
    den_rows: np.ndarray   # their mass rows; ``total`` stands for 1
    starts: np.ndarray     # first display row of each block
    counts: np.ndarray     # nodes of each block
    xbar: np.ndarray       # (sum n, k) each block's x0 average per display row


def assemble(blocks: list[Block]) -> System:
    k = blocks[0].x0.shape[1]
    # state rows: each block's value rows, then a mass copy for ratio blocks
    parts, off = [], 0
    for b in blocks:
        parts.append((off, None if not b.ratio else off + b.n))
        off += b.n * (2 if b.ratio else 1)
    total = off
    rows, cols, arc_w, drop_to, arc_e = [], [], [], [], []
    e_off = 0
    diag = np.zeros(total)
    coef = np.zeros((total, 3))
    x = np.zeros((total, k))
    for b, (v0, m0) in zip(blocks, parts):
        for base in ((v0,) if m0 is None else (v0, m0)):
            i, j = b.edges[:, 0] + base, b.edges[:, 1] + base
            # arc j -> i lands in row i; arc i -> j in row j
            rows += [i, j]
            cols += [j, i]
            arc_w += [b.w_ij, b.w_ji]
            recv = b.renorm == "receiver"
            drop_to += [i if recv else j, j if recv else i]
            arc_e += [np.arange(len(i)) + e_off] * 2
            diag[base:base + b.n] = b.diag
            coef[base:base + b.n] = b.coef
        x[v0:v0 + b.n] = b.x0
        if m0 is not None:
            x[m0:m0 + b.n] = 1.0
        e_off += len(b.edges)
    return System(
        total=total, rows=np.concatenate(rows), cols=np.concatenate(cols),
        arc_w=np.concatenate(arc_w), drop_to=np.concatenate(drop_to),
        arc_e=np.concatenate(arc_e), diag=diag, coef=coef, x0=x,
        bits=np.concatenate([b.bits for b in blocks], axis=1),
        disp_rows=np.concatenate([np.arange(v0, v0 + b.n)
                                  for b, (v0, _) in zip(blocks, parts)]),
        den_rows=np.concatenate([
            np.full(b.n, total) if m0 is None else np.arange(m0, m0 + b.n)
            for b, (_, m0) in zip(blocks, parts)]),
        starts=np.concatenate([[0], np.cumsum([b.n for b in blocks])[:-1]]).astype(np.int64),
        counts=np.asarray([b.n for b in blocks]),
        xbar=np.concatenate([np.broadcast_to(b.x0.mean(axis=0), (b.n, k))
                             for b in blocks]))


def split(s: System, final: np.ndarray, traj: np.ndarray):
    """Per-block (x_final (n, k)) and (mse (T + 1, k)) lists."""
    xs = [final[a:a + n] for a, n in zip(s.starts, s.counts)]
    return xs, [traj[:, i] for i in range(len(s.counts))]


def simulate(blocks: list[Block], rounds: int):
    """Returns ([x_final (n, k)], [mse (T + 1, k)]), one entry per block."""
    s = assemble(blocks)
    k = s.x0.shape[1]
    ar = np.arange(s.total)
    all_r, all_c = np.concatenate([s.rows, ar]), np.concatenate([s.cols, ar])
    order = np.lexsort((all_c, all_r))
    indptr = np.concatenate([[0], np.cumsum(np.bincount(all_r, minlength=s.total))])
    mat = sp.csr_matrix((np.zeros(len(order)), all_c[order], indptr),
                        shape=(s.total, s.total))
    a, b, c = (s.coef[:, q:q + 1] for q in range(3))
    use_b, use_c = bool(b.any()), bool(c.any())
    static = bool(s.bits.all())
    plain = bool((s.den_rows == s.total).all()) and s.disp_rows.size == s.total

    def display(x):
        if plain:
            return x
        ext = np.vstack([x, np.ones((1, k))])
        num, den = ext[s.disp_rows], ext[s.den_rows]
        safe = np.abs(den) > MASS_FLOOR
        return np.where(safe, num, 0.0) / np.where(safe, den, 1.0)

    def mse(x):
        d = display(x) - s.xbar
        d *= d
        return np.add.reduceat(d, s.starts, axis=0) / s.counts[:, None]

    def set_round(up):
        live = s.arc_w if up is None else s.arc_w * up[s.arc_e]
        dg = s.diag if up is None else \
            s.diag + np.bincount(s.drop_to, s.arc_w - live, s.total)
        mat.data[:] = np.concatenate([live, dg])[order]

    if static:
        set_round(None)
    x = s.x0.copy()
    xp = x.copy()
    traj = [mse(x)]
    for t in range(rounds):
        if not static:
            set_round(s.bits[t])
        y = mat @ x
        y *= a
        if use_b:
            y += b * x
        if use_c:
            y += c * xp
        x, xp = y, x
        traj.append(mse(x))
    return split(s, display(x), np.stack(traj))
