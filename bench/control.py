"""The control: the reference's rounds on the device, one precision lower.

The check is only worth something if it fails a computation a step less
precise than the configuration states. ``run`` computes the same blocks as
``reference.rounds.simulate`` with jax, in one of two precisions:

* ``"high"`` — for float32 at HIGHEST (the dense configuration): every
  product of the mixing step as three bf16 passes (hi*hi + hi*lo + lo*hi,
  what ``Precision.HIGH`` does on the MXU), accumulated in float32; the
  taps stay float32.
* ``"bf16"`` — for plain float32 (the sparse configuration): weights and
  state held in bfloat16, each round rounded back to bfloat16.

``"f32"`` keeps every product whole: the same rounds at the configurations'
own precision, which has to pass, so that what fails the control is its
precision and not the harness.

The split is written out with explicit roundings (``lax.reduce_precision``)
rather than left to a precision flag, so the control computes the same
numbers on the CPU, where XLA ignores the flag, and on the TPU, where XLA
would otherwise keep a value it converts to bf16 and back at f32.
"""
from __future__ import annotations

import numpy as np

from .reference import rounds as ref_rounds

PRECISIONS = ("high", "bf16", "f32")


def run(blocks: list, num_rounds: int, precision: str):
    import jax
    import jax.numpy as jnp

    if precision not in PRECISIONS:
        raise ValueError(f"unknown control precision {precision!r}")
    s = ref_rounds.assemble(blocks)
    f32 = jnp.float32
    state = jnp.bfloat16 if precision == "bf16" else f32

    def to_bf16(v):
        # an explicit rounding: XLA may drop a convert pair f32 -> bf16 -> f32
        # as excess precision, and does so on the TPU
        return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)

    def split3(v):
        hi = to_bf16(v)
        return hi, to_bf16(v - hi)

    def mul(w, v):
        if precision == "f32":
            return w * v
        if precision == "bf16":
            return w.astype(f32) * v.astype(f32)
        (wh, wl), (vh, vl) = split3(w), split3(v)
        return wh * vh + wh * vl + wl * vh

    rows, cols = jnp.asarray(s.rows), jnp.asarray(s.cols)
    drop_to, arc_e = jnp.asarray(s.drop_to), jnp.asarray(s.arc_e)
    arc_w = jnp.asarray(s.arc_w, state)
    diag = jnp.asarray(s.diag, state)
    a, b, c = (jnp.asarray(s.coef[:, q:q + 1], state) for q in range(3))
    disp_rows, den_rows = jnp.asarray(s.disp_rows), jnp.asarray(s.den_rows)
    xbar = jnp.asarray(s.xbar, f32)
    seg = jnp.asarray(np.repeat(np.arange(len(s.counts)), s.counts))
    counts = jnp.asarray(s.counts, f32)[:, None]
    static = bool(s.bits.all())
    total, k = s.x0.shape

    def display(x):
        ext = jnp.concatenate([x.astype(f32), jnp.ones((1, k), f32)])
        num, den = ext[disp_rows], ext[den_rows]
        safe = jnp.abs(den) > ref_rounds.MASS_FLOOR
        return jnp.where(safe, num, 0.0) / jnp.where(safe, den, 1.0)

    def mse(x):
        d = display(x) - xbar
        return jax.ops.segment_sum(d * d, seg, len(s.counts)) / counts

    def step(carry, up):
        x, xp = carry
        live = arc_w if up is None else arc_w * up[arc_e].astype(state)
        dg = diag + jax.ops.segment_sum((arc_w - live).astype(f32), drop_to,
                                        total).astype(state)
        y = jax.ops.segment_sum(mul(live[:, None], x[cols]), rows, total) \
            + mul(dg[:, None], x)
        xn = (a.astype(f32) * y + b.astype(f32) * x.astype(f32)
              + c.astype(f32) * xp.astype(f32)).astype(state)
        return (xn, x), mse(xn)

    @jax.jit
    def go(x0, bits):
        xs = None if static else bits
        (x, _), traj = jax.lax.scan(lambda cr, u: step(cr, u), (x0, x0), xs,
                                    length=num_rounds)
        return display(x), jnp.concatenate([mse(x0)[None], traj])

    x0 = jnp.asarray(s.x0, state)
    bits = None if static else jnp.asarray(s.bits[:num_rounds], jnp.uint8)
    final, traj = go(x0, bits)
    return ref_rounds.split(s, np.asarray(final, np.float64),
                            np.asarray(traj, np.float64))


def readings(cell: dict, seed: int, precision: str | None = None, sweep: int = 0):
    """The comparison's numbers for the control on one seed's sample: the
    cells, columns, initial conditions and schedule a run's sweep ``sweep``
    would check, computed by the control instead of the program."""
    from . import check, harness, reference

    cfg, chk, rounds = cell["config_data"], cell["check"], cell["num_iters"]
    lay = reference.graphs.layout(cfg, cell)
    cells = reference.graphs.cells(cfg, cell)
    idx, cols = check.sample(lay, cfg["num_trials"], seed, sweep,
                             chk["cells_per_group"], chk["columns"])
    n_max = max(n for _f, n, *_ in lay)
    x0 = harness.initial_conditions(lay, n_max, cfg["num_trials"], seed, sweep)
    blocks = [reference.block(cells[i], x0[i, :cells[i].graph.n][:, cols].astype(np.float64),
                              rounds, harness.mask_seed(seed, sweep)) for i in idx]
    xr, mr = ref_rounds.simulate(blocks, rounds)
    xc, mc = run(blocks, rounds, precision or cell["control"])
    gaps = check.Gaps()
    for q, b in enumerate(blocks):
        gaps.merge(check.compare(xc[q], mc[q], b.x0, xr[q], mr[q]))
    return gaps


def main(argv=None) -> int:
    """Print the control's numbers at the cell's own size, seed by seed:
    ``python -m bench.control --workload grid_1m.static --seeds 1 2 3``."""
    import argparse
    import dataclasses
    import json
    import time

    import jax

    from . import registry

    ap = argparse.ArgumentParser(description=main.__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", choices=PRECISIONS)
    args = ap.parse_args(argv)
    cell = registry.workload(args.workload)
    dev = jax.devices()[0]
    for seed in args.seeds:
        t0 = time.perf_counter()
        g = readings(cell, seed, args.precision)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "precision": args.precision or cell["control"],
                          "platform": dev.platform, "kind": dev.device_kind,
                          "seconds": time.perf_counter() - t0,
                          "numbers": dataclasses.asdict(g),
                          "limits": cell["check"]["limits"]}), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
