#!/usr/bin/env python3
"""Record a small profiler trace of the sweep engine on the chip, for the
trace-reduction tests in this directory.

    python3 bench/tests/record_trace.py <out.xplane.pb> [<cell>]

From the root of a checkout, on one TPU. Two dense sweeps of a lossy grid
(chain and 2-D grid, N = 64, 2 designs, static + Bernoulli(0.1), G = 8,
F = 128, T = 40) and two static sparse sweeps of a 4096-node 2-D grid
(G = 2, F = 128, T = 40), each inside the harness's spans (``bench.sweep``
holding ``bench.masks`` and ``bench.run_ensemble``), after one warm sweep
each outside the trace. Given a benchmark cell, two sweeps of that cell's
grid instead, at its shapes but T = 40.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

ROUNDS = 40


def main(out: str, cell: str | None = None) -> int:
    import jax
    from jax.profiler import TraceAnnotation

    from repro import sweep

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    if cell is not None:
        sys.path.insert(0, str(ROOT))
        from bench import harness, registry

        grids = [sweep.build_ensemble(harness.sweep_spec(registry.workload(cell)))]
    else:
        grids = [
            sweep.build_ensemble(sweep.SweepSpec(
                topologies=("chain", "grid2d"), sizes=(64,),
                designs=("memoryless", "asymptotic"), num_trials=128,
                dynamics=("static", "bernoulli:0.1"))),
            sweep.build_ensemble(sweep.SweepSpec(
                topologies=("grid2d",), sizes=(4096,),
                designs=("memoryless", "asymptotic"), num_trials=128,
                layout="sparse")),
        ]

    def one_sweep(ens, seed):
        with TraceAnnotation("bench.sweep"):
            with TraceAnnotation("bench.masks"):
                masks = sweep.build_round_masks(ens, ROUNDS, seed=seed)
            with TraceAnnotation("bench.run_ensemble"):
                sweep.run_ensemble(ens, num_iters=ROUNDS, backend="pallas",
                                   round_masks=masks)

    for ens in grids:
        one_sweep(ens, 0)
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for ens in grids:
        for seed in (1, 2):
            one_sweep(ens, seed)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True))[-1]
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"record_trace: wrote {out} ({os.path.getsize(out)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
