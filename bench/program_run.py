#!/usr/bin/env python3
"""Run one benchmark cell traced and read its trace by the program's own
spans and scope as well.

    python3 bench/program_run.py --workload sensor_field.lossy --seed 7 --seconds 36

From the root of a checkout, on the chip. Runs ``bench/run.py`` with
``--trace 1`` in this process and prints its result line, then one more
JSON line: ``bench.program_trace``'s reduction of the same trace, per sweep
of the window. ``expand_us`` is the device time of the in-scan mask
expansion (``sweep.expand``) per round, beside ``round_us`` (the whole
round); ``batch_host_s`` is ``run_batch``'s host time (``sweep.run_batch``
less ``sweep.wait``); ``masks_draw_s`` the per-cell draws of
``build_round_masks`` (``sweep.masks.draw``); ``idle_s`` the first chip's
idle time by the innermost span open over it, and ``idle_in_sweep_spans``
the share of that idle time inside a ``sweep.*`` span. The harness removes
its trace once it has reduced it; this reads the file when the harness
looks it up (``bench.trace.find``). Exits as ``bench/run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def per_sweep(red, prog, rounds: int) -> dict:
    """The program's numbers of one traced window, per sweep."""
    k = max(prog.sweeps, 1)
    idle = sum(prog.idle.values())
    own = sum(t for name, t in prog.idle.items() if name.startswith("sweep."))
    return {
        "sweeps": prog.sweeps,
        "expand_us": prog.expand_s / (k * rounds) * 1e6,
        "round_us": red.scan_busy_s / (k * rounds) * 1e6,
        "batch_host_s": prog.batch_host_s / k,
        "masks_draw_s": prog.masks_draw_s / k,
        "idle_s": {name: t / k for name, t in sorted(prog.idle.items())},
        "idle_in_sweep_spans": 100.0 * own / idle if idle else None,
        "span_counts": prog.span_counts,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import program_trace, registry, run, trace

    rounds = int(registry.workload(args.workload)["num_iters"])
    found = []
    find = trace.find

    def find_and_read(trace_dir: str) -> str:
        path = find(trace_dir)
        found.append(program_trace.load_and_reduce(path))
        return path

    trace.find = find_and_read
    try:
        rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(args.seconds), "--trace", "1"])
    finally:
        trace.find = find
    if rc or not found:
        return rc or 1
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "program": per_sweep(*found[0], rounds)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
