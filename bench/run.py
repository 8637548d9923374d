#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one JSON result line.

    python3 bench/run.py --workload sensor_field.lossy --seed 7 --seconds 36 --trace 0

From the root of a checkout of the repository. ``--trace 0`` reports the
cell's end-to-end metrics (``sweep_s``, ``peak_hbm_gb``, ``setup_s``);
``--trace 1`` records a profiler trace of the window and reports the
cell's per-layer metrics (``bench/metrics``) and the device's busy and
window seconds, with a breakdown of device time and idle gaps. Both check
the answers against the float64 reference and print each number compared
beside its limit, as the last lines of standard error and under ``checks``
at the end of the result line. Exits 2, printing no result, where JAX finds
no TPU or another number of chips than the cell asks for, or where the
program under test (``src/``) is not in the checkout.

A one-chip cell asks the TPU runtime for one chip before JAX starts
(``TPU_VISIBLE_CHIPS``), so that on a host with more the sweep runs the
same single-device program as on a host with one: the engine spreads a
grid over every device it sees.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


ONE_CHIP = {"TPU_VISIBLE_CHIPS": "0", "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness, registry

    try:
        cell = registry.workload(args.workload)
    except KeyError as e:
        return fail(str(e))
    if not (ROOT / "src" / "repro" / "sweep").is_dir():
        return fail(f"the program under test is not in this checkout ({ROOT / 'src'})")

    chips = int(cell["chips"])
    if chips == 1:
        for key, value in ONE_CHIP.items():
            os.environ.setdefault(key, value)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) != chips:
        return fail(f"the cell needs {chips} chips, JAX found {len(devices)}")

    out = harness.run(cell, args.seed, args.seconds, trace=bool(args.trace),
                      device=devices[0], t_start=T_START)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": chips,
              "memory_peak_bytes": out["peak"]}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"], "device": device}
    if args.trace:
        red = out["trace"]
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        line["breakdown"] = {"device_ops": red.device_ops, "idle_gaps": red.idle_gaps}
    line["checks"] = out["checks"]
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
