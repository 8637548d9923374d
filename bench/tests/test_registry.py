"""The harness is driven by files: BENCHMARK.json agrees with them, and a
cell, a metric, a configuration or a graph family is added by adding a
file, editing none."""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

from bench import registry

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_matches_the_files():
    assert sorted(w["name"] for w in BENCH["workloads"]) == registry.workload_names()
    assert sorted(c["name"] for c in BENCH["configs"]) == registry.config_names()
    for c in BENCH["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"] and data["reduced"] == c["reduced"]
        assert data["source"] == c["source"]
    for w in BENCH["workloads"]:
        cell = registry.workload(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == \
            (w["config"], w["traffic"], w["chips"])
    files = registry.metrics()
    assert sorted(m["name"] for m in BENCH["per_layer"]) == sorted(files)
    for m in BENCH["per_layer"]:
        mod = files[m["name"]]
        assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES,
                sorted(mod.WORKLOADS)) == (m["unit"], m["better"], m["source"],
                                           m["layer"], m["moves"], sorted(m["workloads"]))
    for entry in BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] \
            + BENCH["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]


def digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file() and "__pycache__" not in p.parts}


def test_a_cell_and_a_metric_are_added_as_files(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", root,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = digest(root)
    cell = json.loads((root / "cells" / "sensor_field.lossy.json").read_text())
    cell.update(traffic="heavy_loss", dynamics=["bernoulli:0.3"])
    (root / "cells" / "sensor_field.heavy_loss.json").write_text(json.dumps(cell))
    (root / "metrics" / "sweeps_done.py").write_text(
        'LAYER = "host"\nUNIT = "sweeps"\nBETTER = "higher"\nSOURCE = "program_counter"\n'
        'MOVES = "sweep_s"\nWORKLOADS = ["sensor_field.heavy_loss"]\n\n\n'
        "def read(ctx):\n    return ctx.sweeps\n")
    after = digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert "sensor_field.heavy_loss" in registry.workload_names(root)
    added = registry.workload("sensor_field.heavy_loss", root)
    assert added["config_data"]["name"] == "sensor_field"
    assert list(registry.metrics_for("sensor_field.heavy_loss", root)) == ["sweeps_done"]
    assert "sweeps_done" not in registry.metrics_for("sensor_field.lossy", root)


RING = '''"""Ring: node i joined to node i + 1, and node n - 1 to node 0."""
import numpy as np

from . import canonical

RANDOM = False


def edges(n, args, rng):
    i = np.arange(n)
    return canonical(i, (i + 1) % n)
'''

TAKE = '''import json, sys
from bench import registry, reference, work
assert reference.graphs.__file__.startswith(sys.argv[1]), reference.graphs.__file__
cell = registry.workload("overlay_ba.lossy")
cfg = cell["config_data"]
cells = reference.graphs.cells(cfg, cell)
shapes = work.shapes_from_reference(cfg, cell)
print(json.dumps({"cells": [[c.graph.family, c.graph.n, c.graph.draw, c.algorithm, c.design,
                             c.dynamics, len(c.graph.edges), c.weights is not None]
                            for c in cells],
                  "shapes": [[s.n, s.edges, s.algorithm, s.lossy] for s in shapes]}))
'''


def test_a_configuration_and_a_graph_family_are_added_as_files(tmp_path):
    """A Barabási–Albert deployment, its cell and a family the reference did
    not have, added to a copy of the harness, are taken by the reference and
    the work count of that copy."""
    root = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", root,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = digest(root)
    config = json.loads((root / "configs" / "sensor_field.json").read_text())
    config.update(name="overlay_ba", topologies=["ba:3", "ring"], sizes=[40, 64],
                  designs=["asymptotic"], graph_trials=2, num_trials=4, layout="sparse")
    (root / "configs" / "overlay_ba.json").write_text(json.dumps(config))
    cell = json.loads((root / "cells" / "sensor_field.lossy.json").read_text())
    cell.update(config="overlay_ba", traffic="lossy", num_iters=50)
    (root / "cells" / "overlay_ba.lossy.json").write_text(json.dumps(cell))
    (root / "reference" / "families" / "ring.py").write_text(RING)
    after = digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == [
        "cells/overlay_ba.lossy.json", "configs/overlay_ba.json", "reference/families/ring.py"]

    out = subprocess.run([sys.executable, "-c", TAKE, str(tmp_path)], cwd=tmp_path,
                         env={**os.environ, "PYTHONPATH": str(tmp_path)},
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    got = json.loads(out.stdout.splitlines()[-1])
    graphs = [("ba:3", n, d) for n in (40, 64) for d in (0, 1)] + \
        [("ring", n, 0) for n in (40, 64)]
    # accel: one cell per graph, design and dynamics; push-sum: per graph, dynamics
    want = [[fam, n, d, algo, "asymptotic" if algo == "accel" else algo, dyn,
             3 * (n - 3) if fam == "ba:3" else n, algo == "accel"]
            for algo in ("accel", "push_sum") for fam, n, d in graphs
            for dyn in cell["dynamics"]]
    assert got["cells"] == want
    assert got["shapes"] == [[n, e, algo, dyn != "static"]
                             for _f, n, _d, algo, _des, dyn, e, _w in want]
