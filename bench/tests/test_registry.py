"""The harness is driven by files: BENCHMARK.json agrees with them, and a
cell or a metric is added by adding a file, editing none."""
import hashlib
import json
import re
import shutil
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
