"""Benchmark entry point: one suite per paper figure/table + the systems
extensions. Prints CSV blocks; saves CSV + BENCH_*.json under
experiments/bench/.

    PYTHONPATH=src python -m benchmarks.run [--full | --quick | --check]

Default sizes keep a single-core CPU run in minutes; --full uses paper-scale
trial counts; --quick is the CI smoke tier — kernel microbenches plus the
sweep engine at toy sizes, a couple of minutes on a shared runner, emitting
the BENCH_*.json artifacts that the workflow uploads.

--check is the CI perf gate: re-run the kernel microbenches and compare each
kernel row's us_per_call against the tracked repo-root baseline
``BENCH_kernel_perf.json`` (the baseline is read BEFORE the fresh run
overwrites it), exiting non-zero on any >1.5x regression. The ratio is
overridable via REPRO_PERF_GATE_RATIO for machines much slower than the one
that stamped the baseline; in CI the committed baseline is stashed before
the smoke benches rewrite the root JSON. Comparisons are like-for-like
only: every row carries an execution ``mode`` tag ("compiled" or
"pallas-interpret") and rows whose mode differs from the baseline's are
skipped, never ratioed — interpret-vs-compiled timings are different
experiments.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# rows gated by --check: the warmed kernel/engine rows. The simulator_* rows
# include jit trace+compile time and host eigensolves — tracked, not gated.
GATE_PREFIXES = ("gossip_round", "segment_round", "sweep_", "ssd_")


def _trajectory_path() -> str:
    from .common import OUT_DIR

    return os.path.join(OUT_DIR, "TRAJECTORY.jsonl")


def _append_trajectory(rows, path: str | None = None) -> None:
    """Append one per-commit line of gate-row timings to TRAJECTORY.jsonl.

    The line holds (commit, unix_time, {bench: {us_per_call, mode}}) for
    every GATE_PREFIXES row — the tracked perf trajectory that accumulates
    across commits (the BENCH_*.json files only ever hold the latest run).
    Called from the bench tiers, never from --check: a gate run must not
    stamp its own machine-local timings into the history it gates against.
    """
    entry = {
        r["bench"]: {"us_per_call": float(r["us_per_call"]),
                     "mode": r.get("mode")}
        for r in rows if r["bench"].startswith(GATE_PREFIXES)
    }
    if not entry:
        return
    commit = os.environ.get("GITHUB_SHA", "").strip()
    if not commit:
        import subprocess

        try:
            commit = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                capture_output=True, text=True, timeout=10,
            ).stdout.strip()
        except (OSError, subprocess.SubprocessError):
            commit = ""
    line = {"commit": commit, "unix_time": time.time(), "rows": entry}
    path = path or _trajectory_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(line, sort_keys=True) + "\n")


def _trajectory_rows(path: str | None = None) -> dict:
    """bench name -> most recent trajectory row dict ({us_per_call, mode}).

    Later lines win; unparseable lines are skipped (the file is appended by
    many commits on many machines — one bad line must not kill the gate).
    Missing file -> empty dict: the gate then runs purely off the baseline.
    """
    path = path or _trajectory_path()
    out: dict = {}
    try:
        with open(path) as f:
            for raw in f:
                raw = raw.strip()
                if not raw:
                    continue
                try:
                    line = json.loads(raw)
                    rows = line["rows"]
                except (ValueError, KeyError, TypeError):
                    continue
                if isinstance(rows, dict):
                    for name, r in rows.items():
                        if isinstance(r, dict) and "us_per_call" in r:
                            out[name] = {"bench": name, **r}
    except OSError:
        return {}
    return out


def _gate_rows(fresh, base_rows, ratio_max):
    """Pure comparison core of the perf gate (unit-tested in test_perf_gate).

    ``fresh`` is the list of freshly timed row dicts, ``base_rows`` maps
    bench name -> baseline row dict. Rows are compared like-for-like only:
    a row whose execution ``mode`` differs from the baseline's (e.g. the
    baseline was stamped in pallas-interpret on CPU and this run compiled on
    a TPU, or vice versa) is SKIPPED — cross-mode timings differ by orders
    of magnitude and would otherwise hard-fail (or silently ratchet) the
    gate. Rows missing a mode on either side gate as before (pre-mode-tag
    baselines stay comparable). Returns (report_lines, failures).
    """
    lines, failures = [], []
    for r in fresh:
        name = r["bench"]
        if not name.startswith(GATE_PREFIXES):
            continue
        b = base_rows.get(name)
        if b is None:
            lines.append(f"{name}: NEW (no baseline row, passes)")
            continue
        mode_f, mode_b = r.get("mode"), b.get("mode")
        if mode_f is not None and mode_b is not None and mode_f != mode_b:
            lines.append(
                f"{name}: SKIP (mode {mode_b} -> {mode_f}; cross-mode "
                f"timings are not comparable)")
            continue
        ratio = float(r["us_per_call"]) / float(b["us_per_call"])
        verdict = "FAIL" if ratio > ratio_max else "ok"
        lines.append(
            f"{name}: {float(b['us_per_call']):.0f} -> "
            f"{float(r['us_per_call']):.0f} us ({ratio:.2f}x) {verdict}")
        if ratio > ratio_max:
            failures.append((name, ratio))
    return lines, failures


def _check(baseline_path: str) -> int:
    try:
        with open(baseline_path) as f:
            base_text = f.read()
        base = json.loads(base_text)
    except FileNotFoundError:
        print(f"perf gate: no baseline at {baseline_path} — run "
              f"`python -m benchmarks.run --quick` and commit the root "
              f"BENCH_kernel_perf.json to start the trajectory")
        return 1
    # The tracked per-commit trajectory widens the baseline: rows that only
    # exist in TRAJECTORY.jsonl (e.g. a bench added after the last committed
    # baseline refresh) still gate. The baseline JSON wins on conflicts — it
    # is the deliberately stamped reference, the trajectory the running log.
    base_rows = {**_trajectory_rows(), **{r["bench"]: r for r in base["rows"]}}

    from . import kernel_perf

    fresh = kernel_perf.run()
    # kernel_perf's emit() just rewrote the root BENCH_kernel_perf.json —
    # which may BE the tracked baseline we gate against. Restore it: a gate
    # run must never self-ratchet the baseline (two sequential 1.4x
    # regressions would otherwise each pass against the drifted file) nor
    # leave the tracked file dirty with machine-local timings. Refreshing
    # the baseline stays a deliberate act: run --quick and commit.
    if os.path.exists(baseline_path):
        with open(baseline_path, "w") as f:
            f.write(base_text)
    ratio_max = float(os.environ.get("REPRO_PERF_GATE_RATIO", "1.5"))
    print(f"### perf gate (>{ratio_max}x vs {baseline_path})")
    lines, failures = _gate_rows(fresh, base_rows, ratio_max)
    for line in lines:
        print(line)
    if failures:
        print(f"perf gate FAILED: {len(failures)} kernel row(s) regressed "
              f">{ratio_max}x: " + ", ".join(f"{n} {r:.2f}x" for n, r in failures))
        return 1
    print("perf gate passed")
    return 0


def _quick() -> None:
    # fig_robustness is NOT in this tier: CI runs it as its own named step
    # (python -m benchmarks.fig_robustness --quick) so the masked-kernel
    # path's cost and failures stay attributable, and running it here too
    # would double the most expensive interpret-mode bench of the job.
    from . import fig34_scaling, kernel_perf

    _append_trajectory(kernel_perf.run())
    fig34_scaling.run(
        trials=2,
        rgg_sizes=(30, 50),
        chain_sizes=(10, 20, 30),
        backend="pallas",
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale trials (300) instead of CI-scale")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: kernel perf + toy sweep only")
    ap.add_argument("--check", action="store_true",
                    help="perf gate: fresh kernel bench vs the tracked baseline")
    ap.add_argument("--baseline", default=None,
                    help="baseline JSON for --check (default: repo-root "
                         "BENCH_kernel_perf.json)")
    args = ap.parse_args()
    full = args.full
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.check:
        from .common import ROOT_DIR

        baseline = args.baseline or os.path.join(ROOT_DIR, "BENCH_kernel_perf.json")
        sys.exit(_check(baseline))

    t0 = time.time()
    if args.quick:
        _quick()
        print(f"benchmarks (quick) done in {time.time()-t0:.0f}s")
        return

    from . import (fig1_mse, fig2_polyfilt, fig34_scaling, fig5_finite_time,
                   init_cost, kernel_perf, roofline_table, sync_cost)

    fig1_mse.run(trials=300 if full else 8, iters=400)
    fig2_polyfilt.run(trials=100 if full else 5, iters=600)
    fig34_scaling.run(trials=20 if full else 3,
                      rgg_sizes=(50, 100, 150, 200) if full else (50, 100, 150),
                      chain_sizes=(20, 40, 60, 80, 100) if full else (20, 40, 60, 80))
    fig5_finite_time.run(sizes=(50, 100, 150) if full else (40, 80), trials=10 if full else 3)
    init_cost.run()
    sync_cost.run()
    _append_trajectory(kernel_perf.run())
    roofline_table.run(mesh="single")
    roofline_table.run(mesh="multi")
    print(f"benchmarks done in {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
