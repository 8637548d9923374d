"""One run of one cell: set-up, the measured window, then the check.

Set-up builds the cell's sweep grid once (``repro.sweep.build_ensemble``),
then runs one warm sweep at the window's shapes, so that everything the
window runs is compiled, or loaded from the persistent compile cache, before
it starts. The window then repeats sweeps until ``seconds`` of them have
been timed. A sweep is what a user waits for once the grid is built:
``build_round_masks`` samples the link schedule, ``run_ensemble`` runs every
round on the device (``backend`` of the cell, ``pallas`` for the kernels)
and returns the x_final and MSE arrays on the host. Each sweep gets inputs
no sweep before it had: fresh initial conditions and a fresh schedule seed,
both drawn from ``--seed`` and the sweep's index, the initial conditions
outside the timed span. Compilations of the sweep program inside the window
are counted (``repro.sweep.trace_count``) and must be none.

After the window the device's peak memory is read, the program's arrays are
dropped, and a sample of the sweeps' answers is held to the float64
reference (``bench.check``).
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import time
import zlib
from pathlib import Path

import numpy as np

from . import check, reference, registry, trace as trace_mod, work

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"
WARM = 1 << 20             # sweep index of the warm-up sweep's inputs


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events (a persistent-cache hit skips the backend compile)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.total += duration


def mask_seed(seed: int, sweep: int) -> int:
    """The schedule seed of one sweep of one run."""
    return int(seed) * (1 << 21) + int(sweep)


def initial_conditions(layout: list, n_max: int, f: int, seed: int, sweep: int) -> np.ndarray:
    """(G, n_max, F) float32: i.i.d. N(0, 1) per node and column, one block
    per graph shared by its cells, zero on padded nodes."""
    x0 = np.zeros((len(layout), n_max, f), np.float32)
    drawn = {}
    for i, (fam, n, d, *_rest) in enumerate(layout):
        key = (fam, n, d)
        if key not in drawn:
            rng = np.random.default_rng(
                [int(seed), int(sweep), zlib.crc32(repr(key).encode("utf-8"))])
            drawn[key] = rng.standard_normal((n, f), dtype=np.float32)
        x0[i, :n] = drawn[key]
    return x0


def sweep_spec(cell: dict):
    from repro.sweep import SweepSpec

    cfg = cell["config_data"]
    return SweepSpec(
        topologies=tuple(cfg["topologies"]), sizes=tuple(cfg["sizes"]),
        designs=tuple(cfg["designs"]), graph_trials=cfg["graph_trials"],
        num_trials=cfg["num_trials"], seed=cfg["graph_seed"], layout=cfg["layout"],
        init=cell["init"], dynamics=tuple(cell["dynamics"]),
        algorithms=tuple(cell["algorithms"]))


@dataclasses.dataclass
class Sample:
    sweep: int
    cells: np.ndarray
    cols: np.ndarray
    x0: list
    x: list
    mse: list


@dataclasses.dataclass
class Context:
    """What a per-layer metric's ``read`` gets."""

    cell: dict
    sweeps: int
    rounds: int
    masks_s: list
    trace: trace_mod.Reduction | None
    flops: float
    bytes: float
    peaks: dict | None


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run(cell: dict, seed: int, seconds: float, trace: bool = False, device=None,
        t_start: float | None = None, compile_cache: bool = True) -> dict:
    """One run of ``cell``: its metrics, the numbers compared with their
    limits, ``correct``, the sweeps attempted and failed, the device's peak
    bytes, and with ``trace`` the trace's reduction."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro import sweep
    from repro.sweep import engine

    t_start = time.perf_counter() if t_start is None else t_start
    if compile_cache:
        # one fixed directory in the checkout: only a cell's first run there
        # compiles, and two checkouts share nothing
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clock = CompileClock()
    cfg = cell["config_data"]
    rounds, backend = cell["num_iters"], cell["backend"]
    layout = reference.graphs.layout(cfg, cell)
    counts = np.asarray([n for _f, n, *_ in layout])
    ens = sweep.build_ensemble(sweep_spec(cell))
    log(f"set-up: grid of {ens.num_configs} cells built at "
        f"{time.perf_counter() - t_start:.3f} s")
    f = ens.x0.shape[2]
    chk = cell["check"]

    def one_sweep(k: int):
        x0 = initial_conditions(layout, ens.n_max, f, seed, k)
        grid = dataclasses.replace(ens, x0=x0)
        t0 = time.perf_counter()
        with TraceAnnotation("bench.sweep"):
            with TraceAnnotation("bench.masks"):
                masks = sweep.build_round_masks(grid, rounds, seed=mask_seed(seed, k))
            t1 = time.perf_counter()
            with TraceAnnotation("bench.run_ensemble"):
                res = sweep.run_ensemble(grid, num_iters=rounds, backend=backend,
                                         round_masks=masks)
        t2 = time.perf_counter()
        return x0, res, t2 - t0, t1 - t0

    one_sweep(WARM)
    setup_s = time.perf_counter() - t_start
    log(f"set-up: {setup_s:.3f} s, of which compiling {clock.total:.3f} s")

    compiles0 = engine.trace_count()
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the harness's spans are enough
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    samples, masks_s, timed, k = [], [], 0.0, 0
    while timed < seconds:
        x0, res, dt, dm = one_sweep(k)
        timed += dt
        masks_s.append(dm)
        cells, cols = check.sample(layout, f, seed, k, chk["cells_per_group"],
                                   chk["columns"])
        samples.append(Sample(
            k, cells, cols,
            [x0[i, :counts[i]][:, cols].astype(np.float64) for i in cells],
            [res.x_final[i, :counts[i]][:, cols] for i in cells],
            [res.mse[i][:, cols] for i in cells]))
        del res, x0
        k += 1
    if trace:
        jax.profiler.stop_trace()
    window_compiles = engine.trace_count() - compiles0
    stats = device.memory_stats() if device is not None else None
    peak = int(stats["peak_bytes_in_use"]) if stats else None
    log(f"window: {k} sweeps in {timed:.3f} s timed; {window_compiles} compiles")
    del ens
    gc.collect()

    out = {}
    if trace:
        red = trace_mod.reduce(*trace_mod.load(trace_mod.find(str(TRACE_DIR))))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        pk = work.peaks(device.device_kind) if device is not None else None
        shapes = work.shapes_from_reference(cfg, cell)
        flops, bytes_ = work.sweep_work(shapes, f, rounds,
                                        pk["onchip_bytes"] if pk else float("inf"))
        ctx = Context(cell, k, rounds, masks_s, red, flops * k, bytes_ * k, pk)
        metrics = {}
        for name, mod in registry.metrics_for(cell["name"]).items():
            value = mod.read(ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": mod.UNIT}
        out["metrics"] = metrics
        out["trace"] = red
    else:
        out["metrics"] = {
            "sweep_s": {"value": timed / k, "unit": "s"},
            "peak_hbm_gb": {"value": (peak or 0) / 1e9, "unit": "GB"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }

    t_ref = time.perf_counter()
    ref_cells = reference.graphs.cells(cfg, cell)
    gaps, failed = check.Gaps(), 0
    limits = dict(chk["limits"], overflow_mismatch=0, window_compiles=0)
    checked = check.sweeps(k, seed, chk.get("sweeps"))
    for smp in (samples[j] for j in checked):
        blocks = [reference.block(ref_cells[i], x, rounds, mask_seed(seed, smp.sweep))
                  for i, x in zip(smp.cells, smp.x0)]
        xr, mr = reference.rounds.simulate(blocks, rounds)
        g = check.Gaps()
        for q in range(len(blocks)):
            g.merge(check.compare(smp.x[q], smp.mse[q], smp.x0[q], xr[q], mr[q]))
        ok, _ = check.verdict(dataclasses.asdict(g), limits)
        failed += not ok
        gaps.merge(g)
    log(f"reference: {len(checked)} of {k} sweeps checked in "
        f"{time.perf_counter() - t_ref:.3f} s")
    numbers = dict(dataclasses.asdict(gaps), window_compiles=window_compiles)
    correct, checks = check.verdict(numbers, limits)
    out.update(correct=correct, attempted=k, failed=failed, checks=checks, peak=peak)
    return out
