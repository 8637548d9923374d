"""The sweep engine's own instrumentation: host spans on the profiler's clock
around ``run_batch``'s phases and the mask draws, the ``sweep.expand`` scope
in the scan's HLO ``op_name``, and counters whose deltas follow from the
shapes of a run."""
import glob
import os
import re

import numpy as np
import pytest

import jax

from repro.kernels import ops as kops
from repro.sweep import (
    SweepSpec,
    build_ensemble,
    build_round_masks,
    engine,
    run_batch,
    run_ensemble,
    trace_count,
)

LOSSY = SweepSpec(topologies=("chain",), sizes=(8, 12),
                  designs=("memoryless", "asymptotic"), num_trials=4,
                  dynamics=("static", "bernoulli:0.1"))
PHASES = ["sweep.prep", "sweep.put", "sweep.launch", "sweep.wait", "sweep.fetch"]
F32 = 4


def _host_spans(trace_dir) -> list[tuple[int, int, str]]:
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    pd = ProfileData.from_file(path)
    return sorted((ev.start_ns, ev.end_ns, ev.name)
                  for plane in pd.planes if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith("sweep."))


def _delta(fn):
    before = engine.counters()
    out = fn()
    after = engine.counters()
    return out, {k: after[k] - before[k] for k in after}


def _up(x: int, m: int) -> int:
    return -(-x // m) * m


def test_spans_of_a_lossy_sweep_nest_in_order(tmp_path):
    ens = build_ensemble(LOSSY)
    run_ensemble(ens, num_iters=3, backend="jax",
                 round_masks=build_round_masks(ens, 3, seed=1))   # compiles
    with jax.profiler.trace(str(tmp_path)):
        masks = build_round_masks(ens, 3, seed=2)
        run_ensemble(ens, num_iters=3, backend="jax", round_masks=masks)
    spans = _host_spans(tmp_path)

    lossy_cells = sum(c.dynamics != "static" for c in ens.configs)
    (ms, me, _), = [s for s in spans if s[2] == "sweep.masks"]
    draws = [s for s in spans if s[2] == "sweep.masks.draw"]
    assert len(draws) == lossy_cells == 4
    assert all(ms <= s <= e <= me for s, e, _ in draws)

    (bs, be, _), = [s for s in spans if s[2] == "sweep.run_batch"]
    phases = [s for s in spans if s[2] in PHASES]
    assert [name for _, _, name in phases] == PHASES
    assert me <= bs <= phases[0][0] and phases[-1][1] <= be
    assert all(a[1] <= b[0] for a, b in zip(phases, phases[1:]))


def test_a_static_grid_draws_no_masks(tmp_path):
    ens = build_ensemble(SweepSpec(topologies=("chain",), sizes=(8,),
                                   designs=("memoryless",), num_trials=2))
    with jax.profiler.trace(str(tmp_path)):
        assert build_round_masks(ens, 3) is None
    assert [name for _, _, name in _host_spans(tmp_path)] == ["sweep.masks"]


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_counters_follow_the_dense_shapes(backend):
    ens = build_ensemble(LOSSY)
    t = 3
    masks = build_round_masks(ens, t, seed=1)
    _, d = _delta(lambda: run_ensemble(ens, num_iters=t, backend=backend,
                                       round_masks=masks))
    g, n, f = ens.x0.shape
    n_pad, f_pad = n, f
    if backend == "pallas":
        bm, bk, bf = kops.round_tiles(n, f, g, tune=True)
        n_pad, f_pad = _up(n, max(bm, bk)), _up(f, bf)
    e = masks.bits.shape[2]
    c = ens.coefs.shape[1]
    assert d["batches"] == 1
    assert d["bytes_in"] == (F32 * (g * n_pad * n_pad + g * n_pad * f_pad + g * n_pad
                                    + g + g * c)
                             + t * g * e + 4 * g * e * 2)
    assert d["bytes_out"] == F32 * (g * n_pad * f_pad + g * (t + 1) * f_pad)
    counts = np.asarray(ens.node_counts)
    assert d["entries_real"] == int((counts ** 2).sum()) * f
    assert d["entries_padded"] == g * n_pad * n_pad * f_pad
    if backend == "pallas":
        # sizes 8 and 12 in equal halves, padded to the kernel tile
        assert d["entries_real"] / d["entries_padded"] == \
            (64 + 144) / 2 * f / (n_pad * n_pad * f_pad)


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_counters_follow_the_sparse_shapes(backend):
    ens = build_ensemble(SweepSpec(topologies=("grid2d",), sizes=(16, 25),
                                   designs=("memoryless", "asymptotic"),
                                   num_trials=4, layout="sparse"))
    t = 3
    _, d = _delta(lambda: run_ensemble(ens, num_iters=t, backend=backend))
    g, n, f = ens.x0.shape
    c = ens.coefs.shape[1]
    ec = np.asarray(ens.edge_counts)
    e_max = ens.edges.shape[1]
    assert d["batches"] == 1
    assert d["entries_real"] == 2 * int(ec.sum()) * f
    if backend == "pallas":
        bm, bd, bf = kops.segment_tiles(n, f, g, tune=True)
        n_pad, f_pad = _up(n, bm), _up(f, bf)
        degree = max(np.bincount(ens.edges[i, :ec[i]].ravel()).max() for i in range(g))
        slots = _up(degree, min(bd, degree))
        ell = 4 * g * slots * n_pad * 4 + g * n_pad * F32   # nbr wgt wrev slot, diag
        assert d["entries_padded"] == g * slots * n_pad * f_pad
    else:
        n_pad, f_pad = n, f
        # src, dst (int32), weights (f32), edge ids (int32): 2 E_max each; diag
        ell = 4 * g * 2 * e_max * 4 + g * n * F32
        assert d["entries_padded"] == g * 2 * e_max * f
    assert d["bytes_in"] == ell + F32 * (g * n_pad * f_pad + g * n_pad + g + g * c)
    assert d["bytes_out"] == F32 * (g * n_pad * f_pad + g * (t + 1) * f_pad)


def test_trial_chunks_count_each_batch():
    ens = build_ensemble(LOSSY)
    _, whole = _delta(lambda: run_ensemble(ens, num_iters=2, backend="jax"))
    _, chunked = _delta(lambda: run_ensemble(ens, num_iters=2, backend="jax",
                                             trial_chunk=2))
    assert chunked["batches"] == 2 and whole["batches"] == 1
    for k in ("bytes_out", "entries_real", "entries_padded"):
        assert chunked[k] == whole[k], k


def _scan_hlo(dynamic: bool) -> str:
    g, n, f, t, e = 2, 6, 3, 2, 5
    ws = np.broadcast_to(np.eye(n, dtype=np.float32), (g, n, n))
    x0 = np.ones((g, n, f), np.float32)
    lowered = engine._sweep_scan.lower(
        ws, x0, np.ones((g, n), np.float32), np.full(g, 1 / n, np.float32),
        np.tile(np.float32([1, 0, 0]), (g, 1)), num_iters=t, use_kernels=False,
        bits=np.ones((t, g, e), np.uint8) if dynamic else None,
        eidx=np.zeros((g, e, 2), np.int32) if dynamic else None)
    return lowered.compile().as_text()


@pytest.mark.parametrize("dynamic", [True, False])
def test_mask_expansion_carries_its_scope_in_op_name(dynamic):
    names = re.findall(r'op_name="([^"]*)"', _scan_hlo(dynamic))
    assert names
    scoped = [x for x in names if "/sweep.expand/" in x]
    assert bool(scoped) == dynamic
    assert all(x.startswith("jit(_sweep_scan)/while/body/") for x in scoped)


def test_trace_count_is_a_view_of_the_counters():
    g, n, f = 3, 7, 5
    rng = np.random.default_rng(0)
    ws = np.broadcast_to(np.eye(n, dtype=np.float32), (g, n, n))
    coefs = np.tile(np.float32([1, 0, 0]), (g, 1))
    t0 = trace_count()
    for _ in range(2):
        run_batch(ws, rng.standard_normal((g, n, f), dtype=np.float32), coefs,
                  num_iters=4)
    assert trace_count() - t0 == 1          # one compile per signature
    run_batch(ws, rng.standard_normal((g, n, f + 1), dtype=np.float32), coefs,
              num_iters=4)
    assert trace_count() - t0 == 2
    snapshot = engine.counters()
    assert snapshot["traces"] == trace_count()
    snapshot["traces"] += 5                  # a copy: the store is untouched
    assert engine.counters()["traces"] == trace_count()
