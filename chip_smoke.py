#!/usr/bin/env python3
"""Bring-up smoke of the consensus sweep engine on a TPU.

Drives the main path, ``repro.sweep.run_sweep``, on ``backend="pallas"``
(the compiled Mosaic round kernels) and on ``backend="jax"`` at the scale of
the deployments the engine serves, and checks the answers:

* dense phase — sensor-field averaging (the paper's Sec. V graphs): chain,
  grid2d and RGG with N = 64..196, three theta designs, static and
  Bernoulli(0.1) link loss, the two-tap ``accel`` and ``push_sum``
  (receiver- and sender-masked kernels), 3 graph draws, 256 initial
  conditions, 2000 rounds;
* sparse phase — a million-node grid2d overlay on the ELLPACK layout,
  static and Bernoulli(0.1), 128 initial conditions, 200 rounds.

Each phase passes only if pallas and jax agree to the tolerances of the
test suite, the node mean is conserved to f32 roundoff, cells of every
topology match a host float64 reference, and (dense) accel reaches the
average before memoryless gossip on chains. A phase that raises fails the
script. ``--chips 4`` runs only what exists across chips: the G-sharded
pallas sweep over a 4-device ``data`` mesh (each device steps its own block
of cells) against the same sweep on one device, and the in-mesh
``dist.accel_gossip`` against a ``psum`` mean.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips

The last line of standard output is one JSON object naming the device; the
script exits non-zero, printing no such line, when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

DENSE_ITERS = 2000
SPARSE_ITERS = 200
SPARSE_N = 1_000_000
REF_COLUMNS_DENSE = 8     # trial columns held to the float64 reference
REF_COLUMNS_SPARSE = 2
# the tolerances tests/test_sweep.py and tests/test_sparse.py hold
# pallas-vs-jax to, over at most the 60 rounds test_sweep.py runs
X_TOL = dict(rtol=1e-4, atol=1e-6)
MSE_TOL = dict(rtol=1e-4, atol=1e-8)
STRICT_ROUNDS = 60
# The node mean and the float64 reference are held to f32 roundoff: the
# tests' 1e-5 (tests/test_algorithms.py, 45 rounds) plus, per round, one f32
# rounding of each tap (|a| + |b| + |c| ulps of the largest initial value),
# times the gain of the two-tap mean mode on what is injected into it: the
# node mean follows m' = (a + b) m + c m_prev, whose roots are 1 and -c, so
# an error added once settles at 1 / (1 - |c|) times itself. Once a cell has
# converged every node rounds alike and the mean walks by about that much
# per round; a W rounded to bf16 (row sums off by ~1e-3) would drift ~1e-3
# per round before that gain and fail this by orders of magnitude.
F32_EPS = float(np.finfo(np.float32).eps)
REF_RTOL = 1e-4


def f32_drift(res, i) -> float:
    """The roundoff bound above for cell ``i`` of a sweep result."""
    c = res.configs[i]
    # accel's parameter row is its (a, b, c); push_sum runs (1, 0, 0) rounds
    coef = res.ensemble.coefs[i][:3] if c.algorithm == "accel" else (1, 0, 0)
    a, b, cc = (abs(float(v)) for v in coef)
    x0 = res.ensemble.x0[i][:c.n]
    return 1e-5 + (res.num_iters * F32_EPS * float(np.abs(x0).max())
                   * (a + b + cc) / (1.0 - cc))


def log(msg: str) -> None:
    print(msg, flush=True)


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


def timed_sweep(spec, clock, **kw):
    from repro.sweep import run_sweep

    c0, t0 = clock.total, time.perf_counter()
    res = run_sweep(spec, **kw)
    wall = time.perf_counter() - t0
    comp = clock.total - c0
    log(f"  backend={kw['backend']:6s} wall_s={wall:.3f} compile_s={comp:.3f} "
        f"run_s={wall - comp:.3f} (run_s includes the host build)")
    return res


def check_kernels_compiled():
    """The lowered rounds hold the Mosaic kernel, not the interpreter."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    z = jnp.zeros
    dense = jax.jit(lambda w, x, c: ops.batched_round_prim(w, bf=128)(x, x, c))
    txt = dense.lower(z((1, 128, 128)), z((1, 128, 128)), z((1, 3))).as_text()
    sparse = jax.jit(lambda nb, w, s, d, x, c: ops.batched_segment_round_prim(
        nb, w, s, d, bf=128)(x, x, c))
    txt2 = sparse.lower(z((1, 8, 128), jnp.int32), z((1, 8, 128)),
                        z((1, 8, 128), jnp.int32), z((1, 1, 128)),
                        z((1, 128, 128)), z((1, 3))).as_text()
    for name, t in (("dense", txt), ("sparse", txt2)):
        if "tpu_custom_call" not in t:
            raise RuntimeError(f"{name} round lowered without a tpu_custom_call")
    log("check kernels: dense and sparse rounds lower to tpu_custom_call")


def stable(res) -> np.ndarray:
    """(G,) cells whose MSE did not grow over the run.

    The two-tap recursion tuned for the static graph (alpha*) can diverge
    under link loss — the float64 host reference diverges with it (checked
    below) — and a trajectory that grows by 1e30 amplifies every rounding
    difference by as much, so two f32 backends can only agree on such a
    cell in kind: both must find it unstable.
    """
    return res.mse[:, -1].max(axis=-1) <= res.mse[:, 0].max(axis=-1)


def check_agree(r_p, r_j):
    """pallas == jax: the tests' tolerance over the tests' horizon, and over
    the whole run the same rtol with the f32 roundoff bound as atol.

    Past the tests' few dozen rounds a converged cell keeps rounding every
    node alike, and each backend sums in its own order, so the two means
    walk apart by up to twice ``f32_drift``; the tests' atol (1e-6) only
    holds while the signal dominates that walk. The count of cells that
    also meet it at the last round is logged.
    """
    ok = stable(r_j)
    if not np.array_equal(stable(r_p), ok):
        raise AssertionError("pallas and jax disagree on which cells diverge")
    head = slice(0, STRICT_ROUNDS + 1)
    np.testing.assert_allclose(r_p.mse[ok, head], r_j.mse[ok, head],
                               **MSE_TOL)
    strict = 0
    for i in np.flatnonzero(ok):
        tol = 2.0 * f32_drift(r_j, i)
        np.testing.assert_allclose(r_p.x_final[i], r_j.x_final[i],
                                   rtol=X_TOL["rtol"], atol=tol)
        np.testing.assert_allclose(r_p.mse[i], r_j.mse[i], rtol=MSE_TOL["rtol"],
                                   atol=tol * tol + MSE_TOL["atol"])
        strict += np.allclose(r_p.x_final[i], r_j.x_final[i], **X_TOL)
    d = float(np.abs(r_p.x_final[ok] - r_j.x_final[ok]).max())
    log(f"check pallas==jax: {int(ok.sum())} stable cells agree (mse to "
        f"rtol 1e-4/atol 1e-8 over rounds 0..{STRICT_ROUNDS}; x_final and mse "
        f"to the f32 bound over all rounds); max |dx| = {d:.3e}; {strict} of "
        f"them also within rtol 1e-4/atol 1e-6 at the last round; "
        f"{int((~ok).sum())} cells diverge on both")
    for i in np.flatnonzero(~ok):
        c = r_j.configs[i]
        log(f"  diverging: {c.topology} n={c.n} {c.algorithm} {c.design} "
            f"{c.dynamics} coefs={np.round(r_j.ensemble.coefs[i], 4).tolist()}")


def check_mean(res, label):
    ens = res.ensemble
    worst = 0.0   # drift over its bound, the largest across cells
    ok = stable(res)
    for i, c in enumerate(res.configs):
        if c.algorithm != "accel" or not ok[i]:
            # push_sum conserves total mass, not its display's mean; a
            # diverging cell's roundoff scales with its growth
            continue
        n = c.n
        gap = np.abs(res.x_final[i][:n].mean(axis=0, dtype=np.float64)
                     - ens.x0[i][:n].mean(axis=0, dtype=np.float64)).max()
        bound = f32_drift(res, i)
        if gap > bound:
            raise AssertionError(
                f"{label}: node mean of {c.topology} n={c.n} {c.design} "
                f"{c.dynamics} drifted by {gap:.3e} > {bound:.3e}")
        worst = max(worst, float(gap / bound))
    log(f"check mean conserved ({label}): worst drift {worst:.3f} of its "
        "f32 bound")


def dense_reference(res, masks, i, cols):
    from repro.core import algorithms as alg
    from repro.core import dynamics as dyn

    ens, c = res.ensemble, res.configs[i]
    n = c.n
    e = len(dyn.edge_index(ens.ws[i]))
    return alg.get_algorithm(c.algorithm).reference_run(
        ens.ws[i][:n, :n], ens.x0[i][:n, :cols], ens.coefs[i], res.num_iters,
        bits=masks.bits[:, i, :e], idx=masks.idx[i, :e], dtype=np.float64)


def sparse_reference(res, masks, i, cols):
    """Float64 masked two-tap rounds straight from the cell's edge list."""
    ens, c = res.ensemble, res.configs[i]
    n = c.n
    ne = int(ens.edge_counts[i]) if ens.edge_counts is not None \
        else ens.edges.shape[1]
    ei, ej = (ens.edges[i, :ne, k].astype(np.int64) for k in (0, 1))
    w = ens.edge_w[i, :ne].astype(np.float64)
    diag = ens.diag_w[i, :n].astype(np.float64)
    a, b, cc = (float(v) for v in ens.coefs[i][:3])
    x = ens.x0[i][:n, :cols].astype(np.float64)
    xp = x.copy()
    xbar = x.mean(axis=0)
    mse = [((x - xbar) ** 2).mean(axis=0)]
    for t in range(res.num_iters):
        wt = w * masks.bits[t, i, :ne]
        drop = np.bincount(ei, w - wt, n) + np.bincount(ej, w - wt, n)
        wx = np.stack([
            np.bincount(ei, wt * x[ej, k], n) + np.bincount(ej, wt * x[ei, k], n)
            for k in range(cols)], axis=1)
        wx += (diag + drop)[:, None] * x
        x, xp = a * wx + b * x + cc * xp, x
        mse.append(((x - xbar) ** 2).mean(axis=0))
    return x, np.stack(mse)


def check_reference(res, masks, cells, reference, cols):
    for i in cells:
        c = res.configs[i]
        x64, mse64 = reference(res, masks, i, cols)
        atol = f32_drift(res, i)
        np.testing.assert_allclose(res.x_final[i][:c.n, :cols], x64,
                                   rtol=REF_RTOL, atol=atol)
        # mse is the squared distance to the mean: the same drift, squared
        np.testing.assert_allclose(res.mse[i][:, :cols], mse64,
                                   rtol=REF_RTOL, atol=atol * atol + 1e-8)
        err = float(np.abs(res.x_final[i][:c.n, :cols] - x64).max())
        log(f"check f64 reference: {c.topology} n={c.n} {c.algorithm} "
            f"{c.design} {c.dynamics}: max |dx| = {err:.3e} (atol {atol:.2e})")


def check_accel_beats_memoryless(res):
    t = res.averaging_times(eps=1e-3).astype(np.float64)
    t[t < 0] = np.inf
    for n in sorted({c.n for c in res.configs if c.topology == "chain"}):
        def med(design):
            idx = res.cells(topology="chain", n=n, design=design,
                            dynamics="static", algorithm="accel")
            return float(np.median(t[idx]))
        acc, mem = med("asymptotic"), med("memoryless")
        if not (np.isfinite(acc) and acc < mem):
            raise AssertionError(
                f"chain n={n}: accel {acc} rounds does not beat memoryless {mem}")
        log(f"check chain n={n}: accel reaches eps=1e-3 in {acc:.0f} rounds, "
            f"memoryless in {mem:.0f}")


def dense_spec():
    from repro.sweep import SweepSpec

    return SweepSpec(
        topologies=("chain", "grid2d", "rgg"), sizes=(64, 100, 196),
        designs=("memoryless", "ls", "asymptotic"),
        dynamics=("static", "bernoulli:0.1"),
        algorithms=("accel", "push_sum"), graph_trials=3, num_trials=256,
        seed=0, layout="dense")


def one_device_mesh():
    import jax

    from repro.launch.mesh import make_mesh

    return make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])


def dense_phase(clock, mesh):
    from repro.sweep import build_round_masks

    spec = dense_spec()
    log(f"phase dense: {spec.topologies} N={spec.sizes} F={spec.num_trials} "
        f"T={DENSE_ITERS}")
    r_p = timed_sweep(spec, clock, num_iters=DENSE_ITERS, backend="pallas",
                      mesh=mesh)
    r_j = timed_sweep(spec, clock, num_iters=DENSE_ITERS, backend="jax",
                      mesh=mesh)
    log(f"  G={r_p.ensemble.num_configs} cells")
    check_agree(r_p, r_j)
    check_mean(r_p, "pallas")
    check_mean(r_j, "jax")
    masks = build_round_masks(r_p.ensemble, DENSE_ITERS, seed=spec.seed)
    ok = stable(r_p)
    cells = []
    for topo in spec.topologies:
        # per topology: the largest stable lossy cell of each algorithm,
        # the accelerated designs first
        for algo, designs in (("accel", ("asymptotic", "ls", "memoryless")),
                              ("push_sum", ("push_sum",))):
            picks = [i for d in designs for i in sorted(
                r_p.cells(topology=topo, dynamics="bernoulli:0.1",
                          algorithm=algo, design=d),
                key=lambda i: -r_p.configs[i].n) if ok[i]]
            if not picks:
                raise AssertionError(f"no stable lossy {algo} cell on {topo}")
            cells.append(picks[0])
    check_reference(r_p, masks, cells, dense_reference, REF_COLUMNS_DENSE)
    if not ok.all():
        # the smallest diverging cell diverges in float64 on the host too
        i = min(np.flatnonzero(~ok), key=lambda i: r_p.configs[i].n)
        _, mse64 = dense_reference(r_p, masks, i, REF_COLUMNS_DENSE)
        c = r_p.configs[i]
        if not mse64[-1].max() > mse64[0].max():
            raise AssertionError(
                f"{c.topology} n={c.n} {c.design} {c.dynamics} diverges on "
                "the chip but not in the float64 reference")
        log(f"check f64 reference diverges too: {c.topology} n={c.n} "
            f"{c.design} {c.dynamics}: mse {mse64[0].max():.3e} -> "
            f"{mse64[-1].max():.3e}")
    check_accel_beats_memoryless(r_p)


def sparse_phase(clock, mesh):
    from repro.sweep import SweepSpec, build_round_masks

    spec = SweepSpec(topologies=("grid2d",), sizes=(SPARSE_N,),
                     designs=("asymptotic",), dynamics=("static", "bernoulli:0.1"),
                     num_trials=128, seed=0, layout="sparse")
    log(f"phase sparse: grid2d N={SPARSE_N} F={spec.num_trials} T={SPARSE_ITERS}")
    r_p = timed_sweep(spec, clock, num_iters=SPARSE_ITERS, backend="pallas",
                      mesh=mesh)
    r_j = timed_sweep(spec, clock, num_iters=SPARSE_ITERS, backend="jax",
                      mesh=mesh)
    check_agree(r_p, r_j)
    check_mean(r_p, "pallas")
    check_mean(r_j, "jax")
    masks = build_round_masks(r_p.ensemble, SPARSE_ITERS, seed=spec.seed)
    check_reference(r_p, masks, range(r_p.ensemble.num_configs),
                    sparse_reference, REF_COLUMNS_SPARSE)


def four_chip_phase(clock):
    """The G-sharded pallas sweep and the in-mesh accelerated gossip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.dist import make_fabric
    from repro.dist.gossip import accel_gossip
    from repro.launch.mesh import make_mesh

    if len(jax.devices()) != 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    spec = dense_spec()
    log(f"phase sharded sweep: G over a 4-device data mesh, T={DENSE_ITERS}")
    r4 = timed_sweep(spec, clock, num_iters=DENSE_ITERS, backend="pallas",
                     mesh=make_mesh((4, 1), ("data", "model")))
    r1 = timed_sweep(spec, clock, num_iters=DENSE_ITERS, backend="pallas",
                     mesh=one_device_mesh())
    ok = stable(r1)
    if not np.array_equal(stable(r4), ok):
        raise AssertionError("4-device and 1-device runs disagree on which "
                             "cells diverge")
    # tests/test_multidevice.py's tolerances for the sharded pallas sweep
    np.testing.assert_allclose(r4.x_final[ok], r1.x_final[ok], rtol=2e-4,
                               atol=1e-5)
    np.testing.assert_allclose(r4.mse[ok], r1.mse[ok], rtol=2e-4, atol=1e-7)
    d = float(np.abs(r4.x_final[ok] - r1.x_final[ok]).max())
    log(f"check 4-device == 1-device: {int(ok.sum())} stable cells, max |dx| "
        f"= {d:.3e}; {int((~ok).sum())} cells diverge on both")

    pods, width, eps = 4, 1 << 20, 1e-3
    fab = make_fabric(pods, "ring")
    rounds = fab.rounds_for(eps) + 1   # +1: Phi3[alpha*] is defective
    mesh = make_mesh((pods,), ("pod",))
    x = jnp.asarray(np.random.default_rng(0).standard_normal((pods, width)),
                    jnp.float32)

    def body(b):
        return (accel_gossip(b[0], "pod", fab, rounds)[None],
                (jax.lax.psum(b[0], "pod") / pods)[None])

    run = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("pod"),
                                out_specs=(P("pod"), P("pod")), check_vma=False))
    c0, t0 = clock.total, time.perf_counter()
    y, mean = jax.block_until_ready(run(x))
    wall = time.perf_counter() - t0
    y, mean = np.asarray(y, np.float64), np.asarray(mean, np.float64)
    x64 = np.asarray(x, np.float64)
    rel = np.linalg.norm(y - mean) / np.linalg.norm(x64 - mean)
    drift = float(np.abs(y.mean(axis=0) - mean[0]).max())
    log(f"phase accel_gossip: pod=4 ring, {width} floats per pod, {rounds} "
        f"rounds, wall_s={wall:.3f} compile_s={clock.total - c0:.3f}")
    if not (rel <= eps and drift < 1e-5):
        raise AssertionError(
            f"accel_gossip: relative error {rel:.3e} (eps {eps}), "
            f"mean drift {drift:.3e}")
    log(f"check accel_gossip vs psum mean: relative error {rel:.3e} <= {eps}, "
        f"mean drift {drift:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this smoke runs the compiled kernels on a chip only",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} jax={jax.__version__}")
    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    check_kernels_compiled()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phase(clock)
    else:
        # run_sweep would spread a G > 1 sweep over every visible device;
        # this run stays on one even on a host that shows four
        mesh = one_device_mesh() if len(jax.devices()) > 1 else None
        dense_phase(clock, mesh)
        sparse_phase(clock, mesh)
    log(f"total: wall_s={time.perf_counter() - t0:.3f} "
        f"compile_s={clock.total:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
