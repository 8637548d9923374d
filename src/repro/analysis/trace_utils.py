"""Shared jaxpr-tracing machinery for the analysis passes.

Everything here builds jaxprs (``jax.make_jaxpr`` / ``jax.eval_shape``) and
walks them — nothing compiles or executes. The instrumented round primitive
(``ANALYSIS_PRIM``) stands in for the engine's fused-round ``prim`` when a
``round_body`` is traced in isolation; its impl raises, so any accidental
execution of an analysis trace hard-fails instead of silently simulating.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.extend.core import Primitive

from jax import core as jax_core

ANALYSIS_PRIM_NAME = "consensus_round_static"

ANALYSIS_PRIM = Primitive(ANALYSIS_PRIM_NAME)


@ANALYSIS_PRIM.def_abstract_eval
def _analysis_abstract(x, xp, coef):
    return jax_core.ShapedArray(x.shape, x.dtype)


def _analysis_impl(*_args, **_kw):
    raise RuntimeError(
        "the static-analysis round primitive must never execute — "
        "analysis passes trace jaxprs only")


ANALYSIS_PRIM.def_impl(_analysis_impl)


def recording_prim(x, xp, coef, m=None):
    """The ``prim`` handed to ``round_body`` during analysis traces.

    Mirrors the engine's fused-round contract ``a*(W_eff@x) + b*x + c*xp``
    abstractly: one opaque primitive per call site, its third operand the
    (Gp, 3) coefficient rows the coefficient-mass pass inspects.
    """
    del m  # masked rounds share the coefficient contract
    return ANALYSIS_PRIM.bind(x, xp, coef)


# ---------------------------------------------------------------------------
# Probe grids: one tiny representative cell per registration, built entirely
# host-side by the ordinary grid machinery (spectra, designs, coefficients —
# no rounds). Cached per registry generation so fixture (re-)registrations
# can never hit a stale ensemble.
# ---------------------------------------------------------------------------

PROBE_N = 8
PROBE_F = 2


@functools.lru_cache(maxsize=64)
def _probe_ensemble_cached(spec_str: str, generation: int):
    del generation  # cache key only
    from repro.sweep.grid import SweepSpec, build_ensemble

    spec = SweepSpec(
        topologies=("chain",), sizes=(PROBE_N,), designs=("asymptotic",),
        algorithms=(spec_str,), num_trials=PROBE_F, seed=0)
    return build_ensemble(spec)


def probe_ensemble(spec_str: str):
    from repro.core.algorithms import registry_generation

    return _probe_ensemble_cached(str(spec_str), registry_generation())


def carry_structs(algo, ens):
    """Abstract carry slot shapes/dtypes via ``eval_shape`` (nothing runs)."""
    from repro.sweep.engine import _algo_init

    g, n, f = ens.x0.shape
    x0 = jax.ShapeDtypeStruct((g, n, f), jnp.float32)
    coefs = jax.ShapeDtypeStruct(np.asarray(ens.coefs).shape, jnp.float32)
    mask = jax.ShapeDtypeStruct((g, n, 1), jnp.float32)
    return jax.eval_shape(
        lambda x, p, m: _algo_init(algo, x, p, m), x0, coefs, mask)


def trace_round_body(algo, ens, t: int, carry=None, *, abstract_t=False):
    """ClosedJaxpr of one ``round_body`` tick through the recording prim.

    ``t`` is baked concrete by default (the coefficient-mass pass enumerates
    phases of periodic algorithms); ``abstract_t=True`` instead traces ``t``
    as an int32 scalar — exactly what the engine's scan does — so the
    trace/compile pass catches bodies that concretize the tick index.
    """
    if carry is None:
        carry = carry_structs(algo, ens)
    coefs = jax.ShapeDtypeStruct(np.asarray(ens.coefs).shape, jnp.float32)
    if abstract_t:
        def fn(params, c, tt):
            return algo.round_body(recording_prim, params, c, tt)
        return jax.make_jaxpr(fn)(
            coefs, carry, jax.ShapeDtypeStruct((), jnp.int32))

    def fn(params, c):
        return algo.round_body(recording_prim, params, c, t)
    return jax.make_jaxpr(fn)(coefs, carry)


# ---------------------------------------------------------------------------
# Jaxpr walking.
# ---------------------------------------------------------------------------

def subjaxprs_of(eqn):
    """Every sub-jaxpr hanging off an equation's params (ducks ClosedJaxpr)."""
    for v in eqn.params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for x in vals:
            if hasattr(x, "jaxpr") and hasattr(x, "consts"):
                yield x.jaxpr
            elif hasattr(x, "eqns"):
                yield x


def iter_eqns(jaxpr):
    """Yield every eqn of a jaxpr, recursively into sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in subjaxprs_of(eqn):
            yield from iter_eqns(sub)


def count_primitive(jaxpr, name: str) -> int:
    return sum(1 for eqn in iter_eqns(jaxpr) if eqn.primitive.name == name)


# ---------------------------------------------------------------------------
# Engine traces: the full mixed-grid scan as a ClosedJaxpr, per backend.
# Replays run_batch's host-side input preparation (via the shared helpers in
# sweep.engine) on abstract operands, then make_jaxpr's the UNJITTED scan
# body — the same function the jitted path traces, so the jaxpr the analyzer
# walks is the jaxpr the engine compiles.
# ---------------------------------------------------------------------------

def build_probe_grid(specs, *, num_iters: int = 4, seed: int = 0):
    """(ensemble, round_masks) for a representative mixed grid over ``specs``."""
    from repro.sweep.grid import SweepSpec, build_ensemble, build_round_masks

    spec = SweepSpec(
        topologies=("chain",), sizes=(PROBE_N,), designs=("asymptotic",),
        algorithms=tuple(specs), num_trials=PROBE_F, seed=seed)
    ens = build_ensemble(spec)
    masks = build_round_masks(ens, num_iters, seed=seed)
    return ens, masks


def trace_engine(specs, backend: str, *, num_iters: int = 4):
    """ClosedJaxpr of the whole sweep scan over ``specs`` on ``backend``."""
    from repro.core.algorithms import registry_generation
    from repro.sweep import engine

    ens, masks = build_probe_grid(specs, num_iters=num_iters)
    g, n, f = ens.x0.shape
    x0 = np.asarray(ens.x0, np.float32)
    bits = eidx = None
    if masks is not None:
        bits = np.asarray(masks.bits, np.uint8)
        eidx = np.asarray(masks.idx, np.int32)

    tiles = None
    if backend == "pallas":
        _, x0, tiles, n, f = engine._prep_pallas_dense(None, x0)
        ws_shape = (g, n, n)
    else:
        ws_shape = np.asarray(ens.ws).shape

    raw = engine._sweep_scan.__wrapped__
    statics = dict(
        num_iters=num_iters, use_kernels=(backend == "pallas"), tiles=tiles,
        layout=ens.layout, algo_gen=registry_generation(), sparse=False)

    def fn(ws, x0_, mask, inv_n, coefs, bits_, eidx_):
        return raw(ws, x0_, mask, inv_n, coefs, bits=bits_, eidx=eidx_,
                   **statics)

    avals = (
        jax.ShapeDtypeStruct(ws_shape, jnp.float32),
        jax.ShapeDtypeStruct((g, n, f), jnp.float32),
        jax.ShapeDtypeStruct((g, n), jnp.float32),
        jax.ShapeDtypeStruct((g,), jnp.float32),
        jax.ShapeDtypeStruct(np.asarray(ens.coefs).shape, jnp.float32),
        None if bits is None else jax.ShapeDtypeStruct(bits.shape, jnp.uint8),
        None if eidx is None else jax.ShapeDtypeStruct(eidx.shape, jnp.int32),
    )
    return jax.make_jaxpr(fn)(*avals)


def trace_engine_sparse(specs, *, num_iters: int = 4):
    """ClosedJaxpr of the sparse-pallas (ELL segment-kernel) sweep scan.

    Replays ``engine._prep_pallas_sparse`` host-side (numpy-only ELL build —
    no rounds) so the batched segment kernel's real BlockSpecs and VMEM
    footprint appear in the trace the mesh/kernel pass inspects.
    """
    from repro.core.algorithms import registry_generation
    from repro.sweep import engine
    from repro.sweep.grid import SweepSpec, build_ensemble, build_round_masks

    spec = SweepSpec(
        topologies=("chain",), sizes=(PROBE_N,), designs=("asymptotic",),
        algorithms=tuple(specs), num_trials=PROBE_F, seed=0, layout="sparse")
    ens = build_ensemble(spec)
    masks = build_round_masks(ens, num_iters, seed=0)
    g, _, _ = ens.x0.shape
    bits = eidx = None
    if masks is not None:
        bits = np.asarray(masks.bits, np.uint8)
        eidx = np.asarray(masks.idx, np.int32)
    x0, wpack, tiles, n, f = engine._prep_pallas_sparse(
        np.asarray(ens.x0, np.float32),
        np.asarray(ens.edges, np.int32), np.asarray(ens.edge_w, np.float32),
        np.asarray(ens.diag_w, np.float32), ens.edge_counts,
        None if ens.edge_w_rev is None
        else np.asarray(ens.edge_w_rev, np.float32))

    raw = engine._sweep_scan.__wrapped__
    statics = dict(
        num_iters=num_iters, use_kernels=True, tiles=tiles,
        layout=ens.layout, algo_gen=registry_generation(), sparse=True)

    def fn(ws, x0_, mask, inv_n, coefs, bits_, eidx_):
        return raw(ws, x0_, mask, inv_n, coefs, bits=bits_, eidx=eidx_,
                   **statics)

    avals = (
        tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in wpack),
        jax.ShapeDtypeStruct(x0.shape, jnp.float32),
        jax.ShapeDtypeStruct((g, n), jnp.float32),
        jax.ShapeDtypeStruct((g,), jnp.float32),
        jax.ShapeDtypeStruct(np.asarray(ens.coefs).shape, jnp.float32),
        None if bits is None else jax.ShapeDtypeStruct(bits.shape, jnp.uint8),
        None if eidx is None else jax.ShapeDtypeStruct(eidx.shape, jnp.int32),
    )
    return jax.make_jaxpr(fn)(*avals)
