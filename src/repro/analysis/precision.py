"""Pass 4: precision lints over the jitted scan bodies.

The sweep contract is float32 end to end: states, weights and MSE tails are
f32, and the only sanctioned low-precision surface is the compression wire
in ``repro.dist`` (stochastic-rounding bfloat16 on the gossip exchange).
Two statically-detectable ways to break that:

- ``weak-f64-promotion`` (error): a Python float closing over a round body
  is weakly typed; under ``jax.enable_x64`` it promotes the
  whole chain to float64 — 2x memory, several-x slower, and silently
  different roundoff between x64-enabled hosts and default ones. We trace
  each round body (and the full engine scan) INSIDE ``jax.enable_x64(True)`` with
  f32 operands: any f64 eqn output that is not an explicit cast is a
  promotion leak.
- ``bf16-accumulation`` (error): a bfloat16 (or fp16) array inside the
  engine scan body — accumulating consensus state at 8-bit mantissa breaks
  the paper's convergence-rate measurements. Only the dist wire may hold
  bf16, and it never appears inside ``_sweep_scan``.

Tracing only — ``enable_x64`` changes promotion semantics at trace time,
nothing executes.
"""

from __future__ import annotations

import jax
import numpy as np

from .findings import AnalysisFinding, algo_finding, source_of
from . import trace_utils as tu

PASS = "precision"


def _f64_eqns(closed):
    """Eqns carrying float64/complex128 outputs anywhere in the body.

    The f32 policy admits NO 64-bit float values inside a round body, so
    presence is the lint — no provenance analysis needed (promotion chains
    start with an auto-inserted convert, which this also catches). int64 is
    deliberately exempt: index arithmetic legitimately widens under x64.
    """
    hits = []
    for eqn in tu.iter_eqns(closed.jaxpr):
        if eqn.primitive.name in ("pjit", "scan", "custom_partitioning",
                                  "pallas_call", "while", "cond"):
            continue  # containers: their inner eqns are walked anyway
        if any(str(getattr(v.aval, "dtype", "")) in ("float64", "complex128")
               for v in eqn.outvars):
            hits.append(eqn)
    return hits


def _low_prec_vars(closed):
    hits = []
    for eqn in tu.iter_eqns(closed.jaxpr):
        for v in eqn.outvars:
            dt = str(getattr(v.aval, "dtype", ""))
            if dt in ("bfloat16", "float16"):
                hits.append((eqn, dt))
    return hits


def check_precision(algorithms=None):
    from repro.core.algorithms import get_algorithm, registered_algorithms

    specs = tuple(algorithms or registered_algorithms())
    findings: list[AnalysisFinding] = []

    # per-registration: round body traced under x64 semantics on f32 operands
    with jax.enable_x64(True):
        for spec in specs:
            algo = get_algorithm(spec)
            ens = tu.probe_ensemble(algo.spec)
            try:
                closed = tu.trace_round_body(algo, ens, 0, abstract_t=True)
            except Exception:
                continue  # untraceable bodies are pass-2 findings
            hits = _f64_eqns(closed)
            if hits:
                prims = sorted({e.primitive.name for e in hits})
                findings.append(algo_finding(
                    "weak-f64-promotion", "error",
                    f"round_body promotes to float64 under x64 semantics "
                    f"({len(hits)} eqn(s): {', '.join(prims)}) — a weakly "
                    f"typed Python scalar is widening the f32 state chain",
                    algo, PASS))
            low = _low_prec_vars(closed)
            if low:
                dts = sorted({dt for _, dt in low})
                findings.append(algo_finding(
                    "bf16-accumulation", "error",
                    f"round_body carries {'/'.join(dts)} intermediates "
                    f"({len(low)} value(s)) — consensus state must stay "
                    f"f32; only the dist compression wire may narrow",
                    algo, PASS))

    # engine-wide: the jax-backend scan body must be bf16/fp16-free
    try:
        closed = tu.trace_engine(specs, "jax")
    except Exception:
        return findings  # engine-trace failures are pass-2 findings
    low = _low_prec_vars(closed)
    if low:
        from repro.sweep import engine

        file, line = source_of(engine.run_batch)
        dts = sorted({dt for _, dt in low})
        findings.append(AnalysisFinding(
            rule="bf16-accumulation", severity="error",
            message=f"engine scan contains {'/'.join(dts)} intermediates "
            f"({len(low)} value(s)) outside the dist compression wire",
            obj="sweep.engine[jax]", file=file, line=line, passname=PASS))
    return findings
