"""Static verification of the consensus-engine contracts (no rounds run).

Four jaxpr-level passes over the live registry and the sweep engine:

- ``coefficient`` — per-registration coefficient-mass proof: the display
  state's node mean (mean family) / every tap's total (mass family) must be
  an exact convex recombination tick over tick, or the average itself
  drifts (the failure mode that motivates push-sum in lossy settings).
- ``compilation`` — the one-compilation contract: a full mixed-algorithm
  grid traces to exactly one ``scan`` per backend, and no round body
  concretizes traced values (which would fragment the grid into per-cell
  retraces).
- ``meshkernel`` — BlockSpec tile divisibility and the per-step VMEM
  block budget of every ``pallas_call`` in the engine's program, against
  declared shapes (under a mesh each device runs this same program on its
  own block of cells).
- ``precision`` — no weak-type float64 promotions or stray bfloat16
  accumulation inside the jitted scan bodies (the compression wire in
  ``repro.dist`` is the only sanctioned low-precision surface).

Everything here inspects jaxprs built with ``jax.make_jaxpr`` /
``jax.eval_shape`` — tracing only, nothing is compiled or executed; the
instrumented round primitive hard-fails if anything tries. Entry points:
``run_all_checks()`` (the CLI / CI lane) and ``verify_static(spec)`` (one
registration, for authors — also re-exported by ``core.algorithms``).
"""

from .findings import AnalysisFinding, has_errors, render_markdown, render_text
from .coefficient import check_coefficient_mass
from .compilation import check_compilation
from .meshkernel import check_mesh_kernels
from .precision import check_precision

__all__ = [
    "AnalysisFinding",
    "check_coefficient_mass",
    "check_compilation",
    "check_mesh_kernels",
    "check_precision",
    "has_errors",
    "render_markdown",
    "render_text",
    "run_all_checks",
    "verify_static",
]

# Pass registry, in report order. Each entry is (pass name, callable taking
# an optional tuple of algorithm specs and returning list[AnalysisFinding]).
PASSES = (
    ("coefficient-mass", check_coefficient_mass),
    ("trace-compile", check_compilation),
    ("mesh-kernel", check_mesh_kernels),
    ("precision", check_precision),
)


def run_all_checks(algorithms=None) -> list[AnalysisFinding]:
    """Run every pass over ``algorithms`` (default: the whole registry)."""
    findings: list[AnalysisFinding] = []
    for _, check in PASSES:
        findings.extend(check(algorithms))
    return findings


def verify_static(spec) -> list[AnalysisFinding]:
    """Statically verify ONE registration (algorithm-scoped passes only).

    Runs the coefficient-mass, trace/compile and precision passes restricted
    to ``spec``; the engine-wide mesh/kernel pass additionally runs when the
    registration overrides ``pallas_round`` (the only per-algorithm kernel
    surface). Returns the findings list — empty means the registration
    holds every statically-checkable contract.
    """
    from repro.core.algorithms import get_algorithm

    algo = get_algorithm(spec)
    specs = (algo.spec,)
    findings = list(check_coefficient_mass(specs))
    findings.extend(check_compilation(specs))
    findings.extend(check_precision(specs))
    if algo.pallas_round is not None:
        findings.extend(check_mesh_kernels(specs))
    return findings
