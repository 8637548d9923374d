"""Deliberately-broken registrations: one per pass, for red-path testing.

Each fixture violates exactly ONE contract and holds every other, so the
matching pass must produce exactly one error finding with the expected rule
id and the other passes stay quiet about it. ``selftest()`` (the CLI's
``--fixtures`` flag and the CI lane's second step) registers them, runs the
relevant pass per fixture, and reports pass/fail — the analysis lane
verifying its own teeth.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def _convex(x, a, b, c):
    return jnp.broadcast_to(
        jnp.asarray([a, b, c], jnp.float32), (x.shape[0], 3))


def _make_fixture_classes():
    from repro.core.algorithms import ConsensusAlgorithm

    class MassLeaker(ConsensusAlgorithm):
        """a+b+c = 0.99: leaks 1% of the average's mass every round."""

        name = spec = "fx_mass_leaker"
        num_taps = 1

        def round_body(self, prim, params, carry, t):
            (x,) = carry
            return (prim(x, x, _convex(x, 0.66, 0.33, 0.0)),)

        def ref_coef(self, params):
            return (0.66, 0.33, 0.0)

    class TickFragmenter(ConsensusAlgorithm):
        """Branches in Python on the traced tick: fragments the scan."""

        name = spec = "fx_fragmenting"
        num_taps = 1

        def round_body(self, prim, params, carry, t):
            (x,) = carry
            if (t % 2) == 0:  # concretizes t — trace error under the scan
                return (prim(x, x, _convex(x, 0.5, 0.5, 0.0)),)
            return (prim(x, x, _convex(x, 0.25, 0.75, 0.0)),)

        def ref_coef(self, params):
            return (0.5, 0.5, 0.0)

    class F64Promoter(ConsensusAlgorithm):
        """Multiplies state by a strong np.float64 scalar: x64 promotion."""

        name = spec = "fx_f64_promoter"
        num_taps = 1

        def round_body(self, prim, params, carry, t):
            (x,) = carry
            y = prim(x, x, _convex(x, 0.5, 0.5, 0.0))
            return (y * np.float64(1.0),)

        def ref_coef(self, params):
            return (0.5, 0.5, 0.0)

    return (MassLeaker, TickFragmenter, F64Promoter)


def fixture_specs():
    """(spec, pass name, expected rule, pass callable) per fixture."""
    from .coefficient import check_coefficient_mass
    from .compilation import check_compilation
    from .precision import check_precision

    return (
        ("fx_mass_leaker", "coefficient-mass", "coef-mass",
         check_coefficient_mass),
        ("fx_fragmenting", "trace-compile", "retrace-fragmentation",
         check_compilation),
        ("fx_f64_promoter", "precision", "weak-f64-promotion",
         check_precision),
    )


def register_fixtures():
    from repro.core.algorithms import register_algorithm

    for cls in _make_fixture_classes():
        register_algorithm(cls.name, cls)


def unregister_fixtures():
    from repro.core.algorithms import unregister_algorithm

    for cls in _make_fixture_classes():
        unregister_algorithm(cls.name)


def selftest() -> tuple[str, bool]:
    """Red-path self-test: every fixture must trip its pass, exactly once."""
    register_fixtures()
    lines, ok = ["analysis --fixtures self-test:"], True
    try:
        for spec, passname, rule, check in fixture_specs():
            findings = check((spec,))
            errors = [f for f in findings if f.severity == "error"]
            good = len(errors) == 1 and errors[0].rule == rule
            ok = ok and good
            got = [f"{f.rule}({f.severity})" for f in findings] or ["none"]
            lines.append(
                f"  {'PASS' if good else 'FAIL'} {spec}: {passname} "
                f"expected exactly one error `{rule}`, got {', '.join(got)}")
    finally:
        unregister_fixtures()
    lines.append(f"self-test {'passed' if ok else 'FAILED'}.")
    return "\n".join(lines) + "\n", ok
