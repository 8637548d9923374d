"""Static-analysis subsystem tests (repro.analysis).

Green path: every seed registration passes all four passes, and the passes
provably never execute a simulation round (the scan/pallas impls and the
engine entry are boobytrapped during the run). Red path: each
deliberately-broken fixture trips exactly its pass with the expected rule
id. Plus the satellite seams: fail-fast registration, the cp-counter
reset/context API, the checkify runtime twin, and the CLI.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import algorithms as alg
from repro.analysis import (
    AnalysisFinding,
    fixtures,
    has_errors,
    render_markdown,
    render_text,
    run_all_checks,
)
from repro.analysis.coefficient import traced_coef_sites
from repro.analysis.__main__ import main as analysis_main


def _convex(x, a, b, c):
    return jnp.broadcast_to(
        jnp.asarray([a, b, c], jnp.float32), (x.shape[0], 3))


# ---------------------------------------------------------------------------
# Green path — shared across assertions because the full run is expensive.
# The boobytraps make this single run double as the no-execution proof:
# if any pass evaluated a scan, a pallas kernel, or the engine itself, the
# run would raise instead of returning findings.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def seed_findings():
    from jax._src.lax.control_flow.loops import scan_p
    from jax._src.pallas.pallas_call import pallas_call_p

    from repro.sweep import engine

    def _boom(kind):
        def impl(*a, **k):
            raise AssertionError(
                f"static analysis must not execute {kind} — jaxpr "
                f"inspection only")
        return impl

    old_scan, old_pallas = scan_p.impl, pallas_call_p.impl
    old_run_batch = engine.run_batch
    scan_p.def_impl(_boom("a scan"))
    pallas_call_p.def_impl(_boom("a pallas kernel"))
    engine.run_batch = _boom("the sweep engine")
    try:
        findings = run_all_checks()
    finally:
        scan_p.def_impl(old_scan)
        pallas_call_p.def_impl(old_pallas)
        engine.run_batch = old_run_batch
    return findings


def test_seed_registry_all_contracts_green(seed_findings):
    errors = [f for f in seed_findings if f.severity == "error"]
    assert not errors, render_text(errors)


def test_traced_stream_reported_for_adaptive_only(seed_findings):
    traced = [f for f in seed_findings if f.rule == "coef-mass-traced"]
    assert [f.obj for f in traced] == ["accel_adapt"]
    assert traced[0].severity == "info"


def test_findings_carry_source_locations(seed_findings):
    assert seed_findings, "expected at least the advisory findings"
    for f in seed_findings:
        assert f.passname and f.rule
        assert f.file.endswith(".py") and f.line >= 0, f


def test_dist_coverage_advisories_respect_exempt_list(seed_findings):
    from repro.dist.gossip import DIST_EXEMPT

    advisories = {f.obj for f in seed_findings
                  if f.rule == "mesh-dist-coverage"}
    assert not advisories & set(DIST_EXEMPT)
    covered = {n for n in alg.registered_algorithms()
               if alg.dist_variant(n) is not None}
    assert advisories == set(alg.registered_algorithms()) - covered \
        - set(DIST_EXEMPT)


def test_traced_site_classifier():
    # adaptive stream: data-dependent -> guarded; poly_filter's Horner taps
    # are merely tick-dependent (and individually non-convex by design):
    # NOT guarded — the runtime twin would misfire on them.
    assert traced_coef_sites("accel_adapt") == frozenset({0})
    assert traced_coef_sites("poly_filter") == frozenset()
    assert traced_coef_sites("accel") == frozenset()
    assert traced_coef_sites("push_sum") == frozenset()


# ---------------------------------------------------------------------------
# Red path: the deliberately-broken fixtures.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "spec,passname,rule",
    [(s, p, r) for s, p, r, _ in fixtures.fixture_specs()])
def test_broken_fixture_trips_exactly_one_finding(spec, passname, rule):
    check = {s: c for s, _, _, c in fixtures.fixture_specs()}[spec]
    fixtures.register_fixtures()
    try:
        findings = check((spec,))
    finally:
        fixtures.unregister_fixtures()
    errors = [f for f in findings if f.severity == "error"]
    assert len(errors) == 1, render_text(findings)
    assert errors[0].rule == rule
    assert errors[0].passname == passname
    assert errors[0].obj == spec


def test_fixture_selftest_roundtrip():
    report, ok = fixtures.selftest()
    assert ok, report
    assert "self-test passed" in report
    assert "fx_mass_leaker" not in alg.registered_algorithms()  # cleaned up


# ---------------------------------------------------------------------------
# Satellite: fail-fast registration.
# ---------------------------------------------------------------------------

def _mk(name, **overrides):
    body = dict(
        name=name, spec=name,
        round_body=lambda self, prim, params, carry, t:
            (prim(carry[0], carry[0], _convex(carry[0], 0.5, 0.5, 0.0)),),
        ref_coef=lambda self, params: (0.5, 0.5, 0.0))
    body.update(overrides)
    return type("Fx", (alg.ConsensusAlgorithm,), body)


@pytest.mark.parametrize("overrides,match", [
    (dict(num_taps=0), "num_taps"),
    (dict(num_taps=1.5), "num_taps"),
    (dict(num_aux=-1), "num_aux"),
    (dict(invariant="magic"), "invariant"),
    (dict(mass_renorm="router"), "mass_renorm"),
    (dict(round_body=alg.ConsensusAlgorithm.round_body), "round_body"),
    (dict(ref_coef=alg.ConsensusAlgorithm.ref_coef,
          reference_run=alg.ConsensusAlgorithm.reference_run), "ref_coef"),
])
def test_register_algorithm_fails_fast(overrides, match):
    with pytest.raises((ValueError, TypeError), match=match):
        alg.register_algorithm("fx_invalid", _mk("fx_invalid", **overrides))
    assert "fx_invalid" not in alg.registered_algorithms()


def test_valid_registration_and_unregister_roundtrip():
    gen0 = alg.registry_generation()
    alg.register_algorithm("fx_valid", _mk("fx_valid"))
    try:
        assert "fx_valid" in alg.registered_algorithms()
        assert alg.registry_generation() > gen0
    finally:
        alg.unregister_algorithm("fx_valid")
    assert "fx_valid" not in alg.registered_algorithms()
    assert alg.registry_generation() > gen0 + 1  # unregister bumps too


def test_verify_static_entry_point():
    assert not has_errors(alg.verify_static("accel"))
    fixtures.register_fixtures()
    try:
        bad = alg.verify_static("fx_mass_leaker")
    finally:
        fixtures.unregister_fixtures()
    assert any(f.rule == "coef-mass" and f.severity == "error" for f in bad)


# ---------------------------------------------------------------------------
# Satellite: the checkify runtime twin.
# ---------------------------------------------------------------------------

def test_debug_checks_twin_is_bit_exact_and_catches_nan():
    from repro.sweep.engine import run_sweep
    from repro.sweep.grid import SweepSpec

    spec = SweepSpec(
        topologies=("chain",), sizes=(8,), designs=("asymptotic",),
        algorithms=("accel", "accel_adapt"), num_trials=2, seed=0)
    r0 = run_sweep(spec, num_iters=15)
    r1 = run_sweep(spec, num_iters=15, debug_checks=True)
    np.testing.assert_array_equal(r0.x_final, r1.x_final)
    np.testing.assert_array_equal(r0.mse, r1.mse)

    class NaNMaker(alg.ConsensusAlgorithm):
        name = spec = "fx_nan_maker"
        num_taps = 1

        def round_body(self, prim, params, carry, t):
            (x,) = carry
            y = prim(x, x, _convex(x, 0.5, 0.5, 0.0))
            return (y + jnp.sqrt(jnp.full_like(y, -1.0)) * 0.0,)

        def ref_coef(self, params):
            return (0.5, 0.5, 0.0)

    alg.register_algorithm("fx_nan_maker", NaNMaker)
    try:
        s2 = SweepSpec(
            topologies=("chain",), sizes=(8,), designs=("asymptotic",),
            algorithms=("fx_nan_maker",), num_trials=2, seed=0)
        assert np.isnan(run_sweep(s2, num_iters=5).x_final).any()  # silent
        with pytest.raises(Exception, match="nonfinite state"):
            run_sweep(s2, num_iters=5, debug_checks=True)
    finally:
        alg.unregister_algorithm("fx_nan_maker")


def test_debug_checks_guards_traced_coefficient_mass():
    """A data-dependent (traced) coefficient stream that leaks mass is
    invisible to the static pass (it can only record the site) but must
    trip the runtime twin's coefficient-mass guard."""
    from repro.sweep.engine import run_sweep
    from repro.sweep.grid import SweepSpec

    class LeakyStream(alg.ConsensusAlgorithm):
        name = spec = "fx_leaky_stream"
        num_taps = 1

        def round_body(self, prim, params, carry, t):
            (x,) = carry
            # data-dependent a: the classifier marks the site traced
            a = 0.49 + 0.0 * jnp.mean(x, axis=(1, 2), keepdims=False)
            coef = jnp.stack(
                [a, jnp.full_like(a, 0.5), jnp.zeros_like(a)], axis=-1)
            return (prim(x, x, coef),)

        def ref_coef(self, params):
            return (0.49, 0.5, 0.0)

    alg.register_algorithm("fx_leaky_stream", LeakyStream)
    try:
        assert traced_coef_sites("fx_leaky_stream") == frozenset({0})
        s = SweepSpec(
            topologies=("chain",), sizes=(8,), designs=("asymptotic",),
            algorithms=("fx_leaky_stream",), num_trials=2, seed=0)
        run_sweep(s, num_iters=5)  # plain path: silent drift
        with pytest.raises(Exception, match="coefficient-mass guard"):
            run_sweep(s, num_iters=5, debug_checks=True)
    finally:
        alg.unregister_algorithm("fx_leaky_stream")


# ---------------------------------------------------------------------------
# CLI and rendering.
# ---------------------------------------------------------------------------

def test_cli_single_algorithm_green_and_markdown_out(tmp_path, capsys):
    out = tmp_path / "analysis.md"
    rc = analysis_main(
        ["--check", "--algorithms", "accel", "--out", str(out)])
    assert rc == 0
    assert "Static analysis" in out.read_text()
    assert "no findings" in capsys.readouterr().out \
        or "finding(s)" in out.read_text()


def test_cli_exits_nonzero_on_error_finding(capsys):
    fixtures.register_fixtures()
    try:
        rc = analysis_main(
            ["--check", "--algorithms", "fx_mass_leaker"])
    finally:
        fixtures.unregister_fixtures()
    assert rc == 1
    assert "coef-mass" in capsys.readouterr().out


def test_finding_schema_and_renderers():
    with pytest.raises(ValueError, match="severity"):
        AnalysisFinding(rule="r", severity="fatal", message="m")
    f_err = AnalysisFinding(rule="coef-mass", severity="error", message="m|m",
                            obj="x", file="a.py", line=3, passname="p")
    f_info = AnalysisFinding(rule="note", severity="info", message="n",
                             obj="y", passname="p")
    assert has_errors([f_info, f_err]) and not has_errors([f_info])
    txt = render_text([f_info, f_err])
    assert txt.index("ERROR") < txt.index("INFO")  # severity-sorted
    assert "[a.py:3]" in txt
    md = render_markdown([f_err])
    assert "\\|" in md and "| error |" in md
    assert "no findings" in render_text([])
