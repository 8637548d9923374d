"""Algorithm 1 agreement: host simulation vs in-mesh SPMD vs dense solve.

Three layers of the same algorithm must agree:

* ``core.doi.estimate_lambda2`` (numpy network simulation) tracks the dense
  ``lambda_2(W)`` across the paper's topology families;
* the in-mesh ``dist.gossip.distributed_lambda2`` (shard_map over a 'pod'
  axis, subprocess with forced host devices) tracks the dense value too;
* host and in-mesh agree **bit for bit** at P <= 8 when the host runs the
  fabric matvec with the backend's mul+add contraction recipe
  (``fabric_matvec(w, "fma")``) — same ops, same order, same roundings.

The FP footnote the tests encode: rounding re-injects a lambda_1=1 (mean)
component that the W-applications amplify by (1/lambda_2)^K, so K must stay
moderate on fast-mixing graphs — float64 in-mesh runs use K=40 and the f32
sanity check uses K=16.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import accel, doi, topology, weights

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 420, x64: bool = True) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    if x64:
        env["JAX_ENABLE_X64"] = "1"
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=ROOT,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


# ---------------------------------------------------------------------------
# Host Algorithm 1 vs dense lambda_2 across the paper's topology families.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make,num_iters", [
    (lambda rng: topology.chain(20), 400),        # chain: K ~ N^2 (Sec III-D)
    (lambda rng: topology.ring(24), 600),
    (lambda rng: topology.grid2d(5), 200),
    (lambda rng: topology.random_geometric(60, rng), 160),
])
def test_host_doi_tracks_dense(make, num_iters, rng):
    g = make(rng)
    w = weights.metropolis_hastings(g)
    lam2 = accel.lambda2(w)
    res = doi.estimate_lambda2(w, g, num_iters=num_iters, normalize_every=10, rng=rng)
    assert abs(res.lambda2_hat - lam2) / lam2 < 5e-3, (res.lambda2_hat, lam2)


def test_host_doi_rgg_draws_regression(rng):
    """Multiple RGG draws: every draw tracks its own dense solve."""
    for _ in range(3):
        g = topology.random_geometric(50, rng)
        w = weights.metropolis_hastings(g)
        lam2 = accel.lambda2(w)
        res = doi.estimate_lambda2(w, g, num_iters=150, normalize_every=10, rng=rng)
        assert abs(res.lambda2_hat - lam2) / lam2 < 1e-2


def test_fabric_matvec_matches_dense_application(rng):
    """Both contraction recipes of the host mirror are exact matvecs up to
    rounding — the permutation decomposition covers every edge exactly once."""
    from repro.dist.gossip import fabric_matvec, make_fabric

    for p, kind in [(4, "ring"), (7, "ring"), (6, "chain"), (2, "chain")]:
        fab = make_fabric(p, kind)
        v = rng.standard_normal(p)
        dense = fab.w @ v
        for contraction in ("fma", "none"):
            out = fabric_matvec(fab.w, contraction)(v)
            np.testing.assert_allclose(out, dense, atol=1e-12)


# ---------------------------------------------------------------------------
# In-mesh Algorithm 1 (subprocess: forced host devices).
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_inmesh_doi_tracks_dense_f64():
    out = _run("""
        import jax, jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.dist import make_fabric, distributed_lambda2
        # K per graph: (lambda3/lambda2)^K must undercut the tolerance
        # (chain's gap ratio ~0.73 needs K ~ N^2, Sec III-D)
        for p, kind, k in [(8, "ring", 40), (6, "chain", 160), (4, "chain", 40)]:
            fab = make_fabric(p, kind)
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((p,), ("pod",))
            def est(key):
                return distributed_lambda2("pod", p, key, num_iters=k,
                                           fabric=fab, dtype=jnp.float64)[None]
            f = shard_map(est, mesh=mesh, in_specs=P(), out_specs=P("pod"),
                          check_vma=False)
            lam = jax.jit(f)(jax.random.PRNGKey(0))
            err = abs(float(lam[0]) - fab.lambda2)
            assert err < 1e-6, (p, kind, float(lam[0]), fab.lambda2)
            # every pod ends with the same number (max-consensus is exact)
            assert len({float(x) for x in lam}) == 1
        print("OK inmesh f64")
    """)
    assert "OK inmesh f64" in out


@pytest.mark.slow
def test_inmesh_doi_f32_moderate_k():
    """float32 sanity: with K small enough that the (1/lambda2)^K mean
    re-injection stays below tolerance, single precision still tracks."""
    out = _run("""
        import jax, jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.dist import make_fabric, distributed_lambda2
        fab = make_fabric(8, "ring")
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("pod",))
        def est(key):
            return distributed_lambda2("pod", 8, key, num_iters=16,
                                       normalize_every=4, fabric=fab,
                                       dtype=jnp.float32)[None]
        f = shard_map(est, mesh=mesh, in_specs=P(), out_specs=P("pod"),
                      check_vma=False)
        lam = float(jax.jit(f)(jax.random.PRNGKey(0))[0])
        assert abs(lam - fab.lambda2) < 1e-3, (lam, fab.lambda2)
        print("OK inmesh f32", lam)
    """, x64=False)
    assert "OK inmesh f32" in out


@pytest.mark.slow
def test_inmesh_doi_bitwise_matches_host_p_le_8():
    """P <= 8, float64: the jitted SPMD trajectory and the host core/doi.py
    simulation (driven through the fabric matvec mirror) agree bit for bit.

    The host mirrors the backend's mul+add contraction; if a backend ever
    stops emitting fmas, the 'none' recipe covers it — the assertion is that
    ONE arithmetic model reproduces the mesh exactly, for every graph tried.
    """
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.core import doi, topology
        from repro.dist import make_fabric, distributed_lambda2, fabric_matvec
        graphs = [(4, "ring", topology.ring(4)), (6, "chain", topology.chain(6)),
                  (8, "ring", topology.ring(8))]
        for p, kind, g in graphs:
            fab = make_fabric(p, kind)
            v0 = np.random.default_rng(7).standard_normal(p)
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((p,), ("pod",))
            def est(_):
                return distributed_lambda2("pod", p, None, num_iters=40,
                                           fabric=fab, v_init=v0,
                                           dtype=jnp.float64)[None]
            f = shard_map(est, mesh=mesh, in_specs=P(), out_specs=P("pod"),
                          check_vma=False)
            lam_mesh = np.asarray(jax.jit(f)(jnp.zeros(())))
            hosts = {
                c: doi.estimate_lambda2(
                    fab.w, g, num_iters=40, normalize_every=10,
                    v_init=v0.copy(), matvec=fabric_matvec(fab.w, c),
                ).lambda2_hat
                for c in ("fma", "none")
            }
            match = [c for c, lam in hosts.items()
                     if all(float(x) == lam for x in lam_mesh)]
            assert match, (p, kind, float(lam_mesh[0]), hosts)
            print(p, kind, "bitwise via", match[0])
        print("OK bitwise")
    """)
    assert "OK bitwise" in out
