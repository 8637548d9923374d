"""Multi-device integration tests, each in a subprocess with forced host
devices (XLA_FLAGS must precede jax init, so they cannot share this process).

Covers: consensus-vs-allreduce exactness at P=2, accel-vs-memoryless round
advantage (host prediction at P=8, asserted in-mesh on the P=4 ring fixture),
the in-mesh Algorithm-1 DOI, pipeline parallelism, int8-wire consensus, and
the sharding-rule unit logic (AbstractMesh, no devices needed). CI runs this
file with 4 forced host devices; each test pins its own count anyway.
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 4, timeout: int = 420, x64: bool = False) -> str:
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


@pytest.mark.slow
def test_consensus_p2_exactly_matches_allreduce():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import build
        from repro.dist import make_train_step, SyncConfig
        from repro import optim
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 1), ("pod", "data", "model"))
        cfg = get_config("yi-9b", smoke=True)
        model = build(cfg); opt = optim.adamw(1e-3)
        batch = {"tokens": jnp.zeros((8, 16), jnp.int32),
                 "labels": jnp.ones((8, 16), jnp.int32)}
        ts_a = make_train_step(model, opt, mesh, SyncConfig(mode="allreduce"), 8, 16)
        pa, oa = ts_a.init_state(jax.random.PRNGKey(0), model, opt)
        p1, _, m1 = jax.jit(ts_a.fn)(pa, oa, batch)
        ts_g = make_train_step(model, opt, mesh, SyncConfig(mode="accel_gossip", eps=1e-3), 8, 16)
        pg, og = ts_g.init_state(jax.random.PRNGKey(0), model, opt)
        bg = jax.tree.map(lambda t: t.reshape(2, 4, *t.shape[1:]), batch)
        p2, _, m2 = jax.jit(ts_g.fn)(pg, og, bg)
        diff = max(float(jnp.abs(a - b[0]).max())
                   for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        # P=2 ring: lambda2=0 -> one accelerated round averages EXACTLY in
        # real arithmetic; the two programs partition differently (pinned
        # manual region vs pure GSPMD) so only fp reduction order differs
        assert diff < 5e-3, diff
        # the two pod replicas themselves must stay in exact consensus
        gap = max(float(jnp.abs(b[0] - b[1]).max()) for b in jax.tree.leaves(p2))
        assert gap == 0.0, gap
        print("OK exact-to-fp", diff)
    """)
    assert "OK exact-to-fp" in out


@pytest.mark.slow
def test_accel_gossip_round_advantage_p8():
    out = _run("""
        from repro.dist import make_fabric
        fab = make_fabric(8, "ring")
        r_mem = fab.rounds_for_memoryless(1e-3)
        r_acc = fab.rounds_for(1e-3)
        assert r_acc < r_mem / 1.8, (r_mem, r_acc)   # Theorem 2/3 speedup
        print("OK rounds", r_mem, r_acc)
    """, devices=1)
    assert "OK rounds" in out


@pytest.mark.slow
def test_accel_gossip_reaches_eps_in_fewer_rounds_p4_ring():
    """P=4 ring fixture: the *executed* in-mesh recursions hit the consensus
    epsilon at the round counts the fabric's rho_accel/rho_memoryless
    predict, and accelerated needs strictly fewer rounds."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.dist import make_fabric
        from repro.dist.gossip import accel_gossip, gossip
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pod",))
        fab = make_fabric(4, "ring")
        eps = 1e-3
        r_acc, r_mem = fab.rounds_for(eps), fab.rounds_for_memoryless(eps)
        assert r_acc < r_mem, (r_acc, r_mem)  # Theorem 2 prediction
        x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 128)), jnp.float32)
        target = x.mean(axis=0)
        denom = float(jnp.linalg.norm(x - target[None]))

        def rel_after(run, rounds):
            def body(b):
                return run(b[0], "pod", fab, rounds)[None]
            f = shard_map(body, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
                          check_vma=False)
            y = jax.jit(f)(x)
            return float(jnp.linalg.norm(y - target[None])) / denom

        def first_round_reaching(run):
            for r in range(1, r_mem + 3):
                if rel_after(run, r) <= eps:
                    return r
            return r_mem + 3

        hit_acc = first_round_reaching(accel_gossip)
        hit_mem = first_round_reaching(gossip)
        assert hit_acc < hit_mem, (hit_acc, hit_mem)
        # W is symmetric (normal), so rho^R bounds the memoryless error
        # exactly; Phi3[alpha*] is defective (critically damped — coalesced
        # eigenvalues), so the accelerated transient carries a polynomial
        # factor on top of rho_accel^R: allow one extra round over the
        # spectral prediction.
        assert hit_acc <= r_acc + 1, (hit_acc, r_acc)
        assert hit_mem <= r_mem, (hit_mem, r_mem)
        print("OK p4 rounds", hit_acc, hit_mem, r_acc, r_mem)
    """)
    assert "OK p4 rounds" in out


@pytest.mark.slow
def test_pairwise_gossip_p4_ring_matches_host_and_conserves_mean():
    """The registry's async_pairwise dist variant in-mesh: each round's woken
    pair averages over one two-element ppermute, every state stays equal to
    the host pairwise-matrix product, the pod mean is conserved exactly, and
    the algorithm_gossip registry dispatcher routes to the same program."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.dist import make_fabric
        from repro.dist.gossip import algorithm_gossip, pairwise_gossip
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pod",))
        fab = make_fabric(4, "ring")
        edges = [(i, j) for i in range(4) for j in range(i + 1, 4)
                 if fab.w[i, j] != 0.0]
        rng = np.random.default_rng(7)
        sched = rng.integers(0, len(edges), size=40)
        x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)

        def runner(fn, rounds, **kw):
            def body(b):
                return fn(b[0], "pod", fab, rounds, schedule=sched[:rounds],
                          **kw)[None]
            return jax.jit(shard_map(body, mesh=mesh, in_specs=P("pod"),
                                     out_specs=P("pod"), check_vma=False))

        y = runner(pairwise_gossip, 40)(x)
        # host reference: apply the Boyd matrix of each scheduled edge
        ref = np.asarray(x, np.float64)
        for e in sched:
            i, j = edges[int(e)]
            avg = 0.5 * (ref[i] + ref[j])
            ref[i] = ref[j] = avg
        assert float(jnp.abs(y - ref).max()) < 1e-5
        # pod mean conserved exactly up to fp rounding
        assert float(jnp.abs(y.mean(0) - x.mean(0)).max()) < 1e-6
        # and it contracts toward consensus
        spread0 = float(jnp.abs(x - x.mean(0)).max())
        spread = float(jnp.abs(y - y.mean(0)).max())
        assert spread < 0.5 * spread0, (spread, spread0)
        # registry dispatch routes to the identical program
        y2 = runner(algorithm_gossip, 40, algorithm="async_pairwise")(x)
        assert float(jnp.abs(y - y2).max()) == 0.0
        print("OK pairwise", spread / spread0)
    """)
    assert "OK pairwise" in out


@pytest.mark.slow
def test_masked_gossip_degrades_gracefully_p4():
    """Per-round dropped-matching masks: the pod mean is conserved under any
    failure history (mass-preserving re-weighting), an all-ones mask equals
    the unmasked path bit-for-bit, an all-zeros mask freezes the state, and
    the in-mesh run matches the host masked-W reference."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.dist import make_fabric, edge_permutations
        from repro.dist.gossip import accel_gossip, gossip
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pod",))
        fab = make_fabric(4, "ring")
        perms = edge_permutations(fab.w)
        nm = len(perms)
        R = 12
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)

        def run(kind, mask):
            fn = accel_gossip if kind == "accel" else gossip
            def body(b):
                return fn(b[0], "pod", fab, R, drop_mask=mask)[None]
            f = shard_map(body, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
                          check_vma=False)
            return jax.jit(f)(x)

        mask = jnp.asarray((rng.random((R, nm)) >= 0.4), jnp.float32)
        for kind in ("accel", "mem"):
            y = run(kind, mask)
            # pod mean conserved under failures (up to fp roundoff)
            gap = float(jnp.abs(y.mean(0) - x.mean(0)).max())
            assert gap < 1e-5, (kind, gap)
            # ones-mask == unmasked recursion
            y1 = run(kind, jnp.ones((R, nm), jnp.float32))
            fn = accel_gossip if kind == "accel" else gossip
            def plain(b):
                return fn(b[0], "pod", fab, R)[None]
            y0 = jax.jit(shard_map(plain, mesh=mesh, in_specs=P("pod"),
                                   out_specs=P("pod"), check_vma=False))(x)
            d1 = float(jnp.abs(y1 - y0).max())
            assert d1 < 1e-6, (kind, d1)
        # all matchings down every round: W_eff = I, state frozen (up to the
        # f32 roundoff of re-accumulating (1/3 + 1/3 + 1/3) x per round)
        yz = run("mem", jnp.zeros((R, nm), jnp.float32))
        dz = float(jnp.abs(yz - x).max())
        assert dz < 1e-5, dz

        # host reference: apply the per-round masked (renormalized) W
        diag = np.diag(fab.w).copy()
        m_np = np.asarray(mask)
        xs = np.asarray(x, np.float64)
        for r in range(R):
            w_eff = np.diag(diag)
            for k, (perm, wvec) in enumerate(perms):
                for s, d in perm:
                    w_eff[d, s] += m_np[r, k] * wvec[d]
                    w_eff[d, d] += (1.0 - m_np[r, k]) * wvec[d]
            xs = w_eff @ xs
        y_mem = run("mem", mask)
        dref = float(np.abs(np.asarray(y_mem, np.float64) - xs).max())
        assert dref < 1e-5, dref
        print("OK masked gossip", gap, dref)
    """)
    assert "OK masked gossip" in out


@pytest.mark.slow
def test_adaptive_accel_gossip_p4():
    """In-mesh adaptive recursion: periodic Algorithm-1 re-solve composed
    with the accelerated rounds. Static fabric: the floored estimate pins
    alpha at the nominal alpha*, so the trajectory tracks plain accel_gossip
    to f32 noise and still reaches the mean; pod mean is conserved; the
    registry dispatcher routes to the identical program."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.dist import make_fabric
        from repro.dist.gossip import accel_gossip, adaptive_accel_gossip, algorithm_gossip
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pod",))
        fab = make_fabric(4, "chain")
        R = max(fab.rounds_for(1e-3), 8)

        def runner(fn, **kw):
            def body(b):
                return fn(b[0], "pod", fab, R, **kw)[None]
            return jax.jit(shard_map(body, mesh=mesh, in_specs=P("pod"),
                                     out_specs=P("pod"), check_vma=False))

        x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 64)), jnp.float32)
        y = runner(adaptive_accel_gossip, resolve_every=4, doi_iters=8)(x)
        target = x.mean(axis=0)
        rel = float(jnp.linalg.norm(y - target[None])
                    / jnp.linalg.norm(x - target[None]))
        assert rel < 2e-3, rel
        # pod mean conserved through estimator + re-solve composition
        assert float(jnp.abs(y.mean(0) - x.mean(0)).max()) < 1e-5
        # floored-at-nominal on a static fabric == plain accel up to the f32
        # in-mesh alpha* re-solve's last-ulp coefficient difference
        y0 = runner(accel_gossip)(x)
        assert float(jnp.abs(y - y0).max()) < 1e-4
        # registry dispatch routes to the identical program
        y2 = runner(algorithm_gossip, algorithm="accel_adapt",
                    resolve_every=4, doi_iters=8)(x)
        assert float(jnp.abs(y - y2).max()) == 0.0
        print("OK adaptive gossip", rel)
    """)
    assert "OK adaptive gossip" in out


@pytest.mark.slow
def test_inmesh_doi_matches_theory():
    out = _run("""
        import jax, jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.dist import make_fabric, distributed_lambda2
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pod",))
        fab = make_fabric(4, "chain")
        def est(key):
            return distributed_lambda2("pod", 4, key, num_iters=40,
                                       topology_kind="chain",
                                       dtype=jnp.float64)[None]
        f = shard_map(est, mesh=mesh, in_specs=P(), out_specs=P("pod"),
                      check_vma=False)
        lam = float(jax.jit(f)(jax.random.PRNGKey(3))[0])
        assert abs(lam - fab.lambda2) < 1e-4, (lam, fab.lambda2)
        print("OK doi", lam)
    """, x64=True)
    assert "OK doi" in out


@pytest.mark.slow
def test_pipeline_matches_reference():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.dist.pipeline import pipeline_forward, reference_forward
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("stage",))
        rng = np.random.default_rng(0)
        w1 = jnp.asarray(rng.standard_normal((4, 2, 16, 32)), jnp.float32) * 0.1
        w2 = jnp.asarray(rng.standard_normal((4, 2, 32, 16)), jnp.float32) * 0.1
        x = jnp.asarray(rng.standard_normal((6, 3, 16)), jnp.float32)
        out = pipeline_forward(w1, w2, x, mesh)
        ref = reference_forward(w1, w2, x)
        err = float(jnp.abs(out - ref).max())
        assert err < 1e-5, err
        print("OK pipeline", err)
    """)
    assert "OK pipeline" in out


@pytest.mark.slow
def test_int8_wire_consensus_still_converges():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.dist import make_fabric
        from repro.dist.gossip import accel_gossip
        from repro.dist.compression import Int8Wire
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pod",))
        fab = make_fabric(4, "ring")
        R = fab.rounds_for(1e-3)
        def body(x):
            x = x[0]
            out = accel_gossip(x, "pod", fab, R, wire=Int8Wire())
            return out[None]
        f = shard_map(body, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
                      check_vma=False)
        x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 64)), jnp.float32)
        y = jax.jit(f)(x)
        target = x.mean(axis=0)
        rel = float(jnp.linalg.norm(y - target[None]) / jnp.linalg.norm(x - target[None]))
        assert rel < 5e-2, rel   # int8 noise floors above eps but well-mixed
        print("OK wire", rel)
    """)
    assert "OK wire" in out


@pytest.mark.slow
def test_pallas_sweep_partitions_over_g_on_4_devices():
    """The tentpole seam: backend="pallas" under a forced 4-device mesh runs
    the scan inside shard_map over the G axis — each device steps its own
    block of cells, each algorithm partition padded to a multiple of 4
    (3 accel + 3 push_sum cells per layout here, so both partitions pad) —
    and dense and sparse layouts both match the jax backend to f32
    tolerances and a one-device mesh run bit for bit, final carry taps
    included; a dynamic grid with a sender-renorm partition (push_sum)
    exercises the masked + column-masked kernel variants."""
    out = _run("""
        import numpy as np, jax
        assert jax.device_count() == 4, jax.device_count()
        from repro.launch.mesh import make_mesh
        from repro.sweep import SweepSpec, build_ensemble, run_ensemble
        from repro.sweep.engine import build_round_masks

        one = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
        for layout in ("dense", "sparse"):
            spec = SweepSpec(
                topologies=("chain", "rgg", "grid2d"), sizes=(16,),
                designs=("asymptotic",), alphas=(1.0,), num_trials=3,
                seed=7, algorithms=("accel", "push_sum"),
                dynamics=("bernoulli:0.2",), layout=layout,
            )
            ens = build_ensemble(spec)
            assert ens.layout == (("accel", 0, 3), ("push_sum", 3, 6))
            masks = build_round_masks(ens, 30, seed=7)
            r_p = run_ensemble(ens, num_iters=30, backend="pallas",
                               round_masks=masks, return_taps=True)
            r_j = run_ensemble(ens, num_iters=30, backend="jax",
                               round_masks=masks)
            np.testing.assert_allclose(
                r_p.x_final, r_j.x_final, rtol=2e-4, atol=1e-5)
            np.testing.assert_allclose(
                r_p.mse, r_j.mse, rtol=2e-4, atol=1e-7)
            r_1 = run_ensemble(ens, num_iters=30, backend="pallas",
                               round_masks=masks, return_taps=True, mesh=one)
            np.testing.assert_array_equal(r_p.x_final, r_1.x_final)
            np.testing.assert_array_equal(r_p.mse, r_1.mse)
            for (n4, s4, e4, t4), (n1, s1, e1, t1) in zip(r_p.taps, r_1.taps):
                assert (n4, s4, e4) == (n1, s1, e1)
                for a, b in zip(t4, t1):
                    assert a.shape == (e4 - s4, 16, 3)
                    np.testing.assert_array_equal(a, b)
            print("OK sharded", layout)
    """)
    assert "OK sharded dense" in out and "OK sharded sparse" in out


def test_sharding_rules_abstract_mesh():
    """Rule logic is device-free (AbstractMesh)."""
    out = _run("""
        from jax.sharding import AbstractMesh, PartitionSpec as P
        from repro.dist.sharding import partition_spec
        mesh = AbstractMesh((16, 16), ("data", "model"))
        # TP beats cache_seq for 'model' when kv_heads divide
        s = partition_spec((32, 32768, 32, 128), ("batch", "cache_seq", "kv_heads", "head_dim"), mesh)
        assert s == P("data", None, "model"), s
        # kv_heads=4 can't: cache_seq gets 'model' (flash-decode style)
        s = partition_spec((32, 32768, 4, 128), ("batch", "cache_seq", "kv_heads", "head_dim"), mesh)
        assert s == P("data", "model"), s
        # non-divisible batch (8 % 16 != 0) replicates; cache_seq keeps 'model'
        s = partition_spec((8, 32768, 4, 128), ("batch", "cache_seq", "kv_heads", "head_dim"), mesh)
        assert s == P(None, "model"), s
        # embed FSDP + vocab TP
        s = partition_spec((51968, 512), ("vocab", "embed"), mesh)
        assert s == P("model", "data"), s
        # non-divisible dims are replicated, not unevenly sharded
        s = partition_spec((56,), ("heads",), mesh)
        assert s == P(), s
        multi = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
        s = partition_spec((256, 4096), ("batch", None), multi)
        assert s == P(("pod", "data")), s
        print("OK rules")
    """, devices=1)
    assert "OK rules" in out
