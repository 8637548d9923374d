"""The scan's dense mask expansion: each round's (G, E) link bits become the
(G, N, N) 0/1 mask the masked kernels read, by a one-hot contraction per
cell. It must give, bit for bit, the mask that scattering the bits onto
both orientations of every edge gives, and stay within a bounded amount of
memory at the lossy benchmark cell's real shapes."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.sweep import SweepSpec, build_ensemble, build_round_masks, engine, run_batch

ROUNDS = 4


def scatter_expand(bits_t, idx, n):
    """The two-scatter expansion: bits onto (i, j) and (j, i), then ones on
    the diagonal (padded (0, 0) slots land there and are overwritten)."""
    def one(bg, ig):
        b = bg.astype(jnp.float32)
        m0 = jnp.zeros((n, n), jnp.float32)
        m0 = m0.at[ig[:, 0], ig[:, 1]].set(b)
        return m0.at[ig[:, 1], ig[:, 0]].set(b)

    return jnp.where(jnp.eye(n, dtype=bool), 1.0, jax.vmap(one)(bits_t, idx))


expand = jax.jit(engine._expand_mask, static_argnums=2)
reference = jax.jit(scatter_expand, static_argnums=2)

# (topologies, sizes, dynamics, algorithms)
GRIDS = {
    "chain": (("chain",), (12,), ("bernoulli:0.3",), ("accel",)),
    "grid2d": (("grid2d",), (16,), ("bernoulli:0.3",), ("accel",)),
    "rgg": (("rgg",), (20,), ("bernoulli:0.3",), ("accel",)),
    "padded_slots": (("chain", "rgg"), (8, 20), ("bernoulli:0.3",), ("accel",)),
    "churn": (("grid2d",), (16,), ("churn:0.2",), ("accel",)),
    "async_pairwise": (("rgg",), (16,), ("static",), ("async_pairwise",)),
    "two_partitions": (("grid2d", "rgg"), (9, 16), ("static", "bernoulli:0.2"),
                       ("accel", "push_sum")),
}


def _grid(name):
    topologies, sizes, dynamics, algorithms = GRIDS[name]
    ens = build_ensemble(SweepSpec(
        topologies=topologies, sizes=sizes, designs=("memoryless",),
        num_trials=2, seed=3, dynamics=dynamics, algorithms=algorithms,
        layout="dense"))
    return ens, build_round_masks(ens, ROUNDS, seed=11)


@pytest.mark.parametrize("block_bytes", [None, 1], ids=["budget_blocks", "cell_blocks"])
@pytest.mark.parametrize("name", sorted(GRIDS))
def test_expansion_matches_the_scatter_bit_for_bit(name, block_bytes, monkeypatch):
    if block_bytes is not None:      # one cell a block
        monkeypatch.setattr(engine, "_EXPAND_BLOCK_BYTES", block_bytes)
    ens, masks = _grid(name)
    bits, idx = masks.bits, masks.idx
    real = idx[..., 0] != idx[..., 1]
    assert (idx[real, 0] < idx[real, 1]).all()
    if name == "padded_slots":
        assert not real.all()          # cells with fewer edges than E_max
    assert (bits[:, real] == 0).any()  # some link is down in some round
    for n in (ens.ws.shape[1], 128):   # as the jax backend runs, and padded
        for part, s, e in ens.layout:
            for t in range(ROUNDS):
                got = np.asarray(expand(bits[t, s:e], idx[s:e], n))
                want = np.asarray(reference(bits[t, s:e], idx[s:e], n))
                assert got.dtype == want.dtype == np.float32
                np.testing.assert_array_equal(got, want, err_msg=f"{part} t={t} n={n}")


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_random_edge_lists_expand_exactly(seed):
    """Random canonical edge sets of unequal sizes, zero-padded to E_max."""
    rng = np.random.default_rng(seed)
    g, n = 5, 24
    pairs = np.argwhere(np.triu(np.ones((n, n), bool), 1))
    counts = rng.integers(1, len(pairs), size=g)
    e_max = int(counts.max()) + 3
    idx = np.zeros((g, e_max, 2), np.int32)
    for i, c in enumerate(counts):
        idx[i, :c] = pairs[np.sort(rng.choice(len(pairs), c, replace=False))]
    bits = rng.integers(0, 2, size=(g, e_max)).astype(np.uint8)
    np.testing.assert_array_equal(np.asarray(expand(bits, idx, n)),
                                  np.asarray(reference(bits, idx, n)))


def test_blocks_tile_each_partition_within_the_budget():
    for gp, e, n in [(90, 2810, 256), (30, 2810, 256), (7, 2810, 256),
                     (120, 10, 16), (1, 100000, 1024)]:
        k = engine._expand_block(gp, e, n)
        assert 1 <= k <= gp and gp % k == 0
        assert k == 1 or 2 * k * e * n <= engine._EXPAND_BLOCK_BYTES


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_lossy_run_batch_answers_are_unchanged(backend, monkeypatch):
    """run_batch on a small lossy two-partition grid returns the same x_fin
    and mse, bit for bit, as with the scatter expansion in the scan."""
    ens, _ = _grid("two_partitions")
    masks = build_round_masks(ens, 12, seed=5)

    def run():
        engine._sweep_scan.clear_cache()
        return run_batch(ens.ws, ens.x0, ens.coefs, ens.node_counts,
                         num_iters=12, backend=backend, round_masks=masks,
                         algos=ens.layout)

    x_fin, mse = run()
    with monkeypatch.context() as mp:
        mp.setattr(engine, "_expand_mask", scatter_expand)
        x_ref, mse_ref = run()
    engine._sweep_scan.clear_cache()
    assert np.isfinite(mse).all()
    np.testing.assert_array_equal(x_fin, x_ref)
    np.testing.assert_array_equal(mse, mse_ref)


# temp_size_in_bytes of the whole scan compiled for the CPU at the lossy
# cell's shapes (jax backend, 4 rounds; XLA:CPU, jax 0.9.0): 119365136 with
# the two-scatter expansion, 124256512 with blocks of 5 cells. The limit is
# the former plus 10 MB: blocks of 6 cells (136290560), a whole partition at
# once (746807552), or blocks left free to run side by side exceed it.
LOSSY_TEMP_LIMIT = 119365136 + 10_000_000


def test_expansion_memory_at_the_lossy_cell_shapes():
    """Compile only: G = 120 (90 accel + 30 push-sum), E = 2810, N = 256,
    F = 256. XLA:CPU fuses nothing into the dot, so the blocks' one-hots
    are materialised: the worst case of the blocking."""
    g, e, n, f, t = 120, 2810, 256, 256, 4

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    lowered = engine._sweep_scan.lower(
        sds((g, n, n)), sds((g, n, f)), sds((g, n)), sds((g,)), sds((g, 3)),
        num_iters=t, use_kernels=False, bits=sds((t, g, e), jnp.uint8),
        eidx=sds((g, e, 2), jnp.int32),
        layout=(("accel", 0, 90), ("push_sum", 90, 120)))
    temp = lowered.compile().memory_analysis().temp_size_in_bytes
    assert temp < LOSSY_TEMP_LIMIT, temp
