"""Autotuner contract tests: mode semantics, cache determinism, and the
bit-identicality guarantee that candidate tiles only repartition the output
grid (bm/bf) while the contraction tiles (bk/bd) stay pinned — so every
candidate computes the exact same floats.
"""
import json

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import autotune, ops


@pytest.fixture(autouse=True)
def _clean_cache(tmp_path, monkeypatch):
    """Every test gets an empty private JSON cache + empty memory cache."""
    monkeypatch.setenv("REPRO_KERNEL_TUNE_CACHE", str(tmp_path / "tune.json"))
    autotune.clear_memory_cache()
    yield
    autotune.clear_memory_cache()


def test_mode_off_reproduces_static_tiles(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_TUNE", "off")
    calls = []
    assert autotune.get_tiles("round", 200, 300, bench=calls.append) \
        == autotune.static_round_tiles(300)
    assert autotune.get_tiles("segment", 200, 300, bench=calls.append) \
        == autotune.static_segment_tiles(300)
    assert calls == []  # off never measures


def test_mode_cache_miss_degrades_to_static_without_timing(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_TUNE", "cache")
    calls = []
    assert autotune.get_tiles("round", 128, 128, bench=calls.append) \
        == autotune.static_round_tiles(128)
    assert calls == []  # cache mode never invokes the bench closure


def test_mode_full_times_each_candidate_once_then_hits_cache(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_TUNE", "full")
    cands = autotune.round_candidates(256, 256)
    calls = []

    def bench(tiles):
        calls.append(tuple(tiles))

    # deterministic fake timer: make candidate (256, 128, 128) the winner
    def fake_time(bench_fn, tiles, reps=3):
        bench_fn(tiles)
        return 0.1 if tuple(tiles) == (256, 128, 128) else 1.0

    monkeypatch.setattr(autotune, "time_candidate", fake_time)
    won = autotune.get_tiles("round", 256, 256, bench=bench)
    assert won == (256, 128, 128)
    assert sorted(set(calls)) == sorted(cands)  # every candidate timed once

    # second call: in-process cache hit, no timing at all
    calls.clear()
    assert autotune.get_tiles("round", 256, 256, bench=bench) == won
    assert calls == []

    # drop the memory cache: the JSON cache must serve the same winner,
    # and even plain `cache` mode must now return it
    autotune.clear_memory_cache()
    monkeypatch.setenv("REPRO_KERNEL_TUNE", "cache")
    assert autotune.get_tiles("round", 256, 256, bench=bench) == won
    assert calls == []

    # the file itself is namespaced by device kind
    data = json.loads(autotune.cache_path().read_text())
    assert autotune.device_key() in data


def test_invalid_mode_raises(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_TUNE", "sometimes")
    with pytest.raises(ValueError, match="REPRO_KERNEL_TUNE"):
        autotune.get_tiles("round", 128, 128)


def test_corrupt_cache_file_is_ignored(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_TUNE", "cache")
    autotune.cache_path().write_text("{not json")
    assert autotune.get_tiles("round", 128, 128) \
        == autotune.static_round_tiles(128)


def test_candidates_pin_contraction_tiles():
    for bm, bk, bf in autotune.round_candidates(512, 512):
        assert bk == 128
    for bm, bd, bf in autotune.segment_candidates(512, 512):
        assert bd == 8


@settings(max_examples=6, deadline=None)
@given(n=st.integers(1, 3), f=st.integers(1, 3), seed=st.integers(0, 999))
def test_round_outputs_bit_identical_across_candidate_tiles(n, f, seed):
    """The autotuner's core guarantee: any candidate (bm, bf) computes the
    exact same bits as any other, dense and ELL alike, because only the
    output-parallel grid varies. Each candidate pads to its own tiles
    exactly as the sweep engine does, then the unpadded block is compared.
    """
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    nn, ff = 100 * n, 100 * f          # deliberately not tile multiples
    g, interp = 2, ops.use_interpret()
    w = rng.standard_normal((nn, nn)).astype(np.float32) * 0.1
    ws0 = np.stack([w] * g)
    xs0 = rng.standard_normal((g, nn, ff)).astype(np.float32)
    xps0 = rng.standard_normal((g, nn, ff)).astype(np.float32)
    cfs = jnp.asarray(np.tile([1.1, 0.2, -0.3], (g, 1)), jnp.float32)

    outs = []
    for bm, bk, bf in autotune.round_candidates(nn, ff):
        n_pad = ops._round_up(nn, max(bm, bk)) - nn
        f_pad = ops._round_up(ff, bf) - ff
        y = ops.gossip_round_batched_pallas(
            jnp.asarray(np.pad(ws0, ((0, 0), (0, n_pad), (0, n_pad)))),
            jnp.asarray(np.pad(xs0, ((0, 0), (0, n_pad), (0, f_pad)))),
            jnp.asarray(np.pad(xps0, ((0, 0), (0, n_pad), (0, f_pad)))),
            cfs, bm=bm, bk=bk, bf=bf, interpret=interp)
        outs.append(np.asarray(y)[:, :nn, :ff])
    for o in outs[1:]:
        np.testing.assert_array_equal(o, outs[0])


def test_segment_outputs_bit_identical_across_candidate_tiles():
    import jax.numpy as jnp

    from repro.core import topology, weights

    rng = np.random.default_rng(11)
    gph = topology.random_geometric_sparse(150, rng)
    e_w, d_w = weights.metropolis_hastings_edges(gph)
    nn, ff, interp = gph.n, 96, ops.use_interpret()
    x = jnp.asarray(rng.standard_normal((nn, ff)), jnp.float32)
    xp = jnp.asarray(rng.standard_normal((nn, ff)), jnp.float32)

    outs = []
    for bm, bd, bf in autotune.segment_candidates(nn, ff):
        n_pad = ops._round_up(nn, bm) - nn
        nbr, wgt, wrev, slot, diag = ops.build_ell(
            gph.edges, e_w, np.pad(d_w, (0, n_pad)), nn + n_pad)
        d_pad = ops._round_up(nbr.shape[1], bd) - nbr.shape[1]
        nbr, wgt = (np.pad(a, ((0, 0), (0, d_pad))) for a in (nbr, wgt))
        nbrs, wgts, diags = ops.ell_slot_major(
            nbr[None], wgt[None].astype(np.float32),
            diag[None].astype(np.float32))
        f_pad = ops._round_up(ff, bf) - ff
        y = ops.segment_round_batched_pallas(
            jnp.asarray(nbrs), jnp.asarray(wgts), jnp.asarray(diags),
            jnp.pad(x, ((0, n_pad), (0, f_pad)))[None],
            jnp.pad(xp, ((0, n_pad), (0, f_pad)))[None],
            jnp.asarray([[1.1, 0.2, -0.3]], jnp.float32),
            bm=bm, bd=bd, bf=bf, interpret=interp)
        outs.append(np.asarray(y)[0, :nn, :ff])
    for o in outs[1:]:
        np.testing.assert_array_equal(o, outs[0])


def test_ops_tiles_entry_points_respect_off_mode(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_TUNE", "off")
    assert ops.round_tiles(200, 300) == ops._round_tiles(300)
    assert ops.segment_tiles(200, 300) == ops._segment_tiles(300)


def test_require_compiled_raises_on_interpret_backend(monkeypatch):
    import jax

    if jax.default_backend() == "tpu":  # pragma: no cover - CPU CI
        pytest.skip("compiled backend available: nothing to refuse")
    monkeypatch.setenv("REPRO_REQUIRE_COMPILED", "1")
    with pytest.raises(RuntimeError, match="REPRO_REQUIRE_COMPILED"):
        ops.use_interpret()
