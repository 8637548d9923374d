"""Pallas TPU kernel: one fully-fused accelerated gossip round.

    Y = a * (W @ X) + b * X + c * Xp

with a = 1 - alpha + alpha*theta3, b = alpha*theta2, c = alpha*theta1
(Eq. 4a-4c in combined form). This fuses the two kernels the simulator
previously chained per iteration — ``gossip_matvec`` (W @ X) and
``consensus_update`` (the two-tap FMA) — into a single ``pallas_call``:
the matvec accumulates in the output VMEM block across the K grid steps,
and on the final K step the FMA taps are applied to the resident block
before writeback. The intermediate x_w = W @ X therefore never round-trips
through HBM: per round this saves one full write + one full read of the
(N, F) state block, on top of the second kernel's launch and its extra
X read — the simulator's inner loop runs thousands of such rounds.

Grid layout: (G, N/bm, F/bf, N/bk) with K innermost, exactly as in
``gossip_matvec`` — the output index map ignores k, so Pallas keeps the
(bm, bf) block resident across the contraction. A leading G grid axis
indexes a (G, N, N) stacked topology ensemble with per-graph coefficients
(G, 3) — one kernel launch evaluates a full topology x theta x alpha sweep
grid (a single graph is the G = 1 case). Blocks carry a leading length-1
graph dim which is squeezed inside the kernel. X is passed twice with two
different index maps: (kk, j) tiles feed the MXU contraction; the (i, j)
tile (k-independent, fetched once) provides the ``b * X`` tap. The per-cell
(a, b, c) row is a (1, 1, 3) SMEM block of the (G, 1, 3) coefficient stack:
a (1, 3) VMEM block of a (G, 3) array breaks the TPU's (8, 128) tiling rule.

Every contraction runs at ``precision=HIGHEST`` (f32 on the MXU): the
rounds rely on W being exactly doubly (or column) stochastic, and a single
bf16 pass would round W's entries, drifting the mean round after round.

VMEM budget per step at the default 128/128/512 tiles, fp32: out 256 KB +
W 64 KB + three X-shaped tiles 768 KB — comfortably inside ~16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "COEF_SPEC",
    "gossip_round_batched_kernel",
    "gossip_round_batched_pallas",
    "gossip_round_masked_batched_kernel",
    "gossip_round_masked_batched_pallas",
    "gossip_round_sender_masked_batched_kernel",
    "gossip_round_sender_masked_batched_pallas",
]

_HIGHEST = jax.lax.Precision.HIGHEST
# per-cell (a, b, c): one (1, 1, 3) SMEM block of the (G, 1, 3) stack, for
# any grid whose leading axis is G
COEF_SPEC = pl.BlockSpec((1, 1, 3), lambda gg, *_: (gg, 0, 0),
                          memory_space=pltpu.SMEM)


def gossip_round_batched_kernel(nk: int, coef_ref, w_ref, xk_ref, xi_ref, xp_ref, y_ref):
    """Batched-grid body: blocks carry a leading length-1 graph dim."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    y_ref[0] += jnp.dot(
        w_ref[0], xk_ref[0], preferred_element_type=jnp.float32,
        precision=_HIGHEST,
    )

    @pl.when(k == nk - 1)
    def _fma():
        a = coef_ref[0, 0, 0]
        b = coef_ref[0, 0, 1]
        c = coef_ref[0, 0, 2]
        y_ref[...] = a * y_ref[...] + b * xi_ref[...] + c * xp_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bf", "interpret"))
def gossip_round_batched_pallas(
    ws: jax.Array,
    xs: jax.Array,
    xps: jax.Array,
    coefs: jax.Array,
    *,
    bm: int = 128,
    bk: int = 128,
    bf: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Fused round over a stacked ensemble: Ws (G,N,N), Xs/Xps (G,N,F), coefs (G,3).

    Grid (G, N/bm, F/bf, N/bk); each graph g reads its own W stack slice and
    (a, b, c) row, so one launch covers the whole sweep grid.
    """
    g, n, k = ws.shape
    g2, k2, f = xs.shape
    if g != g2 or k != k2 or xs.shape != xps.shape or coefs.shape != (g, 3):
        raise ValueError(
            f"shape mismatch: Ws {ws.shape}, Xs {xs.shape}, Xps {xps.shape}, "
            f"coefs {coefs.shape}"
        )
    if n % bm or k % bk or f % bf:
        raise ValueError(f"shapes ({n},{k},{f}) not multiples of tiles ({bm},{bk},{bf})")
    nk = k // bk
    grid = (g, n // bm, f // bf, nk)
    return pl.pallas_call(
        functools.partial(gossip_round_batched_kernel, nk),
        grid=grid,
        in_specs=[
            COEF_SPEC,
            pl.BlockSpec((1, bm, bk), lambda gg, i, j, kk: (gg, i, kk)),
            pl.BlockSpec((1, bk, bf), lambda gg, i, j, kk: (gg, kk, j)),
            pl.BlockSpec((1, bm, bf), lambda gg, i, j, kk: (gg, i, j)),
            pl.BlockSpec((1, bm, bf), lambda gg, i, j, kk: (gg, i, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bf), lambda gg, i, j, kk: (gg, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, n, f), jnp.float32),
        interpret=interpret,
    )(coefs.reshape(g, 1, 3), ws, xs, xs, xps)


# ---------------------------------------------------------------------------
# Masked variants: per-round edge-failure masks applied INSIDE the kernel.
#
#     W_eff = W .* M + diag((W .* (1 - M)) @ 1)        (mass-preserving)
#     Y     = a * (W_eff @ X) + b * X + c * Xp
#
# M is the 0/1 edge-activity mask of this round (1 on the diagonal, 1 on live
# edges; see repro.core.dynamics). The kernel never materializes W_eff: each
# K step contracts the elementwise-masked tile W.*M against X on the MXU and
# folds that tile's dropped row mass back onto the node's own state via the
# k-independent (i, j) X tile — so a time-varying topology costs one extra
# VPU multiply and row-sum per tile, and the per-round W matrices never
# round-trip through HBM (the scan carries only the compressed bit masks).
# ---------------------------------------------------------------------------


def gossip_round_masked_batched_kernel(nk: int, coef_ref, w_ref, m_ref, xk_ref,
                                       xi_ref, xp_ref, y_ref):
    """Batched-grid masked body: blocks carry a leading length-1 graph dim."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    w = w_ref[0]
    wm = w * m_ref[0]
    drop = jnp.sum(w - wm, axis=1, keepdims=True)
    y_ref[0] += (
        jnp.dot(wm, xk_ref[0], preferred_element_type=jnp.float32,
                precision=_HIGHEST)
        + drop * xi_ref[0]
    )

    @pl.when(k == nk - 1)
    def _fma():
        a = coef_ref[0, 0, 0]
        b = coef_ref[0, 0, 1]
        c = coef_ref[0, 0, 2]
        y_ref[...] = a * y_ref[...] + b * xi_ref[...] + c * xp_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bf", "interpret"))
def gossip_round_masked_batched_pallas(
    ws: jax.Array,
    ms: jax.Array,
    xs: jax.Array,
    xps: jax.Array,
    coefs: jax.Array,
    *,
    bm: int = 128,
    bk: int = 128,
    bf: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Masked fused round over a stacked ensemble (the dynamic-sweep inner loop).

    Ws/Ms (G, N, N), Xs/Xps (G, N, F), coefs (G, 3): each graph g reads its
    own W slice, this round's mask slice, and its (a, b, c) row — one launch
    evaluates a whole failure-probability grid's round.
    """
    g, n, k = ws.shape
    g2, k2, f = xs.shape
    if g != g2 or k != k2 or xs.shape != xps.shape or coefs.shape != (g, 3) \
            or ms.shape != ws.shape:
        raise ValueError(
            f"shape mismatch: Ws {ws.shape}, Ms {ms.shape}, Xs {xs.shape}, "
            f"Xps {xps.shape}, coefs {coefs.shape}"
        )
    if n % bm or k % bk or f % bf:
        raise ValueError(f"shapes ({n},{k},{f}) not multiples of tiles ({bm},{bk},{bf})")
    nk = k // bk
    grid = (g, n // bm, f // bf, nk)
    return pl.pallas_call(
        functools.partial(gossip_round_masked_batched_kernel, nk),
        grid=grid,
        in_specs=[
            COEF_SPEC,
            pl.BlockSpec((1, bm, bk), lambda gg, i, j, kk: (gg, i, kk)),
            pl.BlockSpec((1, bm, bk), lambda gg, i, j, kk: (gg, i, kk)),
            pl.BlockSpec((1, bk, bf), lambda gg, i, j, kk: (gg, kk, j)),
            pl.BlockSpec((1, bm, bf), lambda gg, i, j, kk: (gg, i, j)),
            pl.BlockSpec((1, bm, bf), lambda gg, i, j, kk: (gg, i, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bf), lambda gg, i, j, kk: (gg, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, n, f), jnp.float32),
        interpret=interpret,
    )(coefs.reshape(g, 1, 3), ws, ms, xs, xs, xps)


# ---------------------------------------------------------------------------
# Sender-renorm masked variant: column-stochastic mass preservation.
#
#     W_eff = W .* M + diag(1' @ (W .* (1 - M)))       (column renorm)
#     Y     = a * (W_eff @ X) + b * X + c * Xp
#
# The push_sum / ratio_consensus family keeps W COLUMN stochastic: node j's
# outgoing mass sums to 1 down column j. A dropped edge's mass must return
# to the SENDER's diagonal — W_eff[j, j] += sum_i W[i, j] * (1 - M[i, j]) —
# or masking silently creates/destroys mass. Per output row i that is a
# COLUMN sum of W .* (1 - M), which a row-tiled kernel cannot form from its
# (i, kk) tile alone: W and M are therefore passed twice, once as the usual
# (bm, bk) contraction tile and once as the transposed-access (bk, bm) tile
# at block index (kk, i), whose axis-0 sum accumulates column i's dropped
# mass across the K grid steps. M is symmetric (per undirected edge, 1 on
# the diagonal), so the same mask array serves both access patterns.
# ---------------------------------------------------------------------------


def gossip_round_sender_masked_batched_kernel(nk: int, coef_ref, w_ref, wt_ref,
                                              m_ref, mt_ref, xk_ref, xi_ref,
                                              xp_ref, y_ref):
    """Masked matvec + sender-side (column) dropped-mass return per K tile."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    wm = w_ref[0] * m_ref[0]
    # this K tile's rows are columns of the (kk, i) transposed-access tile:
    # the (bm,) axis-0 sum of W .* (1 - M) accumulates diag(1' @ (W .* (1-M)))
    # restricted to senders in the current K block.
    dropc = jnp.sum(wt_ref[0] * (1.0 - mt_ref[0]), axis=0)
    y_ref[0] += (
        jnp.dot(wm, xk_ref[0], preferred_element_type=jnp.float32,
                precision=_HIGHEST)
        + dropc[:, None] * xi_ref[0]
    )

    @pl.when(k == nk - 1)
    def _fma():
        a = coef_ref[0, 0, 0]
        b = coef_ref[0, 0, 1]
        c = coef_ref[0, 0, 2]
        y_ref[...] = a * y_ref[...] + b * xi_ref[...] + c * xp_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bf", "interpret"))
def gossip_round_sender_masked_batched_pallas(
    ws: jax.Array,
    ms: jax.Array,
    xs: jax.Array,
    xps: jax.Array,
    coefs: jax.Array,
    *,
    bm: int = 128,
    bk: int = 128,
    bf: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Sender-renorm masked fused round over a stacked ensemble.

    Operand contract matches ``gossip_round_masked_batched_pallas`` —
    Ws/Ms (G, N, N), Xs/Xps (G, N, F), coefs (G, 3) — but Ws is column
    stochastic and Ms MUST be symmetric with ones on the diagonal (per
    undirected edge activity, as repro.core.dynamics expands it). Requires
    bm == bk so the transposed-access tile grid lines up.
    """
    g, n, k = ws.shape
    g2, k2, f = xs.shape
    if g != g2 or k != k2 or xs.shape != xps.shape or coefs.shape != (g, 3) \
            or ms.shape != ws.shape:
        raise ValueError(
            f"shape mismatch: Ws {ws.shape}, Ms {ms.shape}, Xs {xs.shape}, "
            f"Xps {xps.shape}, coefs {coefs.shape}"
        )
    if bm != bk:
        raise ValueError(f"sender renorm needs square W tiles, got bm={bm} bk={bk}")
    if n % bm or k % bk or f % bf:
        raise ValueError(f"shapes ({n},{k},{f}) not multiples of tiles ({bm},{bk},{bf})")
    nk = k // bk
    grid = (g, n // bm, f // bf, nk)
    return pl.pallas_call(
        functools.partial(gossip_round_sender_masked_batched_kernel, nk),
        grid=grid,
        in_specs=[
            COEF_SPEC,
            pl.BlockSpec((1, bm, bk), lambda gg, i, j, kk: (gg, i, kk)),
            pl.BlockSpec((1, bk, bm), lambda gg, i, j, kk: (gg, kk, i)),
            pl.BlockSpec((1, bm, bk), lambda gg, i, j, kk: (gg, i, kk)),
            pl.BlockSpec((1, bk, bm), lambda gg, i, j, kk: (gg, kk, i)),
            pl.BlockSpec((1, bk, bf), lambda gg, i, j, kk: (gg, kk, j)),
            pl.BlockSpec((1, bm, bf), lambda gg, i, j, kk: (gg, i, j)),
            pl.BlockSpec((1, bm, bf), lambda gg, i, j, kk: (gg, i, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bf), lambda gg, i, j, kk: (gg, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, n, f), jnp.float32),
        interpret=interpret,
    )(coefs.reshape(g, 1, 3), ws, ws, ms, ms, xs, xs, xps)
