"""Pallas segment-reduce kernel: one fused SPARSE gossip round.

    Y = a * (W_eff @ X) + b * X + c * Xp

where W is never materialized: each cell stores an ELLPACK (padded per-row
neighbor list) view of its canonical edge list, slot-major —

    nbr  (D, N) int32   neighbor node index per slot and row
    wgt  (D, N) f32     edge weight per slot (0 on padding slots)
    wrev (D, N) f32     reverse weight W[nbr, i] of the slot's edge
    slot (D, N) int32   undirected-edge id per slot (RoundMasks bits column)
    diag (1, N) f32     W's diagonal

(``repro.kernels.ops.ell_slot_major`` turns ``build_ell``'s row-major
arrays into this layout) and one round is a gather + weighted slot sum:

    y[i] = a * (diag[i] * x[i] + sum_d wgt[d,i] * x[nbr[d,i]]) + b*x[i] + c*xp[i]

The gather runs in XLA ahead of the kernel: ``x[nbr]`` becomes a (D, N, F)
slot-major stack of neighbor rows (and, for masked rounds, ``bits[slot]`` a
(D, N) live-flag stack), so every kernel operand is a rectangular tile —
an arbitrary-row gather from a VMEM block does not lower on the TPU, and a
resident source block could not hold a million-node state anyway. The
kernel then does the weighted slot reduction, the masked-drop bookkeeping
and the two-tap FMA in one pass over the gathered rows. Per-slot and
per-node vectors keep the node axis on lanes: an (N, 1) array is stored
lane-padded to (N, 128) in HBM, which at a million nodes is gigabytes; the
kernel moves each (bd, bm) tile's nodes onto sublanes itself.

Grid: (G, N/bm, F/bf, D/bd) with the slot axis innermost — the output index
map ignores d, so Pallas keeps the (bm, bf) block resident across the
reduction, initializing at d == 0 and applying the FMA taps (and the
diagonal term) on the final d step. Blocks are (bd, bm), (1, bm) or
(bm, bf) tiles with bm a multiple of 128 and bd a multiple of 8 or the whole
padded degree; the per-cell (a, b, c) row rides in SMEM.

The masked variants apply this round's 0/1 edge-activity bits per slot with
the mass-preserving rule: a dropped slot's weight returns to its row's
diagonal, so W_eff stays doubly stochastic (identical semantics to the dense
masked kernel). The sender variant returns dropped mass to the *sender's*
diagonal instead (column renormalization), through the reverse weight of
each slot's edge — the column-stochastic family (push_sum /
ratio_consensus) stays exactly column-stochastic under masking.

Padding invariants (``repro.kernels.ops`` pads): padded slots carry wgt = 0
*and* wrev = 0 (inert in both the reduction and the dropped-mass sums,
whatever nbr/slot say, as long as they index in range), padded rows carry
diag = 0 and x = 0, padded bits columns are never referenced by a real slot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .gossip_round import COEF_SPEC

__all__ = [
    "gather_rows",
    "gather_bits",
    "segment_round_batched_kernel",
    "segment_round_batched_pallas",
    "segment_round_masked_batched_kernel",
    "segment_round_masked_batched_pallas",
    "segment_round_sender_masked_batched_kernel",
    "segment_round_sender_masked_batched_pallas",
]


def gather_rows(xs, nbrs):
    """(G, N, F) states, (G, D, N) neighbor ids -> (G, D, N, F) neighbor rows."""
    return jax.vmap(lambda x, nb: jnp.take(x, nb, axis=0, mode="clip"))(xs, nbrs)


def gather_bits(bits, slots):
    """(G, E) activity rows, (G, D, N) edge ids -> (G, D, N) live flags."""
    return jax.vmap(lambda b, s: jnp.take(b, s, axis=0, mode="clip"))(bits, slots)


def _check(g, n, dmax, f, bm, bd, bf, coefs, diags, slot_arrays, state_arrays):
    bad = (coefs.shape != (g, 3) or diags.shape != (g, 1, n)
           or any(a.shape != (g, dmax, n) for a in slot_arrays)
           or any(a.shape != (g, n, f) for a in state_arrays))
    if bad:
        raise ValueError(
            "shape mismatch: " + ", ".join(
                str(a.shape) for a in (*slot_arrays, diags, *state_arrays,
                                       coefs)))
    if n % bm or dmax % bd or f % bf:
        raise ValueError(
            f"shapes ({n},{dmax},{f}) not multiples of tiles ({bm},{bd},{bf})")


def _specs(bm, bd, bf, n_slot_ops):
    """coef (SMEM), n_slot_ops (1, bd, bm) slot operands, the (1, bd, bm, bf)
    gathered rows, diag, x, xp; out (1, bm, bf)."""
    slot_tile = pl.BlockSpec((1, bd, bm), lambda gg, i, j, d: (gg, d, i))
    tile = pl.BlockSpec((1, bm, bf), lambda gg, i, j, d: (gg, i, j))
    in_specs = [
        COEF_SPEC,
        *([slot_tile] * n_slot_ops),
        pl.BlockSpec((1, bd, bm, bf), lambda gg, i, j, d: (gg, d, i, j)),
        pl.BlockSpec((1, 1, bm), lambda gg, i, j, d: (gg, 0, i)),
        tile,
        tile,
    ]
    return in_specs, tile


def _rows(v):
    """(bd, bm) slot tile, nodes on lanes -> (bd, bm, 1), nodes on sublanes."""
    return v[:, :, None]


def _fma(coef_ref, diag_ref, xi_ref, xp_ref, y_ref):
    a = coef_ref[0, 0, 0]
    b = coef_ref[0, 0, 1]
    c = coef_ref[0, 0, 2]
    xi = xi_ref[0]
    diag = jnp.transpose(diag_ref[0])                     # (bm, 1)
    y_ref[0] = a * (y_ref[0] + diag * xi) + b * xi + c * xp_ref[0]


def segment_round_batched_kernel(nd: int, coef_ref, wgt_ref, xg_ref, diag_ref,
                                 xi_ref, xp_ref, y_ref):
    """Accumulate one bd-slot weighted partial; diagonal + FMA on the last step."""
    d = pl.program_id(3)

    @pl.when(d == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    y_ref[0] += jnp.sum(_rows(wgt_ref[0]) * xg_ref[0], axis=0)

    @pl.when(d == nd - 1)
    def _last():
        _fma(coef_ref, diag_ref, xi_ref, xp_ref, y_ref)


@functools.partial(jax.jit, static_argnames=("bm", "bd", "bf", "interpret"))
def segment_round_batched_pallas(
    nbrs: jax.Array,
    wgts: jax.Array,
    diags: jax.Array,
    xs: jax.Array,
    xps: jax.Array,
    coefs: jax.Array,
    *,
    bm: int = 128,
    bd: int = 8,
    bf: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Fused sparse round over a stacked ensemble, operands pre-padded.

    nbrs/wgts (G, D, N), diags (G, 1, N), Xs/Xps (G, N, F), coefs (G, 3):
    grid (G, N/bm, F/bf, D/bd), each graph g reads its own ELL
    slices and (a, b, c) row — one launch covers the whole sparse sweep grid.
    """
    g, n, f = xs.shape
    dmax = nbrs.shape[1]
    _check(g, n, dmax, f, bm, bd, bf, coefs, diags, (nbrs, wgts), (xs, xps))
    in_specs, out_spec = _specs(bm, bd, bf, 1)
    return pl.pallas_call(
        functools.partial(segment_round_batched_kernel, dmax // bd),
        grid=(g, n // bm, f // bf, dmax // bd),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((g, n, f), jnp.float32),
        interpret=interpret,
    )(coefs.reshape(g, 1, 3), wgts, gather_rows(xs, nbrs), diags, xs, xps)


# ---------------------------------------------------------------------------
# Masked variants: per-round edge-activity bits applied INSIDE the kernel.
#
#     wt[d, i] = wgt[d, i] * bits[slot[d, i]]          (this round's live edges)
#     drop[i]  = sum_d (wgt[d, i] - wt[d, i])          (mass back to the diagonal)
#     y[i]     = a*( (diag[i]+drop[i])*x[i] + sum_d wt[d,i]*x[nbr[d,i]] )
#                + b*x[i] + c*xp[i]
#
# Exactly the dense masked kernel's mass-preserving rule, evaluated per slot:
# the compressed (G, E) bits row replaces the (G, N, N) mask expansion, so
# the sparse dynamic sweep never materializes a mask matrix at all.
# ---------------------------------------------------------------------------


def segment_round_masked_batched_kernel(nd: int, coef_ref, wgt_ref, live_ref,
                                        xg_ref, diag_ref, xi_ref, xp_ref,
                                        y_ref):
    """Masked weighted partial + dropped-mass return per slot tile."""
    d = pl.program_id(3)

    @pl.when(d == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    w = _rows(wgt_ref[0])
    wt = w * _rows(live_ref[0])
    y_ref[0] += (jnp.sum(wt * xg_ref[0], axis=0)
                 + jnp.sum(w - wt, axis=0) * xi_ref[0])

    @pl.when(d == nd - 1)
    def _last():
        _fma(coef_ref, diag_ref, xi_ref, xp_ref, y_ref)


@functools.partial(jax.jit, static_argnames=("bm", "bd", "bf", "interpret"))
def segment_round_masked_batched_pallas(
    nbrs: jax.Array,
    wgts: jax.Array,
    slots: jax.Array,
    diags: jax.Array,
    bits: jax.Array,
    xs: jax.Array,
    xps: jax.Array,
    coefs: jax.Array,
    *,
    bm: int = 128,
    bd: int = 8,
    bf: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Masked fused sparse round over a stacked ensemble (dynamic sparse sweep).

    nbrs/wgts/slots (G, D, N), diags (G, 1, N), bits (G, E) this round's
    activity rows, Xs/Xps (G, N, F), coefs (G, 3) -> (G, N, F).
    """
    g, n, f = xs.shape
    dmax = nbrs.shape[1]
    _check(g, n, dmax, f, bm, bd, bf, coefs, diags, (nbrs, wgts, slots),
           (xs, xps))
    if bits.ndim != 2 or bits.shape[0] != g:
        raise ValueError(f"bits {bits.shape} is not one (G, E) row per cell")
    in_specs, out_spec = _specs(bm, bd, bf, 2)
    return pl.pallas_call(
        functools.partial(segment_round_masked_batched_kernel, dmax // bd),
        grid=(g, n // bm, f // bf, dmax // bd),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((g, n, f), jnp.float32),
        interpret=interpret,
    )(coefs.reshape(g, 1, 3), wgts, gather_bits(bits, slots),
      gather_rows(xs, nbrs), diags, xs, xps)


# ---------------------------------------------------------------------------
# Sender-renorm masked variant: column-stochastic mass preservation.
#
# For the push_sum / ratio_consensus family W is COLUMN stochastic: node j's
# outgoing mass sums to 1 down column j. When edge {i, j} drops this round,
# the mass j would have sent to i must return to j's own diagonal (the sender
# keeps it) — receiver-side renormalization would silently create or destroy
# mass. Per output row i the returned mass is the column sum
#
#     drop[i] = sum_k W[k, i] * (1 - M[k, i])
#             = sum_d wrev[d, i] * (1 - bits[slot[d, i]])
#
# because masks are per undirected edge (bits hit both directions) and
# wrev[d, i] = W[nbr[d,i], i] stores the reverse weight of slot d's edge.
# Everything else matches the receiver-masked kernel.
# ---------------------------------------------------------------------------


def segment_round_sender_masked_batched_kernel(nd: int, coef_ref, wgt_ref,
                                               wrev_ref, live_ref, xg_ref,
                                               diag_ref, xi_ref, xp_ref,
                                               y_ref):
    """Masked weighted partial with sender-side (column) dropped-mass return."""
    d = pl.program_id(3)

    @pl.when(d == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    live = _rows(live_ref[0])
    y_ref[0] += (jnp.sum(_rows(wgt_ref[0]) * live * xg_ref[0], axis=0)
                 + jnp.sum(_rows(wrev_ref[0]) * (1.0 - live), axis=0)
                 * xi_ref[0])

    @pl.when(d == nd - 1)
    def _last():
        _fma(coef_ref, diag_ref, xi_ref, xp_ref, y_ref)


@functools.partial(jax.jit, static_argnames=("bm", "bd", "bf", "interpret"))
def segment_round_sender_masked_batched_pallas(
    nbrs: jax.Array,
    wgts: jax.Array,
    wrevs: jax.Array,
    slots: jax.Array,
    diags: jax.Array,
    bits: jax.Array,
    xs: jax.Array,
    xps: jax.Array,
    coefs: jax.Array,
    *,
    bm: int = 128,
    bd: int = 8,
    bf: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Sender-renorm masked sparse round over a stacked ensemble.

    Operands match ``segment_round_masked_batched_pallas`` plus wrevs
    (G, D, N): the reverse weight of each slot's edge, 0 on padding
    slots. Dropped mass returns to the sender's diagonal, keeping W_eff
    exactly column stochastic (push_sum / ratio_consensus dynamic sweeps).
    """
    g, n, f = xs.shape
    dmax = nbrs.shape[1]
    _check(g, n, dmax, f, bm, bd, bf, coefs, diags,
           (nbrs, wgts, wrevs, slots), (xs, xps))
    if bits.ndim != 2 or bits.shape[0] != g:
        raise ValueError(f"bits {bits.shape} is not one (G, E) row per cell")
    in_specs, out_spec = _specs(bm, bd, bf, 3)
    return pl.pallas_call(
        functools.partial(segment_round_sender_masked_batched_kernel,
                          dmax // bd),
        grid=(g, n // bm, f // bf, dmax // bd),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((g, n, f), jnp.float32),
        interpret=interpret,
    )(coefs.reshape(g, 1, 3), wgts, wrevs, gather_bits(bits, slots),
      gather_rows(xs, nbrs), diags, xs, xps)
