"""Public jit'd wrappers around the Pallas kernels.

These handle shape normalization (flattening, tile padding), backend
detection (interpret mode on CPU, compiled on TPU), autodiff (Pallas calls
have no reverse-mode rule: ``ssd_scan`` is a ``jax.custom_vjp`` — kernel
forward, differentiable chunked-jnp backward, the standard "kernel fwd /
XLA bwd" production pattern), and the inter-chunk associative scan for SSD.
Models and the simulator call these — never the raw ``*_pallas`` entries.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import os

import jax
import jax.numpy as jnp

from jax.experimental.custom_partitioning import custom_partitioning
from jax.sharding import NamedSharding, PartitionSpec as P

from . import autotune
from .consensus_update import LANES, consensus_update_pallas
from .gossip_matvec import gossip_matvec_pallas
from .gossip_round import (
    gossip_round_batched_pallas,
    gossip_round_masked_batched_pallas,
    gossip_round_sender_masked_batched_pallas,
)
from .ref import ssd_chunk_ref
from .segment_round import (
    segment_round_batched_pallas,
    segment_round_masked_batched_pallas,
    segment_round_sender_masked_batched_pallas,
)
from .ssd_chunk import ssd_chunk_pallas

__all__ = [
    "batched_round_prim",
    "batched_segment_round_prim",
    "build_ell",
    "consensus_update",
    "ell_slot_major",
    "gossip_matvec",
    "gossip_round",
    "gossip_round_batched",
    "gossip_round_masked",
    "gossip_round_masked_batched",
    "round_tiles",
    "segment_round",
    "segment_tiles",
    "ssd_scan",
    "use_interpret",
]


def use_interpret() -> bool:
    """Pallas interpret mode everywhere except on a real TPU backend.

    ``REPRO_REQUIRE_COMPILED=1`` turns silent interpret fallback into a hard
    failure — the CI compiled-bench lane sets it so a kernel quietly running
    under the interpreter (orders of magnitude slower, and not the artifact
    being measured) fails the job instead of polluting the trajectory.
    """
    interp = jax.default_backend() != "tpu"
    if interp and os.environ.get("REPRO_REQUIRE_COMPILED", "").strip() == "1":
        raise RuntimeError(
            "REPRO_REQUIRE_COMPILED=1 but the Pallas kernels would run in "
            f"interpret mode (jax backend: {jax.default_backend()!r}); "
            "run on a TPU backend or unset the flag")
    return interp


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# consensus_update: fused y = a*xw + b*x + c*xp over arbitrary-shape operands.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_rows",))
def consensus_update(xw, x, xp, a, b, c, *, block_rows: int = 256):
    """Fused two-tap update. Operands: any (matching) shape; a/b/c scalars."""
    shape = xw.shape
    dtype = xw.dtype
    flat = xw.size
    rows = _round_up(max(_round_up(flat, LANES) // LANES, 1), block_rows)
    pad = rows * LANES - flat

    def prep(t):
        t = t.reshape(-1)
        if pad:
            t = jnp.pad(t, (0, pad))
        return t.reshape(rows, LANES)

    coef = jnp.stack(
        [jnp.asarray(a, dtype), jnp.asarray(b, dtype), jnp.asarray(c, dtype)]
    ).reshape(1, 3)
    y = consensus_update_pallas(
        prep(xw), prep(x), prep(xp), coef,
        block_rows=block_rows, interpret=use_interpret(),
    )
    return y.reshape(-1)[:flat].reshape(shape)


# ---------------------------------------------------------------------------
# gossip_matvec: Y = W @ X with tile padding.
# ---------------------------------------------------------------------------

@jax.jit
def gossip_matvec(w, x):
    """Y = W(N,N) @ X(N,F), fp32 accumulation, auto-padded to MXU tiles."""
    n, f = w.shape[0], x.shape[1]
    bm, bk, bf = _round_tiles(f)
    np_, fp_ = _round_up(n, 128), _round_up(f, bf)
    wp = jnp.pad(w, ((0, np_ - n), (0, np_ - n)))
    xp_ = jnp.pad(x, ((0, np_ - n), (0, fp_ - f)))
    y = gossip_matvec_pallas(
        wp, xp_, bm=bm, bk=bk, bf=bf, interpret=use_interpret()
    )
    return y[:n, :f]


# ---------------------------------------------------------------------------
# gossip_round: fused Y = a*(W@X) + b*X + c*Xp (one accelerated round).
# ---------------------------------------------------------------------------

def _round_tiles(f: int) -> tuple[int, int, int]:
    """(bm, bk, bf) MXU-aligned tiles; narrow trial blocks get narrow bf."""
    return autotune.static_round_tiles(f)


def _round_bench(n: int, f: int, g: int):
    """Bench closure for the dense autotuner: run one batched round, blocked.

    Dummy operands are cached per padded shape so repeat timings measure the
    kernel, not host array construction. The ensemble axis is clamped — tile
    quality is shape-per-graph-driven, and a G=192 sweep grid would make
    every candidate probe pay the full sweep's memory.
    """
    gb = max(1, min(g, 4))
    arrays = {}

    def bench(tiles):
        bm, bk, bf = tiles
        np_, fp_ = _round_up(n, max(bm, bk)), _round_up(f, bf)
        if (np_, fp_) not in arrays:
            arrays[(np_, fp_)] = (
                jnp.full((gb, np_, np_), 1.0 / np_, jnp.float32),
                jnp.ones((gb, np_, fp_), jnp.float32),
                jnp.ones((gb, 3), jnp.float32),
            )
        ws, xs, coefs = arrays[(np_, fp_)]
        gossip_round_batched_pallas(
            ws, xs, xs, coefs, bm=bm, bk=bk, bf=bf, interpret=use_interpret()
        ).block_until_ready()

    return bench


def round_tiles(n: int, f: int, g: int = 1, tune: bool = False):
    """Autotune-aware (bm, bk, bf) for a dense (G, N, F) round problem.

    ``tune=True`` enables measuring on a cache miss (``REPRO_KERNEL_TUNE=
    full`` only) — callers must be OUTSIDE any jit trace to pass it, which
    the sweep engine and the benches are. Jitted wrappers call with the
    default and get the cached winner or the static heuristic.
    """
    bench = _round_bench(n, f, g) if tune else None
    return autotune.get_tiles("round", n, f, g, bench=bench)


def batched_round_prim(ws, *, bm: int = 128, bk: int = 128, bf: int = 512,
                       interpret: bool | None = None,
                       renorm: str = "receiver"):
    """Fused-round primitive over a pre-padded (Gp, N, N) partition slice.

    This is the kernel-layer dispatch point every registry algorithm's
    ``round_body`` routes through on the pallas backend (an algorithm may
    override it via its ``pallas_round`` hook): the returned

        prim(x, xp, coef, m=None) -> coef[:,0]*(W_eff@x) + coef[:,1]*x
                                     + coef[:,2]*xp

    picks the plain or a masked fused batched kernel by whether a per-round
    (Gp, N, N) activity mask ``m`` is supplied; ``renorm`` selects where a
    dropped edge's mass returns — "receiver" (row renorm, the doubly
    stochastic family) or "sender" (column renorm, push_sum /
    ratio_consensus; masks must be symmetric per undirected edge). Operands
    must already be padded to the (bm, bk, bf) tiles — the sweep engine pads
    ONCE outside its scan (see ``repro.sweep.engine``).

    Under a mesh the engine runs its whole scan inside ``shard_map`` over
    the G axis, so the prim always sees one device's block of cells.

    ``coef`` is a traced per-CALL operand, never a compile-time constant:
    the kernels read it from memory each launch, so per-round coefficient
    streams (``accel_adapt`` re-solving alpha* every tick from its in-scan
    estimate) flow through unchanged with zero recompilation — the
    time-varying coefficient contract (docs/ARCHITECTURE.md) is free at
    this layer. The same holds for ``batched_segment_round_prim``.
    """
    if interpret is None:
        interpret = use_interpret()
    if renorm not in ("receiver", "sender"):
        raise ValueError(f"renorm must be receiver or sender, got {renorm!r}")
    kw = dict(bm=bm, bk=bk, bf=bf, interpret=interpret)

    def prim(x, xp, coef, m=None):
        if m is None:
            return gossip_round_batched_pallas(ws, x, xp, coef, **kw)
        if renorm == "receiver":
            return gossip_round_masked_batched_pallas(ws, m, x, xp, coef, **kw)
        return gossip_round_sender_masked_batched_pallas(
            ws, m, x, xp, coef, **kw)

    return prim


@jax.jit
def gossip_round(w, x, xp, a, b, c):
    """One fused two-tap round on a single graph, auto-padded to MXU tiles.

    W (N, N), X/Xp (N, F), a/b/c scalars (python or traced). Zero padding is
    exact: padded W rows/cols contribute nothing and padded X/Xp entries are
    zero, so the sliced (N, F) output equals the unpadded computation.

    Interpret-mode dispatch (trace-time branch) runs the unfused
    matvec + FMA pair instead: the fusion's win is skipping the x_w HBM
    round-trip, but the interpreter evaluates the fused grid's k-independent
    X/Xp tile loads and FMA predicate on EVERY grid step in Python, which
    costs more than the spill it saves (2.7ms vs 1.8ms per round at
    N200xF300 in BENCH_kernel_perf.json). On a real TPU backend the fused
    kernel is the whole point and is always used.
    """
    if use_interpret():
        return consensus_update(gossip_matvec(w, x), x, xp, a, b, c)
    return gossip_round_batched(w[None], x[None], xp[None], _coef_row(a, b, c))[0]


def _coef_row(a, b, c):
    return jnp.stack(
        [jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32),
         jnp.asarray(c, jnp.float32)]
    ).reshape(1, 3)


@jax.jit
def gossip_round_batched(ws, xs, xps, coefs):
    """Fused round over a stacked ensemble (the sweep-engine inner loop).

    Ws (G, N, N), Xs/Xps (G, N, F), coefs (G, 3) -> (G, N, F) fp32. One
    kernel launch covers the whole grid; per-graph coefficients ride in the
    (G, 3) operand so heterogeneous (alpha, theta) cells share the program.
    """
    g, n, f = xs.shape
    bm, bk, bf = round_tiles(n, f, g)
    np_, fp_ = _round_up(n, max(bm, bk)), _round_up(f, bf)
    wp = jnp.pad(ws.astype(jnp.float32), ((0, 0), (0, np_ - n), (0, np_ - n)))
    xpad = jnp.pad(xs.astype(jnp.float32), ((0, 0), (0, np_ - n), (0, fp_ - f)))
    xppad = jnp.pad(xps.astype(jnp.float32), ((0, 0), (0, np_ - n), (0, fp_ - f)))
    y = gossip_round_batched_pallas(
        wp, xpad, xppad, coefs.astype(jnp.float32),
        bm=bm, bk=bk, bf=bf, interpret=use_interpret(),
    )
    return y[:, :n, :f]


@jax.jit
def gossip_round_masked(w, m, x, xp, a, b, c):
    """One fused masked round on a single graph, auto-padded to MXU tiles.

    ``m`` is the round's (N, N) 0/1 edge-activity mask (ones on the diagonal;
    see ``repro.core.dynamics``): dropped weight returns to the diagonal, so
    W_eff stays doubly stochastic. Mask padding is zeros — padded W entries
    are zero, so they contribute neither matvec nor dropped mass.
    """
    return gossip_round_masked_batched(
        w[None], m[None], x[None], xp[None], _coef_row(a, b, c))[0]


@jax.jit
def gossip_round_masked_batched(ws, ms, xs, xps, coefs):
    """Masked fused round over a stacked ensemble (dynamic-sweep inner loop).

    Ws/Ms (G, N, N), Xs/Xps (G, N, F), coefs (G, 3) -> (G, N, F) fp32.
    """
    g, n, f = xs.shape
    bm, bk, bf = round_tiles(n, f, g)
    np_, fp_ = _round_up(n, max(bm, bk)), _round_up(f, bf)
    wp = jnp.pad(ws.astype(jnp.float32), ((0, 0), (0, np_ - n), (0, np_ - n)))
    mp = jnp.pad(ms.astype(jnp.float32), ((0, 0), (0, np_ - n), (0, np_ - n)))
    xpad = jnp.pad(xs.astype(jnp.float32), ((0, 0), (0, np_ - n), (0, fp_ - f)))
    xppad = jnp.pad(xps.astype(jnp.float32), ((0, 0), (0, np_ - n), (0, fp_ - f)))
    y = gossip_round_masked_batched_pallas(
        wp, mp, xpad, xppad, coefs.astype(jnp.float32),
        bm=bm, bk=bk, bf=bf, interpret=use_interpret(),
    )
    return y[:, :n, :f]


# ---------------------------------------------------------------------------
# segment_round: fused SPARSE Y = a*(W@X) + b*X + c*Xp from an edge list.
# ---------------------------------------------------------------------------


def _segment_tiles(f: int) -> tuple[int, int, int]:
    """(bm, bd, bf) tiles for the ELL kernels; bd is the neighbor-slot axis."""
    return autotune.static_segment_tiles(f)


def _segment_bench(n: int, f: int, g: int):
    """Bench closure for the ELL autotuner: ring-graph dummy, one tile of D."""
    gb = max(1, min(g, 4))
    arrays = {}

    def bench(tiles):
        bm, bd, bf = tiles
        np_, fp_ = _round_up(n, bm), _round_up(f, bf)
        if (np_, fp_) not in arrays:
            idx = jnp.arange(np_, dtype=jnp.int32)
            nbrs = jnp.stack([(idx + 1) % np_, (idx - 1) % np_], axis=0)
            nbrs = jnp.broadcast_to(
                jnp.pad(nbrs, ((0, bd - 2), (0, 0))), (gb, bd, np_))
            wgts = jnp.broadcast_to(
                jnp.pad(jnp.full((2, np_), 0.25, jnp.float32),
                        ((0, bd - 2), (0, 0))), (gb, bd, np_))
            arrays[(np_, fp_)] = (
                nbrs, wgts,
                jnp.full((gb, 1, np_), 0.5, jnp.float32),
                jnp.ones((gb, np_, fp_), jnp.float32),
                jnp.ones((gb, 3), jnp.float32),
            )
        nbrs, wgts, diags, xs, coefs = arrays[(np_, fp_)]
        segment_round_batched_pallas(
            nbrs, wgts, diags, xs, xs, coefs,
            bm=bm, bd=bd, bf=bf, interpret=use_interpret()
        ).block_until_ready()

    return bench


def segment_tiles(n: int, f: int, g: int = 1, tune: bool = False):
    """Autotune-aware (bm, bd, bf) for an ELLPACK (G, N, F) round problem.

    Same contract as ``round_tiles``: ``tune=True`` only from host code
    outside a jit trace; jitted wrappers take the cached/static answer.
    """
    bench = _segment_bench(n, f, g) if tune else None
    return autotune.get_tiles("segment", n, f, g, bench=bench)


def build_ell(edges, edge_w, diag_w, n: int, edge_w_rev=None):
    """ELLPACK (padded per-row neighbor list) arrays from a canonical edge list.

    Host numpy. ``edges`` (E, 2) i < j canonical, ``edge_w`` (E,) the
    undirected weights, ``diag_w`` (N,) the diagonal. Each undirected edge
    becomes two directed slots (one per endpoint row); ``edge_w_rev`` (E,)
    optionally carries the reverse-orientation weight W[j, i] per canonical
    (i, j) for asymmetric bases (push-sum family) — row i's slot then keeps
    ``edge_w`` = W[i, j] while row j's slot gets W[j, i]. None means the
    base is symmetric and ``edge_w`` serves both orientations. Returns

        nbr  (N, D) int32   neighbor node index per slot
        wgt  (N, D) f32     this orientation's weight W[i, nbr[i, d]]
        wrev (N, D) f32     the REVERSE orientation W[nbr[i, d], i]
        slot (N, D) int32   undirected edge id per slot
        diag (N, 1) f32     W's diagonal

    with D = max degree and padding slots wgt = wrev = 0 / nbr = 0 /
    slot = 0 — inert in the kernels whatever their index values.
    ``slot[i, d]`` is the undirected edge id (the RoundMasks bits column)
    the slot mirrors, so the masked kernels gather one (E,) bits row
    instead of an (N, N) mask; ``wrev`` feeds the sender-renorm masked
    kernel's column dropped-mass sum (for symmetric bases wrev == wgt).
    ``ell_slot_major`` turns these into the kernels' operand layout.
    """
    import numpy as np

    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    e = len(edges)
    src = np.concatenate([edges[:, 0], edges[:, 1]])
    dst = np.concatenate([edges[:, 1], edges[:, 0]])
    w_fwd = np.asarray(edge_w, dtype=np.float64)
    w_bwd = w_fwd if edge_w_rev is None else np.asarray(edge_w_rev, np.float64)
    wdir = np.concatenate([w_fwd, w_bwd])       # weight INTO the slot's row
    wrev_dir = np.concatenate([w_bwd, w_fwd])   # weight OUT of the slot's row
    eid = np.concatenate([np.arange(e), np.arange(e)])
    deg = np.bincount(src, minlength=n)
    d_max = max(1, int(deg.max()) if e else 1)
    order = np.argsort(src, kind="stable")
    src_s, dst_s, eid_s = src[order], dst[order], eid[order]
    w_s, wr_s = wdir[order], wrev_dir[order]
    starts = np.concatenate([[0], np.cumsum(deg)[:-1]])
    pos = np.arange(len(src_s)) - starts[src_s]
    nbr = np.zeros((n, d_max), dtype=np.int32)
    wgt = np.zeros((n, d_max), dtype=np.float32)
    wrev = np.zeros((n, d_max), dtype=np.float32)
    slot = np.zeros((n, d_max), dtype=np.int32)
    nbr[src_s, pos] = dst_s
    wgt[src_s, pos] = w_s
    wrev[src_s, pos] = wr_s
    slot[src_s, pos] = eid_s
    diag = np.asarray(diag_w, dtype=np.float32).reshape(n, 1)
    return nbr, wgt, wrev, slot, diag


def ell_slot_major(*arrays):
    """``build_ell``'s row-major (..., N, D) / (..., N, 1) arrays -> the
    kernels' slot-major (..., D, N) / (..., 1, N) layout, node axis on lanes
    (``kernels.segment_round``). Works on numpy and jax arrays, any leading
    (G) dims."""
    return tuple(a.swapaxes(-1, -2) for a in arrays)


@jax.jit
def segment_round(nbr, wgt, slot, diag, x, xp, a, b, c, bits=None):
    """One fused sparse round on a single graph, auto-padded to kernel tiles.

    Row-major ELL operands from ``build_ell``; X/Xp (N, F); a/b/c scalars;
    ``bits`` an optional (E,) 0/1 activity row for this round (None = all
    edges up). Runs the batched kernels at G = 1. Padding is exact: padded
    rows have diag 0 and x 0, padded slots have weight 0.
    """
    n, f = x.shape
    d = nbr.shape[1]
    bm, bd, bf = segment_tiles(n, f)
    bd = min(bd, d)
    np_, dp_, fp_ = _round_up(n, bm), _round_up(d, bd), _round_up(f, bf)
    pad_nd = lambda t: jnp.pad(t, ((0, np_ - n), (0, dp_ - d)))[None]
    nbrs, wgts, slots, diags = ell_slot_major(
        pad_nd(nbr), pad_nd(wgt.astype(jnp.float32)), pad_nd(slot),
        jnp.pad(diag.astype(jnp.float32), ((0, np_ - n), (0, 0)))[None])
    xpad = jnp.pad(x.astype(jnp.float32), ((0, np_ - n), (0, fp_ - f)))[None]
    xppad = jnp.pad(xp.astype(jnp.float32), ((0, np_ - n), (0, fp_ - f)))[None]
    coef = _coef_row(a, b, c)
    kw = dict(bm=bm, bd=bd, bf=bf, interpret=use_interpret())
    if bits is None:
        y = segment_round_batched_pallas(
            nbrs, wgts, diags, xpad, xppad, coef, **kw)
    else:
        y = segment_round_masked_batched_pallas(
            nbrs, wgts, slots, diags, bits.astype(jnp.float32)[None],
            xpad, xppad, coef, **kw)
    return y[0, :n, :f]


def batched_segment_round_prim(nbrs, wgts, slots, diags, *, wrevs=None,
                               bm: int = 128, bd: int = 8, bf: int = 128,
                               interpret: bool | None = None,
                               renorm: str = "receiver"):
    """Sparse fused-round primitive over pre-padded slot-major ELL slices.

    The sparse-layout counterpart of ``batched_round_prim`` — the returned

        prim(x, xp, coef, m=None)

    satisfies the identical layout-polymorphic contract every registry
    algorithm's ``round_body`` is written against, with ``m`` this round's
    (Gp, E) compressed bits rows (NOT an (N, N) mask — the sparse path never
    builds one). ``renorm`` selects where a dropped edge's mass returns
    ("receiver" = row renorm; "sender" = column renorm, which requires the
    reverse weights ``wrevs``). ELL operands are in the kernel layout
    (``ell_slot_major``: nbrs/wgts/wrevs/slots (Gp, D, N), diags (Gp, 1, N))
    and must already be padded to the (bm, bd, bf) tiles by the sweep engine,
    ONCE outside its scan.

    Under a mesh it sees one device's block of cells, like the dense prim.
    """
    if interpret is None:
        interpret = use_interpret()
    if renorm not in ("receiver", "sender"):
        raise ValueError(f"renorm must be receiver or sender, got {renorm!r}")
    if renorm == "sender" and wrevs is None:
        raise ValueError("renorm='sender' needs the wrevs ELL array")
    kw = dict(bm=bm, bd=bd, bf=bf, interpret=interpret)

    def prim(x, xp, coef, m=None):
        if m is None:
            return segment_round_batched_pallas(
                nbrs, wgts, diags, x, xp, coef, **kw)
        if renorm == "receiver":
            return segment_round_masked_batched_pallas(
                nbrs, wgts, slots, diags, m, x, xp, coef, **kw)
        return segment_round_sender_masked_batched_pallas(
            nbrs, wgts, wrevs, slots, diags, m, x, xp, coef, **kw)

    return prim


# ---------------------------------------------------------------------------
# ssd_scan: full-sequence Mamba-2 SSD = intra-chunk kernel + inter-chunk scan.
# ---------------------------------------------------------------------------
#
# pallas_call is an opaque custom call: without a partitioning rule GSPMD
# replicates it (every device would run the FULL global grid — observed as a
# 393216-trip sequential loop per device in the first dry-run). The
# custom_partitioning wrapper below tells GSPMD the op is embarrassingly
# parallel over (batch*chunks, heads): each device runs its LOCAL grid, with
# B/C group projections replicated over the head ('model') axis.

_FORCE_REF = contextvars.ContextVar("ssd_force_ref", default=False)


def in_manual_pod_region() -> bool:
    """True while tracing inside the pod-manual shard_map (consensus mode).

    Model code consults this to avoid constructs XLA cannot partition under
    manual subgroups on this jaxlib: Pallas custom_partitioning, lax.top_k,
    and batched scatter/gather (MoE dispatch)."""
    return _FORCE_REF.get()


@contextlib.contextmanager
def force_ssd_ref():
    """Trace-time escape hatch: jax's custom_partitioning cannot parse the
    manual-subgroup shardings produced inside a partial-auto shard_map
    (NotImplementedError: 'Unhandled OpSharding type ... manual'), so the
    consensus-mode train step traces the SSD intra-chunk block through the
    pure-jnp oracle (GSPMD shards its einsums natively). Everything outside
    the pod-manual region keeps the Pallas kernel."""
    tok = _FORCE_REF.set(True)
    try:
        yield
    finally:
        _FORCE_REF.reset(tok)


def _ssd_chunk_dispatch(xg, ag, bg, cg):
    if _FORCE_REF.get():
        return ssd_chunk_ref(xg, ag, bg, cg)
    if jax.device_count() == 1:
        return ssd_chunk_pallas(xg, ag, bg, cg, interpret=use_interpret())
    return _ssd_chunk_cp(xg, ag, bg, cg)


@custom_partitioning
def _ssd_chunk_cp(xg, ag, bg, cg):
    return ssd_chunk_pallas(xg, ag, bg, cg, interpret=use_interpret())


def _first_dims_spec(shardings, ndim_map):
    """(n_axis, h_axis) from the x operand's sharding; None when replicated."""
    xs = shardings[0]
    spec = xs.spec if isinstance(xs, NamedSharding) else P()
    parts = list(spec) + [None] * 4
    return parts[0], parts[1]


def _ssd_out_shardings(mesh, n_ax, h_ax):
    mk = lambda *s: NamedSharding(mesh, P(*s))
    return (
        mk(n_ax, h_ax, None, None),  # y
        mk(n_ax, h_ax, None, None),  # state
        mk(n_ax, h_ax, None, None),  # din
        mk(n_ax, h_ax, None, None),  # dout
    )


def _ssd_infer(mesh, arg_shapes, result_shape):
    shardings = [a.sharding for a in arg_shapes]
    n_ax, h_ax = _first_dims_spec(shardings, None)
    return _ssd_out_shardings(mesh, n_ax, h_ax)


def _ssd_partition(mesh, arg_shapes, result_shape):
    shardings = [a.sharding for a in arg_shapes]
    n_ax, h_ax = _first_dims_spec(shardings, None)
    mk = lambda *s: NamedSharding(mesh, P(*s))
    arg_shardings = (
        mk(n_ax, h_ax, None, None),   # x
        mk(n_ax, h_ax, None, None),   # a
        mk(n_ax, None, None, None),   # b: groups replicated over 'model'
        mk(n_ax, None, None, None),   # c
    )
    out_shardings = _ssd_out_shardings(mesh, n_ax, h_ax)

    def lower_fn(xg, ag, bg, cg):
        return ssd_chunk_pallas(xg, ag, bg, cg, interpret=use_interpret())

    return mesh, lower_fn, out_shardings, arg_shardings


# Shardy rule: n (batch*chunks) and h (heads) are parallel factors; the
# chunk/state/head_dim factors stay whole per program; g (groups) is
# replicated (its head mapping happens inside the kernel grid).
_ssd_chunk_cp.def_partition(
    partition=_ssd_partition,
    infer_sharding_from_operands=_ssd_infer,
    decode_shardings=True,
    sharding_rule=(
        "n h l p, n h o l, n g l s, n g l s -> n h l p, n h s p, n h o l, n h o q"
    ),
)


def _ssd_core(x, a, b, c, h0, chunk: int, use_kernel: bool):
    """Chunked SSD on pre-padded (T % chunk == 0) fp32 operands.

    The intra-chunk block runs through the Pallas kernel (fwd) or the pure
    jnp oracle (differentiable bwd recompute); the inter-chunk recurrence is
    a log-depth associative scan either way.

    Layout: all intermediate tensors keep the (data-sharded) batch dim major
    and the (model-sharded) head dim separate — merging them would force
    GSPMD to replicate the SSD einsums (verified in the dry-run; this exact
    bug cost 19x flops before the layout was fixed).
    """
    bsz, t, h, dh = x.shape
    g = b.shape[2]
    ds = b.shape[-1]
    nc = t // chunk
    hg = h // g

    def to_blocks(v, nh, feat):
        # (B, T, nh, f) -> (B*nc, nh, L, f); B stays the major factor of dim0
        v = v.reshape(bsz, nc, chunk, nh, feat)
        v = jnp.moveaxis(v, 3, 2)
        return v.reshape(bsz * nc, nh, chunk, feat)

    xg = to_blocks(x.astype(jnp.float32), h, dh)
    bg = to_blocks(b.astype(jnp.float32), g, ds)
    cg = to_blocks(c.astype(jnp.float32), g, ds)
    ag = a.astype(jnp.float32).reshape(bsz, nc, chunk, h)
    ag = jnp.moveaxis(ag, 3, 2).reshape(bsz * nc, h, 1, chunk)

    if use_kernel:
        y_intra, s_chunk, din, dout = _ssd_chunk_dispatch(xg, ag, bg, cg)
    else:
        y_intra, s_chunk, din, dout = ssd_chunk_ref(xg, ag, bg, cg)

    s_chunk = s_chunk.reshape(bsz, nc, h, ds, dh)
    dout = dout.reshape(bsz, nc, h)
    din = din.reshape(bsz, nc, h, chunk)

    def combine(left, right):
        dl, sl = left
        dr, sr = right
        return dl * dr, dr[..., None, None] * sl + sr

    d_inc, h_inc = jax.lax.associative_scan(combine, (dout, s_chunk), axis=1)

    h_shift = jnp.concatenate([jnp.zeros_like(h_inc[:, :1]), h_inc[:, :-1]], axis=1)
    d_shift = jnp.concatenate([jnp.ones_like(d_inc[:, :1]), d_inc[:, :-1]], axis=1)
    h_prev = h_shift + d_shift[..., None, None] * h0[:, None]

    # carry-in: y_inter = din * (C @ h_prev), grouped einsum (no broadcast)
    c_blk = cg.reshape(bsz, nc, g, chunk, ds)
    hp_g = h_prev.reshape(bsz, nc, g, hg, ds, dh)
    y_inter = jnp.einsum("bngls,bnghsd->bnghld", c_blk, hp_g)
    y_inter = y_inter.reshape(bsz, nc, h, chunk, dh)
    y = y_intra.reshape(bsz, nc, h, chunk, dh) + din[..., None] * y_inter

    h_final = h_inc[:, -1] + d_inc[:, -1][..., None, None] * h0
    y = jnp.moveaxis(y, 2, 3).reshape(bsz, t, h, dh)
    return y, h_final


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd_cv(x, a, b, c, h0, chunk):
    return _ssd_core(x, a, b, c, h0, chunk, use_kernel=True)


def _ssd_cv_fwd(x, a, b, c, h0, chunk):
    out = _ssd_core(x, a, b, c, h0, chunk, use_kernel=True)
    return out, (x, a, b, c, h0)


def _ssd_cv_bwd(chunk, res, cotangents):
    x, a, b, c, h0 = res
    _, vjp = jax.vjp(
        lambda x_, a_, b_, c_, h0_: _ssd_core(x_, a_, b_, c_, h0_, chunk, use_kernel=False),
        x, a, b, c, h0,
    )
    return vjp(cotangents)


_ssd_cv.defvjp(_ssd_cv_fwd, _ssd_cv_bwd)


def ssd_scan(x, a, b, c, h0=None, *, chunk: int = 128):
    """Chunked SSD selective scan. (Not jitted here: callers jit the whole
    step, and the force_ssd_ref trace-time flag must not be frozen into a
    jit cache entry.)

    Args:
      x: (B, T, H, dh) inputs (post in-proj, post conv, gated branch).
      a: (B, T, H) per-step log decay (must be <= 0 for stability).
      b: (B, T, G, ds) input->state projection, G groups (mamba2: G=1);
         heads are group-mapped inside the kernel grid, never broadcast.
      c: (B, T, G, ds) state->output projection.
      h0: optional (B, H, ds, dh) initial state (decode/prefill carry).
      chunk: intra-chunk length (multiple of 128 on real TPU).

    Returns: (y (B, T, H, dh) fp32, h_final (B, H, ds, dh) fp32).
    """
    bsz, t, h, dh = x.shape
    ds = b.shape[-1]
    t_orig = t
    if t % chunk:
        # pad to a chunk multiple with identity dynamics: a=0 (decay exp(0)=1)
        # and x=b=0 leave the state untouched; padded y rows are sliced off.
        pad = chunk - t % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if h0 is None:
        h0 = jnp.zeros((bsz, h, ds, dh), dtype=jnp.float32)
    else:
        h0 = h0.astype(jnp.float32)
    y, h_final = _ssd_cv(
        x.astype(jnp.float32), a.astype(jnp.float32),
        b.astype(jnp.float32), c.astype(jnp.float32), h0, chunk
    )
    return y[:, :t_orig], h_final
