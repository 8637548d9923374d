"""Batched sweep engine: the whole experiment grid in ONE jitted program.

Axes and their mapping:

* ``G`` (grid axis)  — every sweep cell: (topology family x size x graph
  draw) x theta design x alpha. Stacked as the leading dim of the (G, N, N)
  weight batch and split across devices over the mesh 'data' axis (mesh
  from ``repro.launch.mesh``): the whole scan runs inside ``shard_map``,
  each device stepping its own block of cells (``_shard_rows``).
* ``N`` (node axis)  — padded to the largest network in the grid; whole on
  every device.
* ``F`` (trial axis) — initial-condition columns, whole on every device.
* ``T`` (iterations) — a single ``lax.scan``; the carry is (x, x_prev) only,
  so memory is O(G N F) while the returned MSE trajectory is O(T G F).

The per-round body comes from the consensus-algorithm registry
(``repro.core.algorithms``): the grid is partitioned along G by algorithm
(``Ensemble.layout``), each partition carries its own tap tuple through the
scan and applies its registered ``round_body`` against the engine's
fused-round primitive. ``backend='jax'`` lowers the primitive to a batched
einsum round; ``backend='pallas'`` drives the batched-grid fused kernel
(``kernels.ops.batched_round_prim``) — matvec accumulation and the FMA taps
in one kernel launch per round, no intermediate x_w in HBM.

The same scan serves both weight layouts: dense feeds (G, N, N) stacked
matrices to the primitives above, while ``SweepSpec(layout="sparse")``
(auto-selected for large N) feeds edge-space operands — directed
gather/segment-sum rounds on the jax backend, batched ELLPACK
segment-reduce kernels (``kernels.ops.batched_segment_round_prim``) on
pallas — so W is never materialized and million-node grids cost O(E), not
O(N^2). ``trial_chunk`` tiles the F axis into independent column blocks
when even O(G N F) state is too big.

Everything funnels through one jit entry (``_sweep_scan``): a full sweep —
and the degenerate G=1 sweep that ``repro.core.simulator.simulate`` routes
through — costs exactly one compilation per (shape, backend) signature.
``trace_count()`` exposes the compile counter so tests can assert that.
"""
from __future__ import annotations

import dataclasses
import functools
import inspect
import itertools

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_cpu_mesh

from .grid import (
    ConfigMeta,
    Ensemble,
    RoundMasks,
    SweepSpec,
    build_ensemble,
    build_round_masks,
)

__all__ = ["SweepResult", "counters", "run_batch", "run_ensemble", "run_sweep",
           "trace_count"]

# The engine's counters, integer adds on the host:
#   traces          compilations of the engine body (bumped at trace time;
#                   tests assert a full heterogeneous grid costs exactly one)
#   batches         run_batch calls past validation (one per F chunk)
#   bytes_in        bytes of every array put on the device, bits included
#   bytes_out       bytes of the outputs fetched back to the host
#   entries_real    round-matrix entries x trial columns of real nodes
#   entries_padded  the same for what the round computes: dense G n_pad^2
#                   F_pad, ELL G n_pad D F_pad, directed arrays G 2E F
_COUNTERS = dict.fromkeys(("traces", "batches", "bytes_in", "bytes_out",
                           "entries_real", "entries_padded"), 0)


def trace_count() -> int:
    return _COUNTERS["traces"]


def counters() -> dict[str, int]:
    """A copy of the engine's counters (see ``_COUNTERS``), totals since
    import: read them before and after a run and take the difference."""
    return dict(_COUNTERS)


def _algo_init(algo, x0_p, coefs_p, mask_p):
    """Dispatch ``init_carry`` across contract generations (trace time).

    The time-varying-coefficient contract passes the partition's traced
    param rows and node mask so aux-carrying algorithms can seed estimator
    state; registrations written against the original one-argument contract
    (including user registrations outside this repo) keep working via the
    same signature-inspection idiom as ``grid._sparse_tick_rho``.
    """
    try:
        takes = "params" in inspect.signature(algo.init_carry).parameters
    except (TypeError, ValueError):
        takes = False
    if takes:
        return algo.init_carry(x0_p, params=coefs_p, mask=mask_p)
    return algo.init_carry(x0_p)


def _dense_round_prim(wsp, renorm: str):
    """Batched dense einsum round over one G partition's (Gp, N, N) slice.

    ``renorm`` picks where a masked-off entry W_ij returns: "receiver" sums
    the dropped weights per ROW (row sums survive — the doubly-stochastic
    family's rule), "sender" per COLUMN (column sums survive — the
    mass-conserving push-sum family).
    """
    axis = 2 if renorm == "receiver" else 1
    # HIGHEST: f32 on the TPU's MXU. Its default single bf16 pass rounds W,
    # which then is no longer doubly stochastic and the mean drifts.
    matmul = functools.partial(
        jnp.einsum, "gij,gjf->gif", preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)

    def prim(x, xp, coef, m=None):
        a = coef[:, 0, None, None]
        b = coef[:, 1, None, None]
        c = coef[:, 2, None, None]
        if m is None:
            xw = matmul(wsp, x)
        else:
            wm = wsp * m
            drop = jnp.sum(wsp - wm, axis=axis)                   # (Gp, N)
            xw = matmul(wm, x) + drop[:, :, None] * x
        return a * xw + b * x + c * xp
    return prim


def _sparse_round_prim(pack, s: int, e: int, nn: int, renorm: str):
    """Directed-arrays gather/segment_sum round over one G partition.

    Each undirected canonical edge appears as two directed slots (forward
    weight W_ij then reverse W_ji — equal for symmetric bases); ``eid`` maps
    a slot back to its RoundMasks bits column. Padded slots have weight 0
    (their src/dst/eid indices are inert), padded rows have diag 0 and x 0,
    so padding is exact. Dropped mass from masked-off edges returns to the
    RECEIVING row's diagonal under "receiver" renorm or to the SENDING
    neighbour's diagonal under "sender" renorm — the latter keeps column
    sums (total mass) intact for the push-sum family.
    """
    src, dst, wdir, eid, diag = pack
    sg, dg = src[s:e], dst[s:e]
    wg = wdir[s:e].astype(jnp.float32)
    eg, gg = eid[s:e], diag[s:e].astype(jnp.float32)
    receiver = renorm == "receiver"

    def prim(x, xp, coef, m=None):
        a = coef[:, 0, None, None]
        b = coef[:, 1, None, None]
        c = coef[:, 2, None, None]
        if m is None:
            def one(s_, d_, w_, g_, x_):
                contrib = w_[:, None] * jnp.take(x_, d_, axis=0)
                return (jax.ops.segment_sum(
                    contrib, s_, num_segments=nn)
                    + g_[:, None] * x_)
            xw = jax.vmap(one)(sg, dg, wg, gg, x)
        else:
            def one(s_, d_, w_, e_, g_, m_, x_):
                sel = jnp.take(m_, e_)                    # (2E,)
                wt = w_ * sel
                drop = jax.ops.segment_sum(
                    w_ - wt, s_ if receiver else d_, num_segments=nn)
                contrib = wt[:, None] * jnp.take(x_, d_, axis=0)
                return (jax.ops.segment_sum(
                    contrib, s_, num_segments=nn)
                    + (g_ + drop)[:, None] * x_)
            xw = jax.vmap(one)(sg, dg, wg, eg, gg, m, x)
        return a * xw + b * x + c * xp
    return prim


@functools.partial(
    jax.jit,
    static_argnames=("num_iters", "use_kernels", "tiles", "layout", "algo_gen",
                     "sparse", "debug_checks", "dbg_sites"))
def _sweep_scan(ws, x0, mask, inv_n, coefs, num_iters: int, use_kernels: bool,
                tiles: tuple[int, int, int] | None = None, bits=None, eidx=None,
                layout: tuple[tuple[str, int, int], ...] | None = None,
                algo_gen: int = 0, sparse: bool = False,
                debug_checks: bool = False,
                dbg_sites: tuple[tuple[int, ...], ...] = ()):
    """One jitted scan for the whole (possibly mixed-algorithm) grid.

    ``layout`` is the static tuple of (algorithm spec, start, stop) G
    partitions (``Ensemble.layout``; None = one two-tap partition). Each
    partition carries its own registry algorithm's tap tuple through the
    scan and applies its own ``round_body``, written against the fused-round
    primitive this function supplies — einsum round on the jax backend, the
    fused batched Pallas kernel (masked or not) on the pallas backend. The
    MSE reduction reads every partition's display state via the algorithm's
    ``display`` hook (carry slot 0 by default; a ratio of taps for the
    push-sum family). Masked-round renormalization follows each partition's
    ``mass_renorm`` ("receiver" keeps row sums, "sender" keeps column sums);
    both renorms have fused masked kernels on the pallas backend (row- and
    column-masked variants), so no partition ever drops to a jnp fallback
    there.

    ``bits``/``eidx`` (None on the static path) carry the compressed
    (T, G, E) uint8 edge-activity schedule: the scan expands each round's
    bits into the dense (G, N, N) 0/1 mask *inside* the body — one round's
    mask lives in registers/VMEM while the per-round effective matrices
    W_eff(t) = W.*M + diag((W.*(1-M))@1) are never materialized in HBM
    (``repro.core.dynamics`` has the model; ``async_pairwise`` rides the
    same machinery with one-hot bits over its pairwise base matrix).

    ``sparse`` (static) switches ``ws`` to the edge-space operand pytree:
    ``(src, dst, wdir, eid, diag)`` directed arrays on the jax backend, or
    the pre-padded slot-major ``(nbrs, wgts, wrevs, slots, diags)`` ELL
    stacks on pallas. The
    dynamic path then feeds each round's raw (Gp, E) bits rows straight to
    the primitive — the dense (G, N, N) mask expansion never happens, which
    is what makes N = 1e5–1e6 dynamic-topology sweeps fit in memory.

    ``algo_gen`` is the registry generation (static): layout names resolve
    to algorithm OBJECTS only at trace time, so a re-registered name must
    miss the jit cache rather than silently run the shadowed round body.
    """
    del algo_gen  # participates only in the jit cache key
    _COUNTERS["traces"] += 1  # trace-time side effect: counts compilations

    from repro.core.algorithms import get_algorithm

    if not sparse:
        ws = ws.astype(jnp.float32)
    x0 = x0.astype(jnp.float32)
    mask = mask.astype(jnp.float32)[:, :, None]
    inv_n = inv_n.astype(jnp.float32)
    coefs = coefs.astype(jnp.float32)
    dynamic = bits is not None
    if layout is None:
        layout = (("accel", 0, x0.shape[0]),)

    if dynamic and not sparse:
        n = ws.shape[1]
        eye = jnp.eye(n, dtype=bool)

        def expand(bits_t, ei):
            """(Gp, E) bits -> (Gp, N, N) dense mask: 1 on live edges + diag.

            Padded edge slots carry index (0, 0); whatever they scatter onto
            the diagonal is overwritten by the eye fill, so padding is exact.
            Its ops carry ``sweep.expand`` in their HLO ``op_name``, which is
            how a device trace tells the expansion from the rest of a round.
            The sorts and fusions the TPU compiler makes of a large scatter
            carry no metadata; ``bench/program_trace.py`` names them by
            their users.
            """
            def one(bg, ig):
                b = bg.astype(jnp.float32)
                m0 = jnp.zeros((n, n), jnp.float32)
                m0 = m0.at[ig[:, 0], ig[:, 1]].set(b)
                m0 = m0.at[ig[:, 1], ig[:, 0]].set(b)
                return m0

            with jax.named_scope("sweep.expand"):
                return jnp.where(eye, 1.0, jax.vmap(one)(bits_t, ei))

    # per-cell target: the true initial average over real nodes (padding is 0)
    xbar = x0.sum(axis=1, keepdims=True) * inv_n[:, None, None]   # (G, 1, F)

    if sparse and use_kernels:
        # Sparse pallas: pre-padded slot-major ELL slices drive the batched
        # segment-reduce kernel; `m` is this round's (Gp, E) bits rows,
        # gathered per slot by undirected edge id — no (N, N) mask anywhere.
        # ``renorm`` routes straight into the kernel layer: receiver-renorm
        # partitions run the row-masked kernel, sender-renorm partitions
        # (push-sum family) the column-masked kernel via the wrev array.
        from repro.kernels.ops import batched_segment_round_prim, use_interpret

        nbrs, wgts, wrevs, slots, diags = ws
        bm, bd, bf = tiles
        interpret = use_interpret()

        def make_prim(s, e, renorm):
            return batched_segment_round_prim(
                nbrs[s:e], wgts[s:e], slots[s:e], diags[s:e],
                wrevs=wrevs[s:e], bm=bm, bd=bd, bf=bf,
                interpret=interpret, renorm=renorm)
    elif sparse:
        nn = x0.shape[1]

        def make_prim(s, e, renorm):
            return _sparse_round_prim(ws, s, e, nn, renorm)
    elif use_kernels:
        # run_batch pre-pads the whole batch to the kernel tiles ONCE (and
        # passes those tiles in), so the scan body drives the raw batched
        # kernel directly — no per-round pad/slice materializations on the
        # carry (the wrapper in kernels.ops pays those per call; over
        # thousands of rounds they would dwarf the x_w round-trip the
        # fusion removes). ``renorm`` picks the masked kernel variant
        # (receiver = row renorm, sender = column renorm) — dynamic
        # sender-renorm partitions no longer drop to the einsum fallback.
        from repro.kernels.ops import batched_round_prim, use_interpret

        bm, bk, bf = tiles
        interpret = use_interpret()

        def make_prim(s, e, renorm):
            return batched_round_prim(
                ws[s:e], bm=bm, bk=bk, bf=bf, interpret=interpret,
                renorm=renorm)
    else:
        def make_prim(s, e, renorm):
            return _dense_round_prim(ws[s:e], renorm)

    # per-partition algorithm objects and primitives (trace-time python)
    parts = []
    for name, s, e in layout:
        algo = get_algorithm(name)
        prim = algo.pallas_round(ws[s:e], tiles=tiles) \
            if (use_kernels and not sparse and algo.pallas_round is not None) \
            else make_prim(s, e, algo.mass_renorm)
        parts.append((algo, s, e, prim))

    if debug_checks:
        # runtime twin of the static coefficient-mass pass: checkify guards
        # at exactly the prim sites whose coefficient streams are traced
        # (data-dependent — the analysis pass could only ASSUME convexity
        # there), plus an isfinite guard on every round output. Static sites
        # are already proven by `python -m repro.analysis --check`; guarding
        # e.g. poly_filter's individually-non-convex Horner taps would
        # misfire, so run_batch precomputes `dbg_sites` per partition from
        # the same classifier (outside this trace — jaxpr interpretation
        # can't nest inside the checkify transform).
        from jax.experimental import checkify

    def mse_of(x):
        d = (x - xbar) * mask
        return (d * d).sum(axis=1) * inv_n[:, None]               # (G, F)

    def body(carry, xs_t):
        t, bits_t = xs_t if dynamic else (xs_t, None)
        new_carry, disp = [], []
        for i, ((algo, s, e, prim), sub) in enumerate(zip(parts, carry)):
            if dynamic:
                m = bits_t[s:e].astype(jnp.float32) if sparse \
                    else expand(bits_t[s:e], eidx[s:e])
            else:
                m = None
            if debug_checks:
                calls = itertools.count()  # trace-time call-order counter

                def pr(x, xp, coef, _p=prim, _m=m, _a=algo,
                       _sites=dbg_sites[i], _c=calls):
                    k = next(_c)
                    if k in _sites:
                        ssum = coef[..., 0] + coef[..., 1] + coef[..., 2]
                        checkify.check(
                            jnp.all(jnp.abs(ssum - 1.0) <= 1e-3),
                            f"coefficient-mass guard: traced (a,b,c) stream "
                            f"at {_a.spec} round_body site {k} strayed from "
                            f"sum 1 (tol 1e-3)")
                    out = _p(x, xp, coef, _m)
                    checkify.check(
                        jnp.all(jnp.isfinite(out)),
                        f"nonfinite state out of {_a.spec} round_body "
                        f"site {k}")
                    return out
            else:
                def pr(x, xp, coef, _p=prim, _m=m):
                    return _p(x, xp, coef, _m)
            sub = algo.round_body(pr, coefs[s:e], sub, t)
            new_carry.append(sub)
            disp.append(algo.display(sub))
        x_all = disp[0] if len(disp) == 1 else jnp.concatenate(disp, axis=0)
        return tuple(new_carry), mse_of(x_all)

    init = tuple(_algo_init(algo, x0[s:e], coefs[s:e], mask[s:e])
                 for algo, s, e, _ in parts)
    t_idx = jnp.arange(num_iters, dtype=jnp.int32)
    carry_fin, mse_tail = jax.lax.scan(
        body, init, (t_idx, bits) if dynamic else t_idx, length=num_iters
    )
    disp_fin = [algo.display(sub)
                for (algo, _, _, _), sub in zip(parts, carry_fin)]
    x_fin = disp_fin[0] if len(disp_fin) == 1 else jnp.concatenate(disp_fin, axis=0)
    mse = jnp.concatenate([mse_of(x0)[None], mse_tail], axis=0)   # (T+1, G, F)
    return x_fin, jnp.moveaxis(mse, 0, 1), carry_fin              # (G, T+1, F)


def _prep_pallas_dense(ws, x0):
    """Pad (ws, x0) to the dense-kernel tile multiples ONCE, host-side.

    Returns ``(ws, x0, tiles, n, f)`` with the padded node/trial extents.
    ``ws=None`` skips the weight pad (the static analyzer replays this prep
    on abstract shapes — keeping it here is what guarantees the jaxpr it
    walks has exactly the shapes ``run_batch`` compiles).
    """
    from repro.kernels import ops as kops

    g, n, f = x0.shape
    tiles = kops.round_tiles(n, f, g, tune=True)
    bm, bk, bf = tiles
    n_pad = kops._round_up(n, max(bm, bk)) - n
    f_pad = kops._round_up(f, bf) - f
    if n_pad or f_pad:
        if ws is not None:
            ws = np.pad(ws, ((0, 0), (0, n_pad), (0, n_pad)))
        x0 = np.pad(x0, ((0, 0), (0, n_pad), (0, f_pad)))
    return ws, x0, tiles, n + n_pad, f + f_pad


def _prep_pallas_sparse(x0, edges, edge_w, diag_w, edge_counts, edge_w_rev):
    """Build the padded slot-major ELL pack for the sparse-pallas layout.

    Per-cell ELL arrays are built ONCE, host-side (N already padded to the
    row tile so ``build_ell`` sizes them directly), the neighbor-slot axis
    is padded to the common tile-rounded max degree (the slot tile shrinks
    to that degree when it is smaller), and the stack is laid out for the
    kernels (``ops.ell_slot_major``). Padded slots have weight 0. Returns
    ``(x0, wpack, tiles, n, f)``.
    """
    from repro.kernels import ops as kops

    g, n, f = x0.shape
    bm, bd, bf = kops.segment_tiles(n, f, g, tune=True)
    n_pad = kops._round_up(n, bm) - n
    f_pad = kops._round_up(f, bf) - f
    if n_pad or f_pad:
        x0 = np.pad(x0, ((0, 0), (0, n_pad), (0, f_pad)))
    n, f = n + n_pad, f + f_pad
    ec = np.full(g, edges.shape[1], dtype=np.int64) \
        if edge_counts is None else np.asarray(edge_counts, dtype=np.int64)
    ells = [
        kops.build_ell(
            edges[i, :int(ec[i])], edge_w[i, :int(ec[i])],
            np.pad(diag_w[i], (0, n_pad)), n,
            edge_w_rev=None if edge_w_rev is None
            else edge_w_rev[i, :int(ec[i])])
        for i in range(g)
    ]
    d_raw = max(e_[0].shape[1] for e_ in ells)
    bd = min(bd, d_raw)
    d_max = kops._round_up(d_raw, bd)

    def stack(k):
        return np.stack([np.pad(e_[k], ((0, 0), (0, d_max - e_[k].shape[1])))
                         for e_ in ells])

    wpack = kops.ell_slot_major(stack(0), stack(1), stack(2), stack(3),
                                np.stack([e_[4] for e_ in ells]))
    return x0, wpack, (bm, bd, bf), n, f


def _prep_jax_sparse(edges, edge_w, diag_w, edge_w_rev):
    """Directed-arrays pack for the sparse jax layout.

    Every canonical undirected edge becomes two directed slots (both
    orientations); the eid row maps a directed slot back to its undirected
    RoundMasks bits column. Padded edge slots carry weight 0, so their
    indices are inert.
    """
    g = edges.shape[0]
    e_und = edges.shape[1]
    return (
        np.concatenate([edges[:, :, 0], edges[:, :, 1]], axis=1),
        np.concatenate([edges[:, :, 1], edges[:, :, 0]], axis=1),
        np.concatenate(
            [edge_w, edge_w if edge_w_rev is None else edge_w_rev],
            axis=1),
        np.ascontiguousarray(np.broadcast_to(
            np.concatenate([np.arange(e_und, dtype=np.int32)] * 2)[None],
            (g, 2 * e_und))),
        diag_w,
    )


def run_batch(
    ws,
    x0,
    coefs,
    node_counts=None,
    *,
    num_iters: int,
    backend: str = "jax",
    mesh=None,
    round_masks: RoundMasks | None = None,
    algos: tuple[tuple[str, int, int], ...] | None = None,
    edges=None,
    edge_w=None,
    diag_w=None,
    edge_counts=None,
    edge_w_rev=None,
    trial_chunk: int | None = None,
    return_taps: bool = False,
    debug_checks: bool = False,
):
    """Evaluate ``num_iters`` rounds over a stacked (G, N, N) ensemble.

    Args:
      ws:    (G, N, N) stacked base matrices (zero-padded rows/cols OK), or
        ``None`` for the SPARSE layout — then ``edges`` (G, Emax, 2) int32
        canonical i<j edge lists (zero-padded slots), ``edge_w`` (G, Emax)
        undirected edge weights (0 on padding), ``diag_w`` (G, N) diagonals
        and optionally ``edge_counts`` (G,) real edge counts carry the
        weights in O(E) instead of O(N^2). The jax backend runs a
        gather/segment-sum round over the directed-arrays form; pallas runs
        the batched ELL segment-reduce kernel (``kernels.ops.build_ell`` +
        ``batched_segment_round_prim``). Same registry round bodies, same
        RoundMasks schedules (bits columns are undirected edge ids in both
        layouts), outputs match the dense layout to f32 roundoff.
        ``edge_w_rev`` (G, Emax) optionally carries the reverse-orientation
        weight W[j, i] per canonical edge (i, j) for asymmetric bases
        (push-sum family); None means W is symmetric and ``edge_w`` serves
        both orientations.
      x0:    (G, N, F) initial-condition blocks (zeros on padded nodes).
      coefs: (G, C) per-cell algorithm parameter rows ((a, b, c) for the
        default two-tap partition).
      node_counts: (G,) real node count per cell; None means no padding.
      num_iters: rounds T.
      backend: 'jax' (einsum round) or 'pallas' (fused batched kernel).
      mesh: optional jax Mesh; defaults to the host mesh when more than one
        device is visible. The G axis is split over 'data': each algorithm
        partition is padded to a multiple of the axis size with replicas of
        its last cell (dropped on return) and dealt out evenly, and every
        device runs the scan on its own block.
      round_masks: optional ``RoundMasks`` (compressed per-round edge-activity
        bits, see ``repro.sweep.grid.build_round_masks``): routes through the
        dynamic-topology scan, where each round runs on the mass-preservingly
        re-normalized masked W of that round. Required whenever a partition's
        algorithm needs a per-tick schedule (``async_pairwise``).
      algos: static (algorithm spec, start, stop) partition layout along G
        (``Ensemble.layout``); None = one two-tap ("accel") partition.
      trial_chunk: optional F-axis tile: run the sweep in independent
        column blocks of this many trials and concatenate — trial columns
        never interact, so results match the unchunked run to f32 roundoff
        (only XLA's reduction vectorization differs with F) while peak
        memory drops from O(G N F) to O(G N chunk). This is what makes
        N = 1e5–1e6 sparse sweeps with many trials fit on one host.
      return_taps: when True, additionally return the final carry taps per
        merged algorithm partition as a tuple of
        ``(spec, start, stop, (tap0, tap1, ...))`` entries, each tap a
        (stop - start, N, F) numpy array. This exposes the raw two-state
        (value, mass) taps of the push-sum family so conformance tests can
        assert total-mass conservation directly, not just the displayed
        ratio. Only the algorithm's ``num_taps`` state slots are returned:
        auxiliary carry slots (``num_aux`` — estimator probes, running
        spectral estimates) are internal state and invariant-exempt by
        contract.
      debug_checks: opt-in runtime twin of the static analysis pass
        (``repro.analysis``): threads ``jax.experimental.checkify`` guards
        through the scan — an isfinite assertion on every round output, and
        a coefficient-mass (|a+b+c - 1| <= 1e-3) assertion at exactly the
        prim sites whose coefficient streams are traced (data-dependent,
        e.g. ``accel_adapt``'s adaptive stream — the cases the static pass
        can only flag). Raises ``jax.experimental.checkify.JaxRuntimeError``
        on the first violated guard. Costs one extra compilation and the
        functionalized check overhead; leave off for production sweeps.

    Note on ``trial_chunk`` with aux-carrying algorithms: ``accel_adapt``
    pools its F trial columns as independent estimator probes (the Gelfand
    quotient maxes over all of them), so chunking the F axis changes the
    probe pool and hence the coefficient stream — chunked and unchunked
    adaptive runs agree in distribution but not to roundoff. Static-
    coefficient algorithms keep the exact-match guarantee.

    Returns:
      (x_final (G, N, F), mse (G, T+1, F)) as numpy arrays, plus the taps
      tuple when ``return_taps``.
    """
    f_total = np.shape(x0)[2]
    if trial_chunk is not None and 0 < trial_chunk < f_total:
        x0 = np.asarray(x0)
        outs = [
            run_batch(
                ws, x0[:, :, s:s + trial_chunk], coefs, node_counts,
                num_iters=num_iters, backend=backend, mesh=mesh,
                round_masks=round_masks, algos=algos, edges=edges,
                edge_w=edge_w, diag_w=diag_w, edge_counts=edge_counts,
                edge_w_rev=edge_w_rev, return_taps=return_taps,
                debug_checks=debug_checks,
            )
            for s in range(0, f_total, trial_chunk)
        ]
        x_cat = np.concatenate([o[0] for o in outs], axis=2)
        m_cat = np.concatenate([o[1] for o in outs], axis=2)
        if not return_taps:
            return x_cat, m_cat
        taps = tuple(
            (name, s_, e_, tuple(
                np.concatenate([o[2][k][3][j] for o in outs], axis=2)
                for j in range(len(sub))))
            for k, (name, s_, e_, sub) in enumerate(outs[0][2])
        )
        return x_cat, m_cat, taps

    # Host spans on the profiler's clock (no-ops without a profiler session):
    # sweep.run_batch holds prep (validation, padding, packing), put (the
    # host-to-device copy; folded into launch under a mesh), launch (the
    # jitted call returning), wait (the device finishing) and fetch (the
    # device-to-host copy and un-padding), in that order.
    with TraceAnnotation("sweep.run_batch"):
        with TraceAnnotation("sweep.prep"):
            if backend not in ("jax", "pallas"):
                raise ValueError(
                    f"unknown backend {backend!r} (sweep runs 'jax' or 'pallas')")
            from repro.core.algorithms import get_algorithm, registry_generation

            sparse = ws is None
            if sparse and (edges is None or edge_w is None or diag_w is None):
                raise ValueError(
                    "sparse mode (ws=None) requires edges, edge_w and diag_w arrays")
            x0 = np.asarray(x0)
            if sparse:
                edges = np.asarray(edges, dtype=np.int32)
                edge_w = np.asarray(edge_w, dtype=np.float32)
                diag_w = np.asarray(diag_w, dtype=np.float32)
                if edge_w_rev is not None:
                    edge_w_rev = np.asarray(edge_w_rev, dtype=np.float32)
            else:
                ws = np.asarray(ws)
            coefs = np.asarray(coefs)
            g, n, f = x0.shape
            if node_counts is None:
                node_counts = np.full(g, n, dtype=np.int64)
            node_counts = np.asarray(node_counts)
            if algos is None:
                algos = (("accel", 0, g),)
            if [s for _, s, _ in algos] != [0] + [e for _, _, e in algos][:-1] \
                    or algos[-1][2] != g:
                raise ValueError(f"algorithm layout {algos} does not tile G={g}")
            # coalesce adjacent same-algorithm partitions (merged ensembles
            # produce them) so the scan body keeps one fused round per
            # distinct algorithm
            merged = [list(algos[0])]
            for name, s, e in algos[1:]:
                if name == merged[-1][0]:
                    merged[-1][2] = e
                else:
                    merged.append([name, s, e])
            algos = tuple((n_, s_, e_) for n_, s_, e_ in merged)
            parts_out = algos  # pre-G-padding layout; frames the returned taps
            if round_masks is None and any(
                    get_algorithm(name).needs_schedule for name, _, _ in algos):
                raise ValueError(
                    "this grid contains a schedule-bearing algorithm "
                    "(async_pairwise): pass "
                    "round_masks=build_round_masks(ens, num_iters)")

            bits = eidx = None
            if round_masks is not None:
                bits = np.asarray(round_masks.bits, dtype=np.uint8)
                eidx = np.asarray(round_masks.idx, dtype=np.int32)
                if bits.shape[0] != num_iters or bits.shape[1] != g:
                    raise ValueError(
                        f"round_masks bits {bits.shape} do not cover "
                        f"(num_iters={num_iters}, G={g}) rounds x cells"
                    )
                if eidx.shape != (g, bits.shape[2], 2):
                    raise ValueError(
                        f"round_masks idx {eidx.shape} inconsistent with "
                        f"bits {bits.shape}"
                    )

            n_orig, f_orig = n, f
            tiles = None
            wpack = None
            if backend == "pallas" and sparse:
                x0, wpack, tiles, n, f = _prep_pallas_sparse(
                    x0, edges, edge_w, diag_w, edge_counts, edge_w_rev)
            elif backend == "pallas":
                # pad N/F to the kernel's tile multiples ONCE, outside the
                # scan; the node mask (below) keeps padded rows out of the
                # MSE, padded trial columns are sliced off the outputs. The
                # jax backend stays unpadded (padding a 20-node graph to 128
                # would be a ~40x flop tax there). The tiles chosen here are
                # threaded into _sweep_scan as static args so padding and
                # kernel blocking can never drift apart.
                ws, x0, tiles, n, f = _prep_pallas_dense(ws, x0)
            elif sparse:
                wpack = _prep_jax_sparse(edges, edge_w, diag_w, edge_w_rev)

            mask = (np.arange(n)[None, :] < node_counts[:, None]).astype(np.float32)
            inv_n = (1.0 / node_counts).astype(np.float32)

            # G=1 (the simulate() degenerate sweep) gains nothing from the
            # mesh and would pay device_count replicas of the whole problem
            # via G-padding — only auto-engage the mesh for real grids.
            if mesh is None and g > 1 and jax.device_count() > 1:
                mesh = make_cpu_mesh()

            w_arrays = wpack if sparse else (ws,)
            nw = len(w_arrays)
            arrays = (*w_arrays, x0, mask, inv_n, coefs)
            # which cell each row of the run's G axis holds, overall and per
            # partition (a partition's carry comes back in its own row order)
            rows = np.arange(g)
            part_rows = [np.arange(s_, e_) for _, s_, e_ in algos]
            if mesh is not None:
                rows, part_rows, algos = _shard_rows(algos, mesh.shape["data"])
                arrays = tuple(a[rows] for a in arrays)
                if bits is not None:
                    bits, eidx = bits[:, rows], eidx[rows]

            statics = dict(
                num_iters=num_iters, use_kernels=(backend == "pallas"),
                tiles=tiles, layout=tuple(algos),
                algo_gen=registry_generation(), sparse=sparse)
            if debug_checks:
                from repro.analysis.coefficient import traced_coef_sites

                statics.update(debug_checks=True, dbg_sites=tuple(
                    tuple(sorted(traced_coef_sites(name))) for name, _, _ in algos))
            real, padded = _entries(sparse, backend, node_counts, edge_counts,
                                    edges, w_arrays, len(rows), n, f_orig, f)
            _COUNTERS["batches"] += 1
            _COUNTERS["entries_real"] += real
            _COUNTERS["entries_padded"] += padded
            _COUNTERS["bytes_in"] += _device_nbytes((arrays, bits, eidx))

        if mesh is None:
            with TraceAnnotation("sweep.put"):
                arrays, bits, eidx = jax.block_until_ready(
                    jax.device_put((arrays, bits, eidx)))
        with TraceAnnotation("sweep.launch"):
            ws_in = tuple(arrays[:nw]) if sparse else arrays[0]
            if mesh is None and not debug_checks:
                out = _sweep_scan(
                    ws_in, *arrays[nw:], bits=bits, eidx=eidx, **statics)
            else:
                out = _scan_program(
                    mesh, bits is not None, tuple(sorted(statics.items())))(
                        ws_in, *arrays[nw:], bits, eidx)
        with TraceAnnotation("sweep.wait"):
            x_fin, mse, carry_fin = jax.block_until_ready(out)
        with TraceAnnotation("sweep.fetch"):
            x_fin, mse = np.asarray(x_fin), np.asarray(mse)
            fetched = x_fin.nbytes + mse.nbytes
            # the first row of each cell carries it (pad rows repeat a cell)
            first = np.unique(rows, return_index=True)[1]
            x_fin = x_fin[first, :n_orig, :f_orig]
            mse = mse[first, :, :f_orig]
            if not return_taps:
                _COUNTERS["bytes_out"] += fetched
                return x_fin, mse
            # Aux carry slots (everything past num_taps) are algorithm-
            # internal estimator state, not network state: excluded by
            # contract.
            taps = []
            for (name, s_p, e_p), prow, sub in zip(parts_out, part_rows, carry_fin):
                keep = np.unique(prow, return_index=True)[1]
                full = [np.asarray(t) for t in sub[:get_algorithm(name).num_taps]]
                fetched += sum(t.nbytes for t in full)
                taps.append((name, s_p, e_p, tuple(
                    t[keep, :n_orig, :f_orig] for t in full)))
            _COUNTERS["bytes_out"] += fetched
            return x_fin, mse, tuple(taps)


def _device_nbytes(tree) -> int:
    """Bytes the arrays of ``tree`` take on the device (JAX's dtypes:
    float64 is stored as float32 unless x64 is on)."""
    return sum(int(np.size(a)) * np.dtype(
        jax.dtypes.canonicalize_dtype(np.asarray(a).dtype)).itemsize
        for a in jax.tree.leaves(tree))


def _entries(sparse, backend, node_counts, edge_counts, edges, w_arrays,
             g_run, n, f_orig, f):
    """(real, padded) round-matrix entries x trial columns of one batch.

    Real: the entries of each cell's own graph (dense n_g^2, sparse 2 e_g
    directed edges) over its F columns. Padded: what the round computes over
    the ``g_run`` rows the device steps — dense G n^2 F at the padded
    extents; ELL G n D F (``D`` the padded slot count); directed arrays
    G 2 E_max F.
    """
    if not sparse:
        return (int((node_counts.astype(np.int64) ** 2).sum()) * f_orig,
                g_run * n * n * f)
    ec = np.full(len(node_counts), edges.shape[1], np.int64) \
        if edge_counts is None else np.asarray(edge_counts, np.int64)
    real = 2 * int(ec.sum()) * f_orig
    if backend == "pallas":
        _, d, n_slots = w_arrays[0].shape            # slot-major (G, D, N)
        return real, g_run * d * n_slots * f
    return real, g_run * w_arrays[0].shape[1] * f   # (G, 2 E_max) directed


def _shard_rows(algos, ndata: int):
    """Lay the G axis out for ``ndata`` devices running the scan locally.

    Each algorithm partition is padded to a multiple of ``ndata`` (repeating
    its last cell) and dealt out in equal contiguous chunks, so every
    device's block holds the same partition layout — one local program, no
    cross-device traffic inside the scan. Returns ``(rows, part_rows,
    local_layout)``:
    ``rows[k]`` is the cell at row k of the sharded G axis (device d owns
    rows [d*B, (d+1)*B)), ``part_rows[p]`` the cells of partition p in the
    order its carry comes back, ``local_layout`` the per-device partitions.
    """
    chunks, local, start = [], [], 0
    for name, s, e in algos:
        idx = np.arange(s, e)
        idx = np.concatenate([idx, np.full((-len(idx)) % ndata, e - 1)])
        chunks.append(idx.reshape(ndata, -1))
        local.append((name, start, start + chunks[-1].shape[1]))
        start += chunks[-1].shape[1]
    rows = np.concatenate(chunks, axis=1).reshape(-1)
    return rows, [c.reshape(-1) for c in chunks], tuple(local)


@functools.lru_cache(maxsize=None)
def _scan_program(mesh, dynamic: bool, statics: tuple):
    """The jitted scan for a mesh run and/or the checkify twin.

    Under a mesh the whole scan runs inside ``shard_map`` over the 'data'
    axis: each device steps its own block of G cells (``_shard_rows``), so
    the round kernels only ever see local operands — nothing for the
    partitioner to split, and nothing crosses devices. ``debug_checks``
    functionalizes the user checks with checkify (the debug program is a
    different computation, error state carried). Cached per (mesh, statics)
    so repeat runs reuse the compiled program.
    """
    kw = dict(statics)
    debug = kw.get("debug_checks", False)

    def fn(ws, x0, mask, inv_n, coefs, bits, eidx):
        return _sweep_scan.__wrapped__(ws, x0, mask, inv_n, coefs, bits=bits,
                                       eidx=eidx, **kw)

    if mesh is not None:
        data = P("data")
        fn = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(data,) * 5 + ((P(None, "data"), data) if dynamic
                                   else (P(), P())),
            out_specs=data, check_vma=False)
    if debug:
        from jax.experimental import checkify

        checked = jax.jit(checkify.checkify(fn, errors=checkify.user_checks))

        def run(*args):
            err, out = checked(*args)
            err.throw()
            return out

        return run
    return jax.jit(fn)


@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Trajectories + per-cell metadata for one engine run."""

    ensemble: Ensemble
    x_final: np.ndarray        # (G, N, F)
    mse: np.ndarray            # (G, T+1, F)
    # Final carry taps per merged algorithm partition, populated only when
    # the run asked for them (``run_ensemble(..., return_taps=True)``):
    # ((spec, start, stop, (tap0, tap1, ...)), ...). Lets tests inspect the
    # raw (value, mass) pair of push-sum-family cells behind the displayed
    # ratio.
    taps: tuple | None = None

    @property
    def configs(self) -> tuple[ConfigMeta, ...]:
        return self.ensemble.configs

    @property
    def num_iters(self) -> int:
        return self.mse.shape[1] - 1

    def averaging_times(self, eps: float = 1e-5, sustained: bool = False) -> np.ndarray:
        """(G, F) empirical eps-averaging times (Eq. 16) from the MSE curves.

        Default (``sustained=False``): first t with
        ||x(t) - xbar|| <= eps ||x(0) - xbar||, i.e. mse(t) <= eps^2 mse(0)
        — the paper's first-crossing definition, matching
        ``metrics.averaging_time``. On non-monotone curves (masked dynamics,
        randomized pairwise exchanges) first crossing under-reports:
        ``sustained=True`` instead returns the first t after which the MSE
        *stays* below the threshold through the end of the horizon. Both
        return -1 where the criterion is never (or never durably) met.
        """
        thresh = (eps * eps) * self.mse[:, :1, :]                 # (G, 1, F)
        hit = self.mse <= np.maximum(thresh, 0.0)                 # (G, T+1, F)
        if sustained:
            # suffix-AND along t: stays[t] == all(hit[t:])
            hit = np.flip(np.logical_and.accumulate(
                np.flip(hit, axis=1), axis=1), axis=1)
        t = np.argmax(hit, axis=1)
        reached = hit.any(axis=1)
        return np.where(reached, t, -1).astype(np.int64)

    def cells(self, **match) -> list[int]:
        """Indices of cells whose ConfigMeta fields equal all of ``match``."""
        out = []
        for i, c in enumerate(self.configs):
            if all(getattr(c, k) == v for k, v in match.items()):
                out.append(i)
        return out


def run_ensemble(
    ens: Ensemble,
    *,
    num_iters: int,
    backend: str = "jax",
    mesh=None,
    round_masks: RoundMasks | None = None,
    trial_chunk: int | None = None,
    return_taps: bool = False,
    debug_checks: bool = False,
) -> SweepResult:
    """Evaluate an already-built (possibly merged) grid in one program.

    ``round_masks`` carries per-round edge-failure schedules; pass the result
    of ``build_round_masks(ens, num_iters)`` (or None for the static path —
    ``run_sweep`` wires this automatically from ``SweepSpec.dynamics``).
    Sparse-layout ensembles (``ens.is_sparse``) route through the edge-space
    engine automatically; ``trial_chunk`` tiles the F axis for memory;
    ``return_taps`` populates ``SweepResult.taps`` with each partition's
    final carry taps (the push-sum family's raw (value, mass) pair);
    ``debug_checks`` threads the checkify runtime guards through the scan
    (see ``run_batch``).
    """
    out = run_batch(
        ens.ws, ens.x0, ens.coefs, ens.node_counts,
        num_iters=num_iters, backend=backend, mesh=mesh,
        round_masks=round_masks, algos=ens.layout,
        edges=ens.edges, edge_w=ens.edge_w, diag_w=ens.diag_w,
        edge_counts=ens.edge_counts, edge_w_rev=ens.edge_w_rev,
        trial_chunk=trial_chunk, return_taps=return_taps,
        debug_checks=debug_checks,
    )
    x_fin, mse = out[0], out[1]
    taps = out[2] if return_taps else None
    return SweepResult(ensemble=ens, x_final=x_fin, mse=mse, taps=taps)


def run_sweep(
    spec: SweepSpec,
    *,
    num_iters: int,
    backend: str = "jax",
    mesh=None,
    trial_chunk: int | None = None,
    debug_checks: bool = False,
) -> SweepResult:
    """Build the grid of ``spec`` and evaluate it in one jitted program.

    When ``spec.dynamics`` contains non-static schedules (e.g.
    ``dynamics=("static", "bernoulli:0.1")``), the per-round edge-failure
    bits are sampled host-side (graph-keyed RNG: coupled across failure
    probabilities and shared across designs) and the whole failure grid runs
    as one jitted vmapped scan, exactly like every other sweep axis.

    ``spec.layout`` picks the weight storage: "dense" stacks (G, N, N)
    matrices, "sparse" keeps per-cell edge lists and runs gather/segment-sum
    rounds (required for N >> 1e4), "auto" switches to sparse when the
    largest size exceeds ``grid.SPARSE_EXACT_SPECTRUM_CUTOFF``. Pair large-N
    sparse sweeps with ``trial_chunk`` to bound peak memory.
    """
    ens = build_ensemble(spec)
    masks = build_round_masks(ens, num_iters, seed=spec.seed)
    return run_ensemble(
        ens, num_iters=num_iters, backend=backend, mesh=mesh,
        round_masks=masks, trial_chunk=trial_chunk,
        debug_checks=debug_checks,
    )
