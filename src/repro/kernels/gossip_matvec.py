"""Pallas TPU kernel: blocked W @ X for the consensus simulation engine.

Computes Y = W X with W (N, N) the consensus weight matrix and X (N, F) the
per-node state block (F = trials/features). This is the inner loop of the
paper-scale numerical experiments (Section IV): hundreds of trials x
thousands of iterations, so the matvec dominates simulator runtime.

TPU mapping: classic 3-loop tiling with the K (contraction) dimension as the
innermost grid axis, fp32 accumulation directly in the output VMEM block
(revisited across the K steps — Pallas keeps the block resident because the
output index map is independent of k). Tiles default to 128 x 128 x 512:
(bm, bk) and (bk, bf) input tiles are MXU-aligned (128 = systolic array edge),
and the working set stays comfortably inside the ~16 MB VMEM budget:
128*512*4 = 256 KB out + 128*128*4 + 128*512*4 = 320 KB in per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["gossip_matvec_kernel", "gossip_matvec_pallas"]


def gossip_matvec_kernel(w_ref, x_ref, y_ref):
    """One (bm, bk) @ (bk, bf) partial product accumulated into y (bm, bf)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    y_ref[...] += jnp.dot(
        w_ref[...], x_ref[...], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(
    jax.jit, static_argnames=("bm", "bk", "bf", "interpret")
)
def gossip_matvec_pallas(
    w: jax.Array,
    x: jax.Array,
    *,
    bm: int = 128,
    bk: int = 128,
    bf: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Y = W @ X with fp32 accumulation; operands pre-padded to tile multiples."""
    n, k = w.shape
    k2, f = x.shape
    if k != k2:
        raise ValueError(f"shape mismatch: W {w.shape} @ X {x.shape}")
    if n % bm or k % bk or f % bf:
        raise ValueError(f"shapes ({n},{k},{f}) not multiples of tiles ({bm},{bk},{bf})")
    grid = (n // bm, f // bf, k // bk)
    return pl.pallas_call(
        gossip_matvec_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bf), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bf), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, f), jnp.float32),
        interpret=interpret,
    )(w, x)
