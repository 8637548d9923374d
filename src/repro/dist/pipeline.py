"""GPipe-style pipeline parallelism over a 'stage' mesh axis.

Small and self-contained: stages hold disjoint layer blocks, microbatches
march through a shard_map ppermute ring. ``pipeline_forward`` is the SPMD
program; ``reference_forward`` is the single-device layer loop it must match
to fp tolerance. Used by the multidevice suite and as the template for
stacking pipeline stages under the consensus fabric (a 'pod' axis outside
the 'stage' axis composes: gossip syncs gradients per stage block).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_forward", "reference_forward"]


def _stage_block(w1, w2, h):
    """Apply one stage's layer stack: h -> tanh(h @ w1[l]) @ w2[l] per layer."""
    for layer in range(w1.shape[0]):
        h = jnp.tanh(h @ w1[layer]) @ w2[layer]
    return h


def reference_forward(w1, w2, x):
    """Sequential reference: every stage's layers applied in order.

    w1 (S, L, D, H), w2 (S, L, H, D), x (M, B, D) -> (M, B, D); microbatches
    are independent rows of the leading axis.
    """
    def one(mb):
        h = mb
        for stage in range(w1.shape[0]):
            h = _stage_block(w1[stage], w2[stage], h)
        return h

    return jax.vmap(one)(x)


def pipeline_forward(w1, w2, x, mesh, axis_name: str = "stage"):
    """GPipe forward: stage s runs microbatch t-s at tick t, handoffs via
    ppermute. M + S - 1 ticks total; the last stage's outputs are broadcast
    back with a psum of a one-hot-masked collect (all stages see the result,
    matching the replicated out_spec).
    """
    num_stages = dict(mesh.shape)[axis_name]
    num_micro = x.shape[0]

    def body(w1_blk, w2_blk, x_all):
        w1_, w2_ = w1_blk[0], w2_blk[0]
        idx = jax.lax.axis_index(axis_name)
        is_first = idx == 0
        is_last = idx == num_stages - 1
        carry = jnp.zeros(x_all.shape[1:], x_all.dtype)
        shift = [(i, (i + 1) % num_stages) for i in range(num_stages)]
        collected = []
        for t in range(num_micro + num_stages - 1):
            feed = x_all[t] if t < num_micro else jnp.zeros_like(carry)
            h = jnp.where(is_first, feed, carry)
            out = _stage_block(w1_, w2_, h)
            collected.append(jnp.where(is_last, out, jnp.zeros_like(out)))
            carry = jax.lax.ppermute(out, axis_name, shift)
        # microbatch m leaves the last stage at tick m + S - 1
        stacked = jnp.stack(
            [collected[m + num_stages - 1] for m in range(num_micro)]
        )
        return jax.lax.psum(stacked, axis_name)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P()),
        out_specs=P(),
        check_vma=False,
    )(w1, w2, x)
