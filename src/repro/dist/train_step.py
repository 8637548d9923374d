"""Gradient-sync train step: allreduce, gossip, or accelerated gossip.

``make_train_step`` wires one model + optimizer + mesh into a jittable
``(params, opt_state, batch) -> (params, opt_state, metrics)`` step under one
of three sync modes (``SyncConfig``):

* ``allreduce`` — classic data parallelism: one replica of the parameters,
  the global batch sharded over the ('pod', 'data') axes, GSPMD inserts the
  cross-pod all-reduce. Recovery from pod loss is checkpoint-restart.
* ``gossip`` / ``accel_gossip`` — decentralized consensus: each pod keeps its
  own replica (parameters gain a leading (P, ...) pod axis, ``pod_stacked``),
  computes gradients on its own shard of the batch, then mixes gradients with
  R rounds of (accelerated) gossip over the fabric graph instead of an
  all-reduce. R = ceil(log eps / log rho) comes off the fabric — the paper's
  Theorem 2 is why ``accel_gossip`` needs ~sqrt the rounds of ``gossip``.
  A pod failure is then a graph edit (``repro.runtime.elastic``), not a
  world stall.

The consensus region is a shard_map pinned to the 'pod' mesh axis; every
other dimension keeps its GSPMD sharding, so each parameter shard gossips
with the matching shard of the neighbour pods — per-round wire cost is two
neighbour payloads regardless of P.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import optim
from .compression import BF16Wire, Int8Wire
from .gossip import PodFabric, accel_gossip, gossip, make_fabric
from .sharding import abstract_params, partition_spec

PyTree = Any

__all__ = ["SyncConfig", "TrainStep", "make_train_step"]

_WIRES = {None: None, "bf16": BF16Wire, "int8": Int8Wire}


@dataclasses.dataclass(frozen=True)
class SyncConfig:
    """How gradients cross pods."""

    mode: str = "allreduce"        # allreduce | gossip | accel_gossip
    eps: float = 1e-2              # consensus epsilon (rounds knob)
    topology: str = "ring"         # pod fabric graph
    wire: str | None = None        # None | bf16 | int8 (EF compression)
    backup_rounds: int = 0         # straggler slack (ElasticFabric policy)

    def __post_init__(self):
        if self.mode not in ("allreduce", "gossip", "accel_gossip"):
            raise ValueError(f"unknown sync mode {self.mode!r}")
        if self.wire not in _WIRES:
            raise ValueError(f"unknown wire {self.wire!r}")


@dataclasses.dataclass(frozen=True)
class TrainStep:
    """One lowered-shape train step + the input specs to lower/run it with."""

    fn: Callable                   # (params, opt_state, batch) -> (params, opt_state, metrics)
    init_state: Callable           # (key, model, opt) -> (params, opt_state)
    params_sharding: PyTree        # ShapeDtypeStructs with NamedShardings
    opt_sharding: PyTree
    batch_sharding: PyTree
    fabric: PodFabric | None       # None in allreduce mode
    rounds: int                    # consensus rounds per step (0 for allreduce)
    pod_stacked: bool              # params/batch carry a leading (P, ...) axis
    mesh: Any
    sync: SyncConfig


def _opt_sharding(opt, params_sds: PyTree, mesh, num_pods: int, stacked: bool) -> PyTree:
    """Best-effort shardings for the optimizer state.

    Subtrees that mirror the parameter tree exactly (AdamW's mu/nu) reuse the
    parameter specs; anything else (step counts, factored Adafactor moments)
    keeps the leading pod axis in stacked mode and replicates the rest.
    """
    init = jax.vmap(opt.init) if stacked else opt.init
    state_sds = jax.eval_shape(init, params_sds)
    param_struct = jax.tree.structure(params_sds)

    def generic(leaf):
        pod = stacked and leaf.ndim >= 1 and leaf.shape[0] == num_pods
        return jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype,
            sharding=NamedSharding(mesh, P("pod") if pod else P()),
        )

    def mirror(sub):
        return jax.tree.map(
            lambda leaf, src: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=src.sharding
            ),
            sub, params_sds,
        )

    if isinstance(state_sds, dict):
        return {
            k: mirror(sub) if jax.tree.structure(sub) == param_struct
            else jax.tree.map(generic, sub)
            for k, sub in state_sds.items()
        }
    return jax.tree.map(generic, state_sds)


def _accum_grads(loss_fn, params, batch, grad_accum: int):
    """value_and_grad with optional micro-batch accumulation (mean-of-means)."""
    if grad_accum <= 1:
        return jax.value_and_grad(loss_fn)(params, batch)

    micro = jax.tree.map(
        lambda t: t.reshape(grad_accum, t.shape[0] // grad_accum, *t.shape[1:]),
        batch,
    )

    def body(carry, mb):
        loss, grads = jax.value_and_grad(loss_fn)(params, mb)
        acc_loss, acc_grads = carry
        return (acc_loss + loss, jax.tree.map(jnp.add, acc_grads, grads)), None

    zero = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(body, zero, micro)
    scale = 1.0 / grad_accum
    return loss * scale, jax.tree.map(lambda g: g * scale, grads)


def make_train_step(
    model,
    opt,
    mesh,
    sync: SyncConfig,
    global_batch: int,
    seq_len: int,
    grad_accum: int = 1,
) -> TrainStep:
    """Build the train step + input specs for one (model, mesh, sync) cell."""
    axis_sizes = dict(mesh.shape)
    num_pods = axis_sizes.get("pod", 1)
    consensus = sync.mode != "allreduce"
    stacked = consensus and num_pods > 1
    fabric = make_fabric(num_pods, sync.topology) if consensus else None
    if stacked and global_batch % num_pods:
        raise ValueError(f"global batch {global_batch} not divisible by {num_pods} pods")
    if consensus:
        rounds = (
            fabric.rounds_for(sync.eps) if sync.mode == "accel_gossip"
            else fabric.rounds_for_memoryless(sync.eps)
        ) + sync.backup_rounds
    else:
        rounds = 0
    wire_cls = _WIRES[sync.wire]

    # ---- input specs -------------------------------------------------------
    params_sds = abstract_params(
        model.param_specs, mesh, stacked_pods=num_pods if stacked else 0
    )
    batch_sds = {}
    for name, (shape, axes, dtype) in model.batch_spec(global_batch, seq_len).items():
        if stacked:
            shape = (num_pods, shape[0] // num_pods, *shape[1:])
            axes = ("pod", *axes)
        batch_sds[name] = jax.ShapeDtypeStruct(
            shape, dtype,
            sharding=NamedSharding(mesh, partition_spec(shape, axes, mesh)),
        )
    opt_sds = _opt_sharding(opt, params_sds, mesh, num_pods, stacked)
    param_pspecs = jax.tree.map(lambda s: s.sharding.spec, params_sds)

    # ---- gradient sync (the consensus region) ------------------------------
    def sync_grads(grads: PyTree) -> PyTree:
        flat, treedef = jax.tree.flatten(grads)
        specs = tuple(jax.tree.leaves(param_pspecs))

        def body(*blocks):
            wire = wire_cls() if wire_cls is not None else None
            run = accel_gossip if sync.mode == "accel_gossip" else gossip
            return tuple(
                run(b[0], "pod", fabric, rounds, wire=wire)[None] for b in blocks
            )

        synced = shard_map(
            body, mesh=mesh, in_specs=specs, out_specs=specs, check_vma=False
        )(*flat)
        return jax.tree.unflatten(treedef, synced)

    # ---- the step ----------------------------------------------------------
    def loss_fn(p, b):
        return model.loss(p, b)

    def fn(params, opt_state, batch):
        if stacked:
            loss, grads = jax.vmap(
                lambda p, b: _accum_grads(loss_fn, p, b, grad_accum)
            )(params, batch)
            grads = sync_grads(grads)
            gnorm = jax.vmap(optim.global_norm)(grads)
            updates, opt_state = jax.vmap(opt.update)(grads, opt_state, params)
        else:
            loss, grads = _accum_grads(loss_fn, params, batch, grad_accum)
            gnorm = optim.global_norm(grads)
            updates, opt_state = opt.update(grads, opt_state, params)
        params = jax.tree.map(jnp.add, params, updates)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    # ---- state init --------------------------------------------------------
    def init_state(key, model_, opt_):
        params = model_.init(key)
        if stacked:
            # every pod starts from the same replica: already in consensus
            params = jax.tree.map(
                lambda t: jnp.broadcast_to(t[None], (num_pods, *t.shape)), params
            )
            opt_state = jax.vmap(opt_.init)(params)
        else:
            opt_state = opt_.init(params)
        params = jax.device_put(params, jax.tree.map(lambda s: s.sharding, params_sds))
        opt_state = jax.device_put(opt_state, jax.tree.map(lambda s: s.sharding, opt_sds))
        return params, opt_state

    return TrainStep(
        fn=fn,
        init_state=init_state,
        params_sharding=params_sds,
        opt_sharding=opt_sds,
        batch_sharding=batch_sds,
        fabric=fabric,
        rounds=rounds,
        pod_stacked=stacked,
        mesh=mesh,
        sync=sync,
    )
