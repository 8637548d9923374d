"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch yi-9b --smoke \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt --resume auto

Wires together: config -> model -> optimizer (per-config schedule) ->
synthetic data stream -> train step (allreduce or consensus sync) ->
async checkpointing with auto-resume. On CPU this trains the reduced (smoke)
configs; on a real cluster the same driver runs the full configs on the
production mesh (launch.mesh).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import optim
from ..checkpoint import AsyncCheckpointer, latest_valid, restore
from ..configs import ARCH_IDS, get_config
from ..data import SyntheticStream
from ..dist import SyncConfig, make_train_step
from ..models import build
from .compile_cache import enable_compile_cache
from .mesh import make_cpu_mesh, make_production_mesh

__all__ = ["main", "train_loop"]


def train_loop(
    arch: str,
    smoke: bool = True,
    steps: int = 100,
    global_batch: int = 8,
    seq_len: int = 128,
    sync_mode: str = "allreduce",
    pods: int = 1,
    lr: float = 3e-3,
    ckpt_dir: str | None = None,
    ckpt_every: int = 50,
    resume: str = "none",
    log_every: int = 10,
    production_mesh: bool = False,
    seed: int = 0,
):
    cfg = get_config(arch, smoke=smoke)
    model = build(cfg)
    mesh = (
        make_production_mesh(multi_pod=pods > 1)
        if production_mesh else make_cpu_mesh(pods=pods)
    )
    opt = optim.for_config(cfg, total_steps=steps, peak_lr=lr)
    sync = SyncConfig(mode=sync_mode)
    ts = make_train_step(
        model, opt, mesh, sync, global_batch, seq_len,
        grad_accum=cfg.grad_accum if not smoke else 1,
    )
    params, opt_state = ts.init_state(jax.random.PRNGKey(seed), model, opt)

    start = 0
    ck = AsyncCheckpointer(ckpt_dir, keep=3) if ckpt_dir else None
    if ckpt_dir and resume == "auto":
        found = latest_valid(ckpt_dir)
        if found:
            start, state, extra = restore(found[1])
            params = jax.tree.map(jnp.asarray, state["params"])
            opt_state = jax.tree.map(jnp.asarray, state["opt_state"])
            print(f"resumed from step {start} ({found[1]})")

    stream = SyntheticStream(cfg, global_batch, seq_len, seed=seed)
    step_fn = jax.jit(ts.fn, donate_argnums=(0, 1))
    losses = []
    t0 = time.time()
    for step in range(start, steps):
        batch = jax.tree.map(jnp.asarray, stream.batch_at(step))
        if ts.pod_stacked:
            p = ts.fabric.num_pods
            batch = jax.tree.map(
                lambda t: t.reshape(p, t.shape[0] // p, *t.shape[1:]), batch
            )
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(np.mean(np.asarray(metrics["loss"])))
        losses.append(loss)
        if step % log_every == 0 or step == steps - 1:
            print(f"step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(np.mean(np.asarray(metrics['grad_norm']))):.3f} "
                  f"({(time.time()-t0):.1f}s)", flush=True)
        if ck and (step + 1) % ckpt_every == 0:
            ck.submit(step + 1, {"params": params, "opt_state": opt_state},
                      extra={"arch": arch})
    if ck:
        ck.submit(steps, {"params": params, "opt_state": opt_state}, extra={"arch": arch})
        ck.close(flush=True)
    return losses, params


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="yi-9b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--sync", default="allreduce",
                    choices=["allreduce", "gossip", "accel_gossip"])
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default="none", choices=["none", "auto"])
    ap.add_argument("--production-mesh", action="store_true")
    a = ap.parse_args(argv)
    enable_compile_cache()
    losses, _ = train_loop(
        a.arch, smoke=a.smoke, steps=a.steps, global_batch=a.batch,
        seq_len=a.seq, sync_mode=a.sync, pods=a.pods, lr=a.lr,
        ckpt_dir=a.ckpt_dir, ckpt_every=a.ckpt_every, resume=a.resume,
        production_mesh=a.production_mesh,
    )
    print(f"final loss {losses[-1]:.4f} (from {losses[0]:.4f})")


if __name__ == "__main__":
    main()
