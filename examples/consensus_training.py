"""Decentralized training demo: 4 'pods' on this host, gradients synchronized
by the paper's accelerated gossip instead of an all-reduce.

Spawns a subprocess with 4 XLA host devices on the CPU backend (the flags
must be set before jax initializes; ``JAX_PLATFORMS=cpu`` keeps the demo off
any attached accelerator, whose single chip could not hold a 4-pod mesh), builds the (pod=4, data=1, model=1) mesh, and trains a small LM
with sync modes {allreduce, gossip, accel_gossip}, printing the loss curves
and the consensus round counts (accel needs ~sqrt of the memoryless rounds).

    PYTHONPATH=src python examples/consensus_training.py
"""
import os
import subprocess
import sys

INNER = r"""
import os
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import build
from repro.dist import make_train_step, SyncConfig
from repro.data import SyntheticStream
from repro import optim
from repro.launch.mesh import make_mesh

cfg = get_config("yi-9b", smoke=True)
model = build(cfg)
opt = optim.adamw(3e-3)
mesh = make_mesh((4, 1, 1), ("pod", "data", "model"))
stream = SyntheticStream(cfg, global_batch=16, seq_len=64, seed=0)

for mode in ("allreduce", "gossip", "accel_gossip"):
    ts = make_train_step(model, opt, mesh, SyncConfig(mode=mode, eps=1e-3),
                         global_batch=16, seq_len=64)
    params, opt_state = ts.init_state(jax.random.PRNGKey(0), model, opt)
    step = jax.jit(ts.fn, donate_argnums=(0, 1))
    losses = []
    for i in range(40):
        batch = jax.tree.map(jnp.asarray, stream.batch_at(i))
        if ts.pod_stacked:
            batch = jax.tree.map(lambda t: t.reshape(4, 4, *t.shape[1:]), batch)
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(np.mean(np.asarray(m["loss"]))))
    rounds = ts.rounds if ts.fabric else 0
    lam2 = ts.fabric.lambda2 if ts.fabric else 0.0
    print(f"{mode:13s} rounds/step={rounds:3d} lambda2={lam2:.3f} "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
print("accel_gossip reaches the same loss as allreduce with bounded-staleness")
print("gradient mixing; rounds ratio gossip/accel shows the paper's speedup.")
"""


def main():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", INNER], env=env)
    sys.exit(r.returncode)


if __name__ == "__main__":
    main()
