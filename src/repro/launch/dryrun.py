import os

# A CPU rehearsal of the 512-chip programs: 512 forced host devices, and
# never the attached accelerator (one chip cannot hold these meshes, and
# a run that took it would keep every other process off it).
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "").replace(
        "--xla_force_host_platform_device_count=512", ""
    )
).strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver builds the exact production program — train step
(grad accumulation, optimizer, gradient sync mode), prefill, or decode — as
abstract ShapeDtypeStructs with production NamedShardings, then:

    lowered  = jax.jit(step).lower(*input_specs(...))
    compiled = lowered.compile()
    memory   = compiled.memory_analysis()     # proves it fits
    roofline = analyze_hlo(compiled.as_text())  # FLOPs/bytes/collectives

and writes one JSON record per cell under --out. The (16,16) single-pod mesh
is the roofline table; the (2,16,16) multi-pod mesh proves the 'pod' axis
(consensus fabric) shards. Failures here are bugs in the system.

Run a single cell:   python -m repro.launch.dryrun --arch yi-9b --shape train_4k
Run everything:      python -m repro.launch.dryrun --all [--jobs N]
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback

import jax

# Workaround: the Shardy partitioner crashes (C++ CHECK in
# PartitionGather/ExpandDeviceGroupsWithIota) on embedding gathers inside the
# pod-manual shard_map on the 3-axis 512-chip mesh; GSPMD classic handles the
# same programs. Tracked as an XLA bug; revisit on newer jaxlibs.
jax.config.update("jax_use_shardy_partitioner", False)

import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCH_IDS, SHAPES, applicable, get_config
from ..dist import SyncConfig, make_train_step
from ..dist import sharding as shd
from ..models import build
from .. import optim
from .mesh import make_production_mesh
from .roofline import analyze_hlo, peaks, roofline_report

__all__ = ["input_specs", "dryrun_cell", "main"]

# the production meshes are v5e pods (``launch/mesh.py``); the rehearsal
# runs on host devices, so the roofline is read against the target chip
PRODUCTION_DEVICE_KIND = "TPU v5 lite"

# params above this bf16-bytes-per-chip budget keep FSDP for serving
SERVE_TP_HBM_BUDGET = 8e9


def _model_flops(cfg, shape) -> float:
    """Standard 6ND (train) / 2ND (inference) useful-FLOPs yardstick."""
    n = cfg.active_params()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def input_specs(arch: str, shape_name: str, multi_pod: bool, sync_mode: str = "accel_gossip",
                pad_heads: int = 0):
    """(step_fn, arg specs tuple, metadata) for one dry-run cell."""
    cfg = get_config(arch)
    if pad_heads:
        cfg = dataclasses.replace(cfg, tp_pad_heads=pad_heads)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build(cfg)
    num_pods = 2 if multi_pod else 1

    if shape.kind == "train":
        opt = optim.for_config(cfg)
        ts = make_train_step(
            model, opt, mesh,
            SyncConfig(mode=sync_mode if multi_pod else "allreduce"),
            shape.global_batch, shape.seq_len, grad_accum=cfg.grad_accum,
        )
        meta = {"rounds": ts.rounds, "pod_stacked": ts.pod_stacked,
                "grad_accum": cfg.grad_accum, "sync": sync_mode if multi_pod else "allreduce"}
        return ts.fn, (ts.params_sharding, ts.opt_sharding, ts.batch_sharding), meta

    # serving: params bf16; pure-TP when the model fits, else FSDP+TP
    tp = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    pure_tp = cfg.num_params() * 2 / tp <= SERVE_TP_HBM_BUDGET
    rules = shd.serving_rules() if pure_tp else None
    params = shd.abstract_params(model.param_specs, mesh, dtype=jnp.bfloat16, rules=rules)
    act = shd.make_activations(mesh, include_pod=True)
    meta = {"serving_layout": "tp" if pure_tp else "fsdp+tp"}

    if shape.kind == "prefill":
        batch_tree = {
            k: v for k, v in model.batch_spec(shape.global_batch, shape.seq_len).items()
            if k != "labels"
        }
        batch = shd.abstract_tree(batch_tree, mesh)

        def step(p, b):
            return model.prefill(p, b, shape.seq_len, act)

        return step, (params, batch), meta

    # decode: one new token against a seq_len cache
    cache = shd.abstract_tree(model.cache_specs(shape.global_batch, shape.seq_len), mesh)
    if cfg.num_heads:
        # pin expanded K/V to the cache storage sharding (see make_activations)
        s_len = min(cfg.sliding_window or shape.seq_len, shape.seq_len)
        kv_spec = shd.partition_spec(
            (shape.global_batch, s_len, cfg.physical_kv_heads, cfg.resolved_head_dim),
            ("batch", "cache_seq", "kv_heads", "head_dim"), mesh,
        )
        act = shd.make_activations(mesh, include_pod=True, kv_spec=kv_spec)
    bspec = shd.batch_pspecs(
        {"tokens": ((shape.global_batch, 1), ("batch", None), jnp.int32)}, mesh
    )["tokens"]
    token = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32,
                                 sharding=NamedSharding(mesh, bspec))
    pos = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32,
                               sharding=NamedSharding(mesh, P()))

    def step(p, tok, pos_, c):
        return model.decode(p, tok, pos_, c, act)

    return step, (params, token, pos, cache), meta


def dryrun_cell(arch: str, shape_name: str, multi_pod: bool,
                sync_mode: str = "accel_gossip", verbose: bool = True,
                pad_heads: int = 0) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = applicable(cfg, shape)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "params": cfg.num_params(),
        "active_params": cfg.active_params(),
    }
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec
    chips = 512 if multi_pod else 256
    t0 = time.time()
    try:
        step, specs, meta = input_specs(arch, shape_name, multi_pod, sync_mode, pad_heads)
        rec.update(meta)
        # donate params/opt-state (train) or cache (decode): in-place updates
        donate = ()
        if shape.kind == "train":
            donate = (0, 1)
        elif shape.kind == "decode":
            donate = (3,)
        lowered = jax.jit(step, donate_argnums=donate).lower(*specs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
        mem["total_hbm_bytes"] = (
            mem["argument_bytes"] + mem["output_bytes"] + mem["temp_bytes"]
            - mem["alias_bytes"]
        )
        ca = compiled.cost_analysis() or {}
        cost = analyze_hlo(compiled.as_text(), num_pods=2 if multi_pod else 1)
        rep = roofline_report(cost, chips, _model_flops(cfg, shape),
                              peaks(PRODUCTION_DEVICE_KIND))
        rec.update(
            status="ok",
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            memory=mem,
            xla_cost_analysis_flops=float(ca.get("flops", -1.0)),
            roofline=rep,
        )
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    if verbose:
        _print_cell(rec)
    return rec


def _print_cell(rec: dict) -> None:
    if rec["status"] == "ok":
        r = rec["roofline"]
        print(
            f"OK   {rec['arch']:24s} {rec['shape']:12s} {rec['mesh']:10s} "
            f"hbm={rec['memory']['total_hbm_bytes']/2**30:6.1f}GiB "
            f"bound={r['bound']:10s} "
            f"tc={r['compute_s']:.3e} tm={r['memory_s']:.3e} tn={r['collective_s']:.3e} "
            f"roofline={r.get('roofline_fraction', 0):.3f} "
            f"compile={rec['compile_s']:.0f}s",
            flush=True,
        )
    elif rec["status"] == "skipped":
        print(f"SKIP {rec['arch']:24s} {rec['shape']:12s} {rec['mesh']:10s} {rec['reason']}",
              flush=True)
    else:
        print(f"FAIL {rec['arch']:24s} {rec['shape']:12s} {rec['mesh']:10s} {rec['error']}",
              flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--sync", default="accel_gossip",
                    choices=["allreduce", "gossip", "accel_gossip"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=1,
                    help="parallel worker subprocesses for --all")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--pad-heads", type=int, default=0,
                    help="SPerf knob: pad head counts to this TP degree")
    args = ap.parse_args(argv)

    cells = []
    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    for a in archs:
        for s in shapes:
            for m in meshes:
                cells.append((a, s, m))

    os.makedirs(args.out, exist_ok=True)
    if len(cells) > 1:
        # one subprocess per cell: an XLA C++ CHECK failure (hard abort) in
        # one cell must not take down the rest of the sweep
        return _run_parallel(cells, args)

    failures = 0
    for a, s, m in cells:
        rec = dryrun_cell(a, s, m, args.sync, pad_heads=args.pad_heads)
        fname = f"{a}__{s}__{'multi' if m else 'single'}__{args.sync}.json"
        with open(os.path.join(args.out, fname), "w") as f:
            json.dump(rec, f, indent=1)
        failures += rec["status"] == "error"
    return 1 if failures else 0


def _run_parallel(cells, args) -> int:
    """Each cell in its own subprocess (isolated XLA heap), --jobs at a time.

    A child killed by an XLA CHECK abort leaves no JSON; record the abort."""
    procs: list = []
    failures = 0
    queue = list(cells)
    while queue or procs:
        while queue and len(procs) < args.jobs:
            a, s, m = queue.pop(0)
            fname = os.path.join(
                args.out, f"{a}__{s}__{'multi' if m else 'single'}__{args.sync}.json"
            )
            if os.path.exists(fname):
                with open(fname) as f:
                    if json.load(f).get("status") in ("ok", "skipped"):
                        continue  # incremental: keep prior good results
            cmd = [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", a, "--shape", s,
                "--mesh", "multi" if m else "single",
                "--sync", args.sync, "--out", args.out,
            ]
            procs.append((subprocess.Popen(cmd), a, s, m, fname))
        still = []
        for p, a, s, m, fname in procs:
            if p.poll() is None:
                still.append((p, a, s, m, fname))
                continue
            if p.returncode != 0:
                failures += 1
                if not os.path.exists(fname):  # hard abort: no JSON written
                    with open(fname, "w") as f:
                        json.dump({
                            "arch": a, "shape": s,
                            "mesh": "pod2x16x16" if m else "pod16x16",
                            "status": "error",
                            "error": f"subprocess aborted rc={p.returncode} "
                                     "(XLA CHECK failure)",
                        }, f, indent=1)
                    print(f"ABRT {a:24s} {s:12s} rc={p.returncode}", flush=True)
        procs = still
        time.sleep(0.5)
    print(f"dry-run sweep complete: {failures} failures", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
