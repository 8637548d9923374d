"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is the
consensus fabric (DCN), 'data'/'model' stay on ICI.

Every mesh here has Auto axes: GSPMD/Shardy propagate shardings through the
vmapped per-pod train step and the sweep engine's G-sharded scan, which
Explicit axes (``jax.make_mesh``'s default) would turn into sharding-type
errors on every pod-indexed gather.

The constructors are functions (never module-level constants) so importing
this module never touches jax device state — the dry-run sets XLA_FLAGS
before first jax init, everything else sees the real device count.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_cpu_mesh"]


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axis types (optionally on given devices)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_cpu_mesh(pods: int = 1):
    """Degenerate mesh for CPU examples/tests on however many host devices
    are available (1 by default; tests force more via XLA_FLAGS)."""
    n = jax.device_count()
    if pods > 1:
        if n % pods:
            raise ValueError(f"{n} devices not divisible into {pods} pods")
        return make_mesh((pods, n // pods, 1), ("pod", "data", "model"))
    return make_mesh((n, 1), ("data", "model"))
