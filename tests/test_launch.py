"""Launch-layer contracts: mesh axis types, per-chip peak rates, and the
entry points' compile-cache location."""
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_make_mesh_gives_auto_axes():
    from jax.sharding import AxisType

    from repro.launch.mesh import make_cpu_mesh, make_mesh

    for mesh in (make_mesh((1, 1), ("data", "model")), make_cpu_mesh()):
        assert set(mesh.axis_types) == {AxisType.Auto}, mesh.axis_types


def test_roofline_peaks_are_keyed_by_device_kind():
    from repro.launch.roofline import peaks

    v5e = peaks("TPU v5 lite")
    assert v5e["peak_flops"] == 197e12 and v5e["hbm_bw"] == 819e9
    for kind in ("cpu", "TPU v4", "TPU v6 lite"):
        with pytest.raises(ValueError, match="no peak rates"):
            peaks(kind)


def test_compile_cache_honours_the_environment(monkeypatch):
    from repro.launch import compile_cache

    updates = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert updates == []       # JAX reads the variable itself


def test_compile_cache_defaults_to_one_ignored_path_in_the_checkout(monkeypatch):
    from repro.launch import compile_cache

    updates = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert ("jax_compilation_cache_dir", path) in updates
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert "/.jax_cache/" in f.read().split()
