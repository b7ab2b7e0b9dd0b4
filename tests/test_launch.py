"""Launcher helpers: the persistent compile cache location and the mesh."""
import os

import jax

from repro.launch import compile_cache
from repro.launch.mesh import make_mesh


def test_compile_cache_dir_is_fixed_under_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == jax.config.jax_compilation_cache_dir
        root = os.path.dirname(os.path.dirname(__file__))
        assert path == os.path.join(os.path.abspath(root), ".cache", "jax")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_dir_is_left_alone(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


def test_make_mesh_over_chosen_devices():
    dev = jax.devices()[0]
    mesh = make_mesh((1, 1), ("data", "model"), devices=[dev])
    assert list(mesh.devices.flat) == [dev]
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,) * 2
