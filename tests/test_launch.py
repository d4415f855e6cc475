"""Launcher helpers: the ``--mesh`` spec and the compile-cache placement."""
import jax
import pytest

from repro.launch import cache
from repro.launch.mesh import mesh_from_spec


def test_mesh_spec_dx1_is_the_pure_data_mesh():
    mesh = mesh_from_spec("1x1")
    assert mesh.axis_names == ("data",)
    assert mesh.devices.size == 1
    assert mesh_from_spec(None) is None


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cache.use_compile_cache() == cache.CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == cache.CACHE_DIR
    assert cache.CACHE_DIR.endswith(".jax_cache")


def test_compile_cache_left_where_the_environment_puts_it(
        monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
