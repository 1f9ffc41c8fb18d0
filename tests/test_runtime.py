"""The shared JAX runtime settings (``repro.runtime``) and the host mesh
helper: the float64 scope, where the persistent compile cache goes, and a
mesh that asks for more devices than exist."""

from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import runtime
from repro.launch.mesh import make_host_mesh

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def cache_config():
    """Restore the process's compile-cache setting after the test."""
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_enable_x64_is_a_scope():
    with runtime.enable_x64():
        assert jnp.zeros(1).dtype == jnp.float64
    assert jnp.zeros(1).dtype == jnp.float32


def test_compile_cache_env_wins(monkeypatch, tmp_path, cache_config):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself and no other
    path is set in code."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_and_ignored(monkeypatch,
                                                   cache_config):
    """Without the variable, compiled programs go to ``<checkout>/.jax_cache``,
    a fixed path that git ignores."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = runtime.enable_compile_cache()
    assert path == str(REPO / ".jax_cache") == str(runtime.DEFAULT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()


def test_host_mesh_refuses_missing_devices():
    n = len(jax.devices())
    assert make_host_mesh((1, n)).devices.size == n
    with pytest.raises(ValueError, match="needs"):
        make_host_mesh((2, n))
