"""repro.runtime: one rule for where the compile cache lives."""

import os

import jax

from repro.runtime import use_compile_cache


def test_compile_cache_defaults_to_fixed_dir(tmp_path, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache(str(tmp_path))
        assert path == os.path.join(str(tmp_path), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert use_compile_cache(str(tmp_path)) == path  # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_wins(tmp_path, monkeypatch):
    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    was = jax.config.jax_compilation_cache_dir
    assert use_compile_cache(str(tmp_path)) == env_dir
    assert jax.config.jax_compilation_cache_dir == was  # nothing set
    assert not os.path.exists(os.path.join(str(tmp_path), ".jax_cache"))
