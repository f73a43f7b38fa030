"""Where enable_compile_cache puts JAX's persistent compilation cache:
the directory JAX_COMPILATION_CACHE_DIR names, else a fixed .jax_cache/
at the root of the checkout (listed in .gitignore)."""
import pathlib

import jax
from jax.experimental.compilation_cache import compilation_cache

from repro.launch.compile_cache import DEFAULT_DIR, ENV_VAR, \
    enable_compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_default_dir_is_fixed_under_the_checkout(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    assert DEFAULT_DIR == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    was = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was
