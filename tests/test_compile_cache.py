"""Where the entry points keep JAX's persistent compilation cache."""
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import enable_compile_cache


@pytest.fixture
def cache_dir_restored():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_env_dir_is_left_to_jax(monkeypatch, cache_dir_restored, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing overridden


def test_default_dir_is_fixed_in_the_checkout(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(Path(__file__).resolve().parents[1] / ".cache" / "jax_compile")
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert enable_compile_cache() == want  # the same path on every call
