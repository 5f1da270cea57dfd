"""Where the entry points keep JAX's persistent compilation cache."""
import jax
import pytest

from repro.launch.compile_cache import CACHE_DIR, enable_compile_cache


@pytest.fixture
def cache_config():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_env_dir_stands(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_repo_dir_without_env(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
    assert CACHE_DIR.name == ".jax_cache"
    assert (CACHE_DIR.parent / "chip_smoke.py").exists()
