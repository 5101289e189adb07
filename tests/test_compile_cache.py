"""The entry points' compile-cache helper: where the persistent cache goes.

``jax.config.update`` is recorded, not applied, so this process's own
compilation settings stay as they are."""
import pytest

from repro.launch import compile_cache


@pytest.fixture
def updates(monkeypatch):
    calls = {}
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    return calls


def test_env_dir_is_left_to_jax(monkeypatch, updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in updates
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_default_dir_is_fixed_in_the_checkout(monkeypatch, updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert updates["jax_compilation_cache_dir"] == path
    assert (compile_cache.DEFAULT_DIR.parent / "chip_smoke.py").exists()
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
