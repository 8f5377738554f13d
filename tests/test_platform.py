"""Entry-point bootstrap: where the persistent compilation cache lives."""
import os

import jax
import pytest

from repro.launch import platform


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config updates instead of applying them."""
    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.__setitem__(name, value))
    return seen


def test_compile_cache_defaults_to_fixed_dir_in_checkout(monkeypatch,
                                                         config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    platform.enable_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert config_updates["jax_compilation_cache_dir"] == \
        os.path.join(repo, ".jax_cache")
    assert config_updates["jax_persistent_cache_min_compile_time_secs"] == 0


def test_compile_cache_leaves_env_var_in_charge(monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    platform.enable_compile_cache()
    assert "jax_compilation_cache_dir" not in config_updates
    assert config_updates["jax_persistent_cache_min_entry_size_bytes"] == -1
