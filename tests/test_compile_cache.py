"""``repro.compile_cache``: the persistent cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to a fixed path in the checkout,
and stays off on the CPU."""

from pathlib import Path

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_env_dir_wins(monkeypatch, tmp_path, restore_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert (jax.config.jax_persistent_cache_min_compile_time_secs
            == compile_cache.MIN_COMPILE_SECS)


def test_default_dir_without_env(monkeypatch, restore_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    on_cpu = jax.default_backend() == "cpu"
    expected = None if on_cpu else str(compile_cache.CACHE_DIR)
    assert compile_cache.enable() == expected


def test_fixed_dir_in_checkout():
    root = Path(__file__).resolve().parents[1]
    assert compile_cache.CACHE_DIR == root / ".jax_cache"
