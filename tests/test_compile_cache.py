"""The persistent-compile-cache helper of the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set the helper configures nothing;
otherwise it points JAX at the fixed ``.jax_cache/`` of the checkout.  No
test here switches the cache on or touches a device: ``jax.config.update``
is replaced by a recorder.
"""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_var_set_means_no_path_is_set(monkeypatch, config_updates):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
    assert compile_cache.cache_dir_to_set() is None
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert config_updates == []


def test_default_is_the_fixed_dir_in_the_checkout(monkeypatch,
                                                  config_updates):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = str(ROOT / ".jax_cache")
    assert compile_cache.cache_dir_to_set({}) == want
    assert compile_cache.enable_compile_cache() == want
    assert config_updates == [("jax_compilation_cache_dir", want)]
    # the same path on every call: the cache key includes it
    assert compile_cache.enable_compile_cache() == want


def test_no_checkout_means_no_default(tmp_path, config_updates):
    """Imported from an installed copy (no pyproject.toml three levels up)
    the helper refuses to guess a directory that checkouts would share."""
    module = tmp_path / "site-packages" / "repro" / "launch" / "cc.py"
    with pytest.raises(RuntimeError, match=compile_cache.ENV_VAR):
        compile_cache.cache_dir_to_set({}, module_file=str(module))
    assert config_updates == []


def test_cache_dir_is_git_ignored():
    lines = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in lines
