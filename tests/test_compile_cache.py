"""The compile-cache rule (repro.compile_cache): ``$JAX_COMPILATION_CACHE_DIR``
when set, else ``.jax_cache/`` at the checkout root, and no other path."""

from pathlib import Path

import pytest

from repro import compile_cache

jax = pytest.importorskip("jax")


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_env_var_is_used_and_nothing_else_set(monkeypatch, tmp_path,
                                              restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_fixed_checkout_path(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    root = Path(__file__).resolve().parents[1]
    assert compile_cache.CHECKOUT_CACHE == root / ".jax_cache"
    assert compile_cache.enable() == str(root / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
