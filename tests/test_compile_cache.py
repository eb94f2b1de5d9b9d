"""The entry points' persistent compile cache: env var wins, else a fixed
git-ignored directory inside the checkout."""
from pathlib import Path

import jax

from repro import compile_cache

REPO = Path(__file__).resolve().parents[1]


def _with_restored_cache_dir(fn):
    before = jax.config.jax_compilation_cache_dir
    try:
        return fn()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_set_changes_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    got = _with_restored_cache_dir(compile_cache.enable_compile_cache)
    assert got == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_env_var_unset_uses_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)

    def enable_and_read():
        got = compile_cache.enable_compile_cache()
        return got, jax.config.jax_compilation_cache_dir

    got, configured = _with_restored_cache_dir(enable_and_read)
    assert got == configured == str(REPO / ".jax_cache")
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
