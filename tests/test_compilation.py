"""The compile-cache helper and the compile counter
(``repro.runtime.compilation``)."""

import pathlib
import tempfile

import jax
import jax.numpy as jnp
import pytest

from repro.runtime.compilation import (
    CACHE_ENV,
    CompileCounter,
    compile_cache_dir,
    enable_compile_cache,
)

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture()
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


class TestCompileCacheDir:
    def test_env_path_is_used(self, monkeypatch, tmp_path,
                              restore_cache_dir):
        monkeypatch.setenv(CACHE_ENV, str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)

    def test_default_is_fixed_inside_the_checkout(self, monkeypatch,
                                                   restore_cache_dir):
        monkeypatch.delenv(CACHE_ENV, raising=False)
        first, second = compile_cache_dir(), compile_cache_dir()
        assert first == second == str(REPO_ROOT / ".jax_cache")
        assert enable_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
        tmp = pathlib.Path(tempfile.gettempdir()).resolve()
        assert tmp not in pathlib.Path(first).resolve().parents


class TestCompileCounter:
    def test_counts_new_executables_only(self):
        fn = jax.jit(lambda x: x * 3.0 + 1.0)
        x = jnp.arange(7.0)
        with CompileCounter() as first:
            fn(x).block_until_ready()
        with CompileCounter() as again:
            fn(x).block_until_ready()
        assert first.count == 1
        assert again.count == 0
