"""The persistent compilation cache: placeable from outside, else fixed."""
import os

import jax
import pytest

from repro.compile_cache import CACHE_SUBDIR, enable_compile_cache


@pytest.fixture
def cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_var_wins_and_nothing_is_overridden(tmp_path, monkeypatch,
                                                cache_config):
    outside = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache(str(tmp_path / "repo")) == outside
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("relative", [False, True],
                         ids=["absolute", "relative"])
def test_default_is_a_fixed_dir_in_the_checkout(tmp_path, monkeypatch,
                                                cache_config, relative):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    want = os.path.join(str(tmp_path), "repo", CACHE_SUBDIR)
    got = enable_compile_cache("repo" if relative
                               else str(tmp_path / "repo"))
    assert got == want
    assert jax.config.jax_compilation_cache_dir == want
    # the same checkout always maps to the same directory
    assert enable_compile_cache(str(tmp_path / "repo")) == want
