"""Where the persistent compile cache goes
(``ray_tpu/_private/compile_cache.py``)."""

import os
import subprocess
import sys

import jax
import pytest

from ray_tpu._private import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_placement_is_left_to_jax(monkeypatch, restore_cache_dir):
    """With JAX_COMPILATION_CACHE_DIR set, jax has read it at import;
    our code sets nothing."""
    jax.config.update("jax_compilation_cache_dir", "/value/jax/holds")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    assert compile_cache.configure_compile_cache() == "/placed/from/outside"
    assert jax.config.jax_compilation_cache_dir == "/value/jax/holds"


def test_default_is_a_fixed_path_under_the_checkout(monkeypatch,
                                                    restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.configure_compile_cache()
    assert first == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert compile_cache.configure_compile_cache() == first


def test_importing_ray_tpu_does_not_import_jax():
    subprocess.run(
        [sys.executable, "-c",
         "import sys, ray_tpu; assert 'jax' not in sys.modules"],
        check=True, cwd=REPO, timeout=60)
