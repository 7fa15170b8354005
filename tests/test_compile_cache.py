"""Where entry points put JAX's persistent compilation cache
(``repro.utils.cache.use_compile_cache``)."""
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.utils import cache

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture()
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_used_and_nothing_overrides_it(monkeypatch, tmp_path,
                                                   cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch,
                                                  cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = cache.use_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert cache.use_compile_cache() == path          # stable across calls


def test_importing_the_library_sets_no_cache_dir():
    code = ("import jax, repro.api, repro.fl, repro.shard, repro.server, "
            "repro.kernels.ops; print(jax.config.jax_compilation_cache_dir)")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "None"
