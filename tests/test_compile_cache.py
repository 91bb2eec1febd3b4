"""The persistent compile cache: the environment's directory, else a fixed
one at the checkout root."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    cc.reset_cache()


def test_env_var_set_sets_nothing(monkeypatch, tmp_path,
                                  restore_cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_checkout_path(monkeypatch, restore_cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # the same path on every call: no temp name, pid or time in it
    assert compile_cache.enable_compile_cache() == path


def test_env_var_cache_is_written_there(tmp_path):
    """A process that compiles with the variable set writes its cache
    entries into that directory."""
    env = dict(os.environ)
    env[compile_cache.ENV_VAR] = str(tmp_path / "cache")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(ROOT / "src")
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "enable_compile_cache()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "print(jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).sum())\n")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    assert any((tmp_path / "cache").iterdir())
