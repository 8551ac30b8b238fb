"""The persistent compile cache: placed from outside, or at a fixed path.

With ``JAX_COMPILATION_CACHE_DIR`` set the entry points leave the cache to
JAX; without it they point JAX at ``<checkout>/.jax_cache``.  Either way a
second run of the same program is served from the cache.
"""
import json
import os
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fresh(monkeypatch):
    """Record config updates instead of applying them; fresh module state."""
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setattr(jax.monitoring, "register_event_listener",
                        lambda cb: None)
    monkeypatch.setattr(compile_cache, "_state", {})
    return updates


def test_fixed_checkout_path_without_the_variable(fresh, monkeypatch):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert fresh["jax_compilation_cache_dir"] == path
    assert compile_cache.enable_compile_cache() == path  # idempotent


def test_variable_set_means_nothing_is_set(fresh, monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert fresh == {}


_PROGRAM = (
    "import json, jax, jax.numpy as jnp\n"
    "from repro.launch.compile_cache import enable_compile_cache, "
    "compile_cache_stats\n"
    "enable_compile_cache()\n"
    "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()\n"
    "print(json.dumps(compile_cache_stats()))\n"
)


def test_second_run_is_served_from_the_variable_dir(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(ROOT, "src"), os.environ.get("PYTHONPATH", "")]))
    runs = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", _PROGRAM], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        runs.append(json.loads(out.stdout.splitlines()[-1]))
    assert runs[0]["dir"] == str(tmp_path)
    assert runs[0]["misses"] >= 1 and os.listdir(tmp_path)
    assert runs[1]["hits"] >= 1 and runs[1]["misses"] == 0
