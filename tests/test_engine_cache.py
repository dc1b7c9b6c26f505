"""Persistent compile-cache plumbing pins: where
:func:`repro.core.engine.enable_persistent_cache` points JAX's cache."""

import json
import os
import subprocess
import sys

from repro.core.engine import cache as engine_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_CACHE_SCRIPT = """
import json, sys
import jax
from repro.core.engine import enable_persistent_cache
out = {"ret": enable_persistent_cache(),
       "jax": jax.config.jax_compilation_cache_dir}
if sys.argv[1:] == ["compile"]:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7)).block_until_ready()
print(json.dumps(out))
"""


def _cache_probe(env_dir, *args):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop(engine_cache.ENV_VAR, None)
    if env_dir is not None:
        env[engine_cache.ENV_VAR] = env_dir
    r = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT, *args],
                       capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_enable_persistent_cache_env_gated():
    """Without JAX_COMPILATION_CACHE_DIR the cache goes to one fixed
    directory: the same in every process, never a tmp/pid/time name."""
    fixed = os.path.join(REPO, ".jax_cache")
    assert engine_cache.DEFAULT_DIR == fixed
    first, second = _cache_probe(None), _cache_probe(None)
    assert first == second == {"ret": fixed, "jax": fixed}


def test_enable_persistent_cache_reads_env(tmp_path):
    """JAX_COMPILATION_CACHE_DIR is honoured: JAX's own setting is left
    alone, and compiled executables land in that directory."""
    d = str(tmp_path / "env-cache")
    assert _cache_probe(d, "compile") == {"ret": d, "jax": d}
    assert os.listdir(d)
