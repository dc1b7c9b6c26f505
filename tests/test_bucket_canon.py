"""Bucket canonicalization + persistent-cache plumbing pins.

``SimEngine(canon=True)`` pow2-pads the stacked batch axes (workload
count, seed count, lane count) so nearby grid sizes land on one compiled
executable.  Padded lanes repeat real ones and are discarded — so the
property under test is that canonicalization NEVER changes a SimResult,
and the trace-counter pin is that two nearby grid sizes now share one
compile (plus hit/miss counters that surface the amortization rate).
"""

import json
import os
import subprocess
import sys

from repro.core import traffic as tr
from repro.core.allocation import allocate_partition
from repro.core.engine import SimEngine
from repro.core.engine import cache as engine_cache
from repro.core.hyperx import HyperX

SMALL = HyperX(n=4, q=2)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HORIZON = 5000
STRATS = ("row", "diagonal", "full_spread", "rectangular", "column")


def _wl(strategy: str):
    part = allocate_partition(strategy, SMALL, 0)
    return tr.compose_workload(SMALL, [(tr.all_to_all(16), part)])


def test_canon_never_changes_results():
    """Property: pow2 padding of every batch axis is result-invariant —
    delivered / latency / hops / makespan bit-identical, on odd-sized
    workload lists, seed lists, and the single-run path."""
    wls = [_wl(s) for s in STRATS[:3]]          # 3 -> pads to 4
    seeds = (0, 3, 11)                          # 3 -> pads to 4
    plain = SimEngine(SMALL, mode="omniwar")
    canon = SimEngine(SMALL, mode="omniwar", canon=True)
    assert canon.run_grid(wls, seeds=seeds, horizon=HORIZON) == \
        plain.run_grid(wls, seeds=seeds, horizon=HORIZON)
    assert canon.run_batch(wls, seeds=[1, 2, 3], horizon=HORIZON) == \
        plain.run_batch(wls, seeds=[1, 2, 3], horizon=HORIZON)
    assert canon.run_seeds(wls[0], seeds=seeds, horizon=HORIZON) == \
        plain.run_seeds(wls[0], seeds=seeds, horizon=HORIZON)
    assert canon.run(wls[0], seed=5, horizon=HORIZON) == \
        plain.run(wls[0], seed=5, horizon=HORIZON)


def test_canon_shares_compiles_across_nearby_sizes():
    """The trace-counter pin: 3-workload and 4-workload grids (same shape
    bucket) hit one compiled executable under canon — and the second
    dispatch is recorded as a bucket hit."""
    canon = SimEngine(SMALL, mode="omniwar", canon=True)
    canon.run_grid([_wl(s) for s in STRATS[:3]], seeds=(0,),
                   horizon=HORIZON)
    t0 = canon.trace_count
    assert canon.bucket_stats()["misses"] == 1
    canon.run_grid([_wl(s) for s in STRATS[:4]], seeds=(0,),
                   horizon=HORIZON)
    assert canon.trace_count == t0  # no new compile: 3 padded to 4
    assert canon.bucket_stats() == {
        "hits": 1, "misses": 1, "hit_rate": 0.5}

    # control: the uncanonicalized engine re-traces for the new size
    plain = SimEngine(SMALL, mode="omniwar")
    plain.run_grid([_wl(s) for s in STRATS[:3]], seeds=(0,),
                   horizon=HORIZON)
    t0 = plain.trace_count
    plain.run_grid([_wl(s) for s in STRATS[:4]], seeds=(0,),
                   horizon=HORIZON)
    assert plain.trace_count == t0 + 1
    assert plain.bucket_stats()["hits"] == 0


def test_canon_pad_sizes():
    eng = SimEngine(SMALL, mode="omniwar", canon=True)
    assert [eng._canon_pad(n) for n in (1, 2, 3, 5, 8, 9)] == \
        [1, 2, 4, 8, 8, 16]
    off = SimEngine(SMALL, mode="omniwar")
    assert [off._canon_pad(n) for n in (3, 5)] == [3, 5]


# ------------------------------------------------------- persistent cache
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
