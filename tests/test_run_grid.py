"""Device-sharded lane dispatch: run_grid parity, trace counting, and the
multi-device path (emulated via XLA host-device splitting in a subprocess).
"""

import json
import os
import subprocess
import sys

import pytest

from repro import traffic as tr
from repro.core.allocation import allocate_partition
from repro.core.engine import SimEngine, default_lane_backend
from repro.core.hyperx import HyperX

SMALL = HyperX(n=4, q=2)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _a2a_workload(strategy: str):
    part = allocate_partition(strategy, SMALL, 0)
    return tr.compose_workload(SMALL, [(tr.all_to_all(16), part)])


def _uniform_workload(strategy: str):
    part = allocate_partition(strategy, SMALL, 0)
    return tr.compose_workload(SMALL, [(tr.uniform(4, packets=4), part)])


def test_run_grid_matches_solo_run_bitwise():
    """On one device run_grid IS the nested-vmap cross product — results
    must equal solo runs field-for-field, including with duplicate seeds."""
    engine = SimEngine(SMALL, mode="omniwar")
    wls = [_a2a_workload(s) for s in ("row", "diagonal", "full_spread")]
    seeds = (0, 7, 7)  # duplicate seed: lane indexing must not collapse it
    assert engine.run_grid(wls, seeds=seeds, horizon=5000) == [
        [engine.run(wl, seed=s, horizon=5000) for s in seeds] for wl in wls
    ]
    assert engine.lane_backend == "vmap"


def test_run_grid_default_seed_zero():
    engine = SimEngine(SMALL, mode="omniwar")
    wl = _a2a_workload("row")
    assert engine.run_grid([wl], horizon=5000) == [
        [engine.run(wl, seed=0, horizon=5000)]
    ]


def test_run_grid_compiles_once_per_shape_bucket():
    """The trace-counter pin: a grid compiles once per shape bucket, and a
    second grid of the same buckets re-traces nothing."""
    engine = SimEngine(SMALL, mode="omniwar")
    a2a = [_a2a_workload(s) for s in ("row", "diagonal")]
    uni = [_uniform_workload(s) for s in ("row", "diagonal")]
    engine.run_grid(a2a + uni, seeds=(0, 1), horizon=5000)
    assert engine.trace_count == 2    # exactly one trace per bucket
    assert engine.device_calls == 2   # one dispatch per bucket
    engine.run_grid(
        [_a2a_workload("full_spread"), _a2a_workload("l_shape"),
         _uniform_workload("full_spread"), _uniform_workload("l_shape")],
        seeds=(4, 5), horizon=5000,
    )
    assert engine.trace_count == 2    # same buckets -> compilations reused
    assert engine.device_calls == 4


def test_lane_backend_reported_at_construction():
    """Regression pin: ``lane_backend`` must be populated from engine
    construction, not lazily after the first ``run_grid`` — on a
    single-device host it is "vmap" immediately and stays "vmap"."""
    engine = SimEngine(SMALL, mode="omniwar")
    assert engine.lane_backend == default_lane_backend()
    assert engine.lane_backend is not None
    before = engine.lane_backend
    engine.run_grid([_a2a_workload("row")], horizon=5000)
    assert engine.lane_backend == before


_SHARDED_SCRIPT = """
import json
import jax
from repro import traffic as tr
from repro.core.allocation import allocate_partition
from repro.core.engine import SimEngine
from repro.core.hyperx import HyperX

assert jax.local_device_count() == 4, jax.local_device_count()
SMALL = HyperX(n=4, q=2)
wls = [
    tr.compose_workload(
        SMALL, [(tr.all_to_all(16), allocate_partition(s, SMALL, 0))]
    )
    for s in ("row", "diagonal", "full_spread")  # 3 x 2 lanes: needs padding
]
engine = SimEngine(SMALL, mode="omniwar")
pre_backend = engine.lane_backend  # populated at construction (no run yet)
grid = engine.run_grid(wls, seeds=(0, 7), horizon=5000)
print(json.dumps({
    "pre_backend": pre_backend,
    "backend": engine.lane_backend,
    "traces": engine.trace_count,
    "grid": [[{k: v for k, v in r.__dict__.items() if k != "telemetry"}
              for r in per_seed] for per_seed in grid],
}))
"""


@pytest.mark.slow
def test_run_grid_sharded_matches_single_device():
    """4 emulated devices (lane padding exercised: 6 lanes -> 8) must give
    bitwise the same grid as this process's single-device reference."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr
    payload = json.loads(r.stdout.strip().splitlines()[-1])
    assert payload["backend"] == "shard_map"
    # lane_backend is reported from construction and the first run_grid
    # must dispatch through that same backend
    assert payload["pre_backend"] == payload["backend"]
    assert payload["traces"] == 1  # SPMD: still one trace for the bucket

    engine = SimEngine(SMALL, mode="omniwar")
    wls = [_a2a_workload(s) for s in ("row", "diagonal", "full_spread")]
    ref = engine.run_grid(wls, seeds=(0, 7), horizon=5000)
    # tuples (per-epoch counters) round-trip through JSON as lists
    assert payload["grid"] == [
        [{k: list(v) if isinstance(v, tuple) else v
          for k, v in r.__dict__.items() if k != "telemetry"}
         for r in per_seed] for per_seed in ref]
