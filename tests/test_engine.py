"""SimEngine tests: batch/single equivalence, compile sharing, facade
regression against recorded seed-simulator outputs."""

import numpy as np
import pytest

from repro.core import traffic as tr
from repro.core.allocation import allocate_partition
from repro.core.engine import (
    SimEngine,
    make_workload_tables,
    shape_bucket,
    stack_tables,
)
from repro.core.hyperx import HyperX
from repro.core.simulator import build_simulator, simulate

SMALL = HyperX(n=4, q=2)


def _a2a_workload(strategy: str):
    part = allocate_partition(strategy, SMALL, 0)
    return tr.compose_workload(SMALL, [(tr.all_to_all(16), part)])


# ------------------------------------------------------------------ batching
def test_run_batch_bitwise_matches_run():
    """Vmapped batch execution returns exactly the per-scenario results."""
    engine = SimEngine(SMALL, mode="omniwar")
    wls = [_a2a_workload(s) for s in ("row", "diagonal", "full_spread")]
    seeds = [0, 1, 2]
    solo = [engine.run(wl, seed=s, horizon=5000)
            for wl, s in zip(wls, seeds)]
    batch = engine.run_batch(wls, seeds=seeds, horizon=5000)
    assert batch == solo  # SimResult dataclass equality: every field exact


def test_run_batch_seeds_matches_run():
    """Workload x seed cross product (seeds broadcast, no table
    replication) returns exactly the per-scenario results."""
    engine = SimEngine(SMALL, mode="omniwar")
    wls = [_a2a_workload(s) for s in ("row", "diagonal")]
    seeds = (0, 7)
    grid = engine.run_batch_seeds(wls, seeds=seeds, horizon=5000)
    assert grid == [
        [engine.run(wl, seed=s, horizon=5000) for s in seeds] for wl in wls
    ]
    assert engine.trace_count == 2  # one cross-product trace + one single


def test_run_seeds_matches_run():
    engine = SimEngine(SMALL, mode="omniwar")
    wl = _a2a_workload("row")
    solo = [engine.run(wl, seed=s, horizon=5000) for s in (0, 5, 9)]
    fanned = engine.run_seeds(wl, seeds=(0, 5, 9), horizon=5000)
    assert fanned == solo


# ----------------------------------------------------------- compile sharing
def test_same_shape_workloads_share_one_compilation():
    """Two workloads (different strategies, same shapes) must not re-trace:
    the tables are jit arguments, so the cache keys on shape buckets only."""
    engine = SimEngine(SMALL, mode="omniwar")
    engine.run(_a2a_workload("row"), seed=0, horizon=5000)
    assert engine.trace_count == 1
    engine.run(_a2a_workload("diagonal"), seed=0, horizon=5000)
    engine.run(_a2a_workload("l_shape"), seed=3, horizon=4000)
    assert engine.trace_count == 1  # no new trace for same-bucket workloads
    assert engine.device_calls == 3


def test_strategy_grid_is_single_batched_device_call():
    """A whole strategy grid = one run_batch dispatch; a second grid of the
    same shapes reuses the compilation (trace count stays flat)."""
    engine = SimEngine(SMALL, mode="omniwar")
    grid1 = [_a2a_workload(s) for s in ("row", "diagonal", "full_spread")]
    engine.run_batch(grid1, horizon=5000)
    assert engine.device_calls == 1          # one dispatch for the grid
    traces_after_first = engine.trace_count  # one batched trace
    assert traces_after_first == 1
    # same batch size + same bucket => the compilation is reused (the jit
    # cache keys on the stacked shapes, which include the batch dim)
    grid2 = [_a2a_workload(s) for s in ("rectangular", "l_shape", "row")]
    engine.run_batch(grid2, seeds=[4, 5, 6], horizon=5000)
    assert engine.device_calls == 2
    assert engine.trace_count == traces_after_first  # compilation reused


def test_bucketing_does_not_change_results():
    """Shape-bucket padding (extra ranks/steps/slots) is semantics-free."""
    padded = SimEngine(SMALL, mode="omniwar", bucket=True)
    exact = SimEngine(SMALL, mode="omniwar", bucket=False)
    wl = _a2a_workload("diagonal")
    assert padded.run(wl, seed=2, horizon=5000) == exact.run(
        wl, seed=2, horizon=5000
    )


def test_stack_tables_rejects_mixed_buckets():
    big = tr.compose_workload(
        SMALL, [(tr.all_to_all(16), allocate_partition("row", SMALL, 0))]
    )
    small = tr.compose_workload(
        SMALL, [(tr.uniform(4, packets=4),
                 allocate_partition("row", SMALL, 0))]
    )
    ta = make_workload_tables(big).tables
    tb = make_workload_tables(small).tables
    assert ta.shape_bucket != tb.shape_bucket
    with pytest.raises(ValueError):
        stack_tables([ta, tb])


def test_shape_bucket_rounds_up_to_pow2():
    assert shape_bucket(16, 15, 1) == (16, 16, 1)
    assert shape_bucket(17, 4, 3) == (32, 4, 4)
    assert shape_bucket(3, 1, 1) == (8, 4, 1)


# ------------------------------------------------------------------- facade
def test_facade_simulate_unchanged_vs_seed():
    """Regression: simulate() must reproduce the recorded outputs of the
    seed (pre-engine) simulator for a small HyperX(n=4, q=2) case.

    Recorded under jax's default ``jax_threefry_partitionable=True``
    (jax >= 0.5); the older default drew different PRNG bits."""
    part = allocate_partition("row", SMALL, 0)
    wl = tr.compose_workload(SMALL, [(tr.all_to_all(16), part)])

    r = simulate(SMALL, wl, mode="omniwar", seed=0, horizon=5000)
    assert (r.makespan, r.delivered, r.injected) == (31, 240, 240)
    assert r.makespan_cycles == 496
    assert r.avg_latency == pytest.approx(6.9625)
    assert r.avg_hops == pytest.approx(1.15)
    assert r.completed

    r = simulate(SMALL, wl, mode="min", seed=0, horizon=5000)
    assert (r.makespan, r.delivered, r.injected) == (37, 240, 240)
    assert r.avg_latency == pytest.approx(9.920833333333333)
    assert r.avg_hops == pytest.approx(0.8)

    part2 = allocate_partition("diagonal", SMALL, 0)
    wl2 = tr.compose_workload(SMALL, [(tr.uniform(16, packets=8), part2)])
    r = simulate(SMALL, wl2, mode="omniwar", seed=3, horizon=4000)
    assert (r.makespan, r.delivered, r.injected) == (17, 128, 128)
    assert r.avg_latency == pytest.approx(3.1015625)
    assert r.avg_hops == pytest.approx(1.40625)


def test_facade_build_simulator_debug_hook():
    wl = _a2a_workload("row")
    run = build_simulator(SMALL, wl, horizon=5000)
    final, d, i, qs = run.debug(seed=0, steps=64, stride=16)
    assert len(d) == len(i) == len(qs) == 4
    assert int(i[-1]) > 0  # packets were injected within 64 cycles


def test_engine_rejects_pool_mismatch():
    engine = SimEngine(SMALL, mode="omniwar", num_pools=1)
    parts = [allocate_partition("row", SMALL, 0),
             allocate_partition("row", SMALL, 1)]
    wl = tr.compose_workload(
        SMALL, [(tr.all_to_all(16), p) for p in parts],
        fabric_partitioning="per_app",
    )
    assert wl.num_pools == 2
    with pytest.raises(ValueError):
        engine.run(wl, seed=0, horizon=1000)
