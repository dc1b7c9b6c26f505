"""SimEngine tests: grid/single equivalence, compile sharing, regression
against recorded seed-simulator outputs."""

import jax
import numpy as np
import pytest

from repro import traffic as tr
from repro.core.allocation import allocate_partition
from repro.core.engine import (
    SimEngine,
    build_step,
    get_engine,
    init_state,
    make_workload_tables,
    shape_bucket,
    stack_tables,
)
from repro.core.hyperx import HyperX

SMALL = HyperX(n=4, q=2)


def _a2a_workload(strategy: str):
    part = allocate_partition(strategy, SMALL, 0)
    return tr.compose_workload(SMALL, [(tr.all_to_all(16), part)])


# ------------------------------------------------------------------ batching
GRIDS = {
    # a zip of per-workload seeds: the diagonal of the cross product
    "diagonal": (("row", "diagonal", "full_spread"), (0, 1, 2)),
    "workloads_x_seeds": (("row", "diagonal"), (0, 7)),
    "one_workload_many_seeds": (("row",), (0, 5, 9)),
}


@pytest.mark.parametrize("case", sorted(GRIDS))
def test_run_grid_equals_solo_run(case):
    """The vmapped workload x seed cross product (seeds broadcast, no
    table replication) returns exactly the per-scenario results."""
    strategies, seeds = GRIDS[case]
    engine = SimEngine(SMALL, mode="omniwar")
    wls = [_a2a_workload(s) for s in strategies]
    grid = engine.run_grid(wls, seeds=seeds, horizon=5000)
    if case == "diagonal":
        assert [grid[i][i] for i in range(len(wls))] == [
            engine.run(wl, seed=s, horizon=5000) for wl, s in zip(wls, seeds)
        ]
    else:
        # SimResult dataclass equality: every field exact
        assert grid == [
            [engine.run(wl, seed=s, horizon=5000) for s in seeds]
            for wl in wls
        ]
    assert engine.trace_count == 2  # one cross-product trace + one single


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
    """A whole strategy grid = one run_grid dispatch; a second grid of the
    same shapes reuses the compilation (trace count stays flat)."""
    engine = SimEngine(SMALL, mode="omniwar")
    grid1 = [_a2a_workload(s) for s in ("row", "diagonal", "full_spread")]
    engine.run_grid(grid1, horizon=5000)
    assert engine.device_calls == 1          # one dispatch for the grid
    traces_after_first = engine.trace_count  # one batched trace
    assert traces_after_first == 1
    # same batch size + same bucket => the compilation is reused (the jit
    # cache keys on the stacked shapes, which include the batch dim)
    grid2 = [_a2a_workload(s) for s in ("rectangular", "l_shape", "row")]
    engine.run_grid(grid2, seeds=[4], horizon=5000)
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


# --------------------------------------------------------------- seed pins
def _simulate(wl, mode, seed, horizon):
    engine = get_engine(SMALL, mode=mode, num_pools=wl.num_pools)
    return engine.run(wl, seed=seed, horizon=horizon)


def test_facade_simulate_unchanged_vs_seed():
    """Regression: ``run`` must reproduce the recorded outputs of the
    seed (pre-engine) simulator for a small HyperX(n=4, q=2) case.

    Recorded under jax's default ``jax_threefry_partitionable=True``
    (jax >= 0.5); the older default drew different PRNG bits."""
    part = allocate_partition("row", SMALL, 0)
    wl = tr.compose_workload(SMALL, [(tr.all_to_all(16), part)])

    r = _simulate(wl, mode="omniwar", seed=0, horizon=5000)
    assert (r.makespan, r.delivered, r.injected) == (31, 240, 240)
    assert r.makespan_cycles == 496
    assert r.avg_latency == pytest.approx(6.9625)
    assert r.avg_hops == pytest.approx(1.15)
    assert r.completed

    r = _simulate(wl, mode="min", seed=0, horizon=5000)
    assert (r.makespan, r.delivered, r.injected) == (37, 240, 240)
    assert r.avg_latency == pytest.approx(9.920833333333333)
    assert r.avg_hops == pytest.approx(0.8)

    part2 = allocate_partition("diagonal", SMALL, 0)
    wl2 = tr.compose_workload(SMALL, [(tr.uniform(16, packets=8), part2)])
    r = _simulate(wl2, mode="omniwar", seed=3, horizon=4000)
    assert (r.makespan, r.delivered, r.injected) == (17, 128, 128)
    assert r.avg_latency == pytest.approx(3.1015625)
    assert r.avg_hops == pytest.approx(1.40625)


def test_step_scan_injects_within_64_cycles():
    """The cycle kernel stands alone: a scan of ``build_step`` from
    ``init_state`` injects and conserves packets cycle by cycle."""
    engine = SimEngine(SMALL, mode="omniwar")
    wt = engine.prepare(_a2a_workload("row")).tables
    step = build_step(engine.static)

    def body(state, _):
        s2 = step(state, wt)
        return s2, (s2.n_delivered, s2.n_injected, s2.qlen.sum())

    state = init_state(engine.static, wt, 0)
    _, (d, i, qs) = jax.lax.scan(body, state, None, length=64)
    d, i, qs = (np.asarray(x)[::16] for x in (d, i, qs))
    assert len(d) == len(i) == len(qs) == 4
    assert int(i[-1]) > 0  # packets were injected within 64 cycles
    assert (i >= d + qs).all()  # in flight or ejected, never lost


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
