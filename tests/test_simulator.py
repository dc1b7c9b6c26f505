"""Simulator behaviour tests — conservation, determinism, PB ordering."""

import numpy as np
import pytest

from repro import traffic as tr
from repro.core.allocation import allocate_partition, machine_partitions
from repro.core.engine import get_engine
from repro.core.hyperx import HyperX

SMALL = HyperX(n=4, q=2)
PAPER = HyperX(n=8, q=2)


def _run(topo, wl, mode="omniwar", seed=0, horizon=60_000):
    engine = get_engine(topo, mode=mode, num_pools=wl.num_pools)
    return engine.run(wl, seed=seed, horizon=horizon)


def _expect_packets(wl):
    return int(wl.npkts[~wl.infinite].sum())


@pytest.mark.parametrize("mode", ["min", "omniwar"])
def test_conservation_all_to_all(mode):
    part = allocate_partition("row", SMALL, 0)
    wl = tr.compose_workload(SMALL, [(tr.all_to_all(16), part)])
    res = _run(SMALL, wl, mode=mode, horizon=5000)
    assert res.completed
    assert res.delivered == res.injected == _expect_packets(wl)


@pytest.mark.parametrize(
    "app",
    [
        tr.all_reduce(16, vector_packets=16),
        tr.stencil(16, "von_neumann", rounds=4),
        tr.stencil(16, "moore", rounds=2),
        tr.random_involution(16, packets=8),
        tr.uniform(16, packets=16),
        tr.random_permutation(16, packets=16),
    ],
    ids=lambda a: a.name,
)
def test_conservation_each_pattern(app):
    part = allocate_partition("diagonal", SMALL, 0)
    wl = tr.compose_workload(SMALL, [(app, part)])
    res = _run(SMALL, wl, mode="omniwar", horizon=8000)
    assert res.completed
    assert res.delivered == res.injected == _expect_packets(wl)


def test_deterministic_same_seed():
    part = allocate_partition("l_shape", SMALL, 0)
    wl = tr.compose_workload(SMALL, [(tr.uniform(16, packets=8), part)])
    a, b = (_run(SMALL, wl, seed=7, horizon=4000) for _ in range(2))
    assert a == b
    c = _run(SMALL, wl, seed=8, horizon=4000)
    assert c.completed  # different seed still completes


def test_min_mode_never_deroutes():
    part = allocate_partition("diagonal", SMALL, 0)
    wl = tr.compose_workload(SMALL, [(tr.uniform(16, packets=16), part)])
    res = _run(SMALL, wl, mode="min", horizon=5000)
    # diagonal switches are mutually unaligned in both dims: avg minimal
    # distance is 2 - 2/n at switch level; MIN hop counts can never exceed it
    assert res.avg_hops <= 2.0 + 1e-6


def test_window_enforced_for_synchronous_kernels():
    """All-reduce (window=1) must be slower than its packet count alone:
    each of the 2*log2(k) steps serializes behind partner receives."""
    part = allocate_partition("row", SMALL, 0)
    ar = tr.all_reduce(16, vector_packets=8)
    wl = tr.compose_workload(SMALL, [(ar, part)])
    res = _run(SMALL, wl, horizon=5000)
    assert res.completed
    assert res.makespan >= ar.T  # at least one cycle per synchronous step


@pytest.mark.slow
def test_pb_ordering_under_min_uniform_paper_scale():
    """The paper's central claim chain: PB predicts uniform-traffic makespan
    under MIN (Fig. 7 / Lesson 2): rectangular (PB=0.25) is clearly worst,
    diagonal/full-spread (PB>=2) in the best group."""
    makespans = {}
    for strat in ["row", "diagonal", "full_spread", "rectangular"]:
        parts = machine_partitions(strat, PAPER, num_jobs=8)
        apps = [(tr.uniform(64, packets=64), p) for p in parts]
        wl = tr.compose_workload(PAPER, apps)
        res = _run(PAPER, wl, mode="min", horizon=30000)
        assert res.completed, strat
        makespans[strat] = res.makespan
    assert makespans["rectangular"] > 1.5 * makespans["row"]
    assert makespans["diagonal"] < makespans["row"]
    assert makespans["full_spread"] < makespans["row"]


@pytest.mark.slow
def test_background_interference_slows_target():
    part = allocate_partition("diagonal", PAPER, 0)
    app = tr.uniform(64, packets=64)
    iso = _run(
        PAPER, tr.compose_workload(PAPER, [(app, part)]), horizon=30000
    )
    free = np.setdiff1d(np.arange(PAPER.num_endpoints), part.endpoints)
    bg = tr.background_noise(PAPER, free)
    wl = tr.compose_workload(PAPER, [(app, part)], background=[bg], warmup=400)
    noisy = _run(PAPER, wl, horizon=60000)
    assert iso.completed and noisy.completed
    assert noisy.makespan > iso.makespan  # interference costs something


def test_fabric_partitioning_pools_isolate_state():
    """per_app pools give each app private FIFOs; workload still completes."""
    parts = machine_partitions("random_switch", SMALL, num_jobs=2)
    apps = [(tr.all_to_all(16), p) for p in parts]
    wl = tr.compose_workload(SMALL, apps, fabric_partitioning="per_app")
    assert wl.num_pools == 2
    res = _run(SMALL, wl, horizon=8000)
    assert res.completed
    assert res.delivered == _expect_packets(wl)
