"""Traffic/workload generation tests (paper Section 6.1)."""

import numpy as np
import pytest

from repro import traffic as tr
from repro.core.allocation import allocate_partition, machine_partitions
from repro.core.hyperx import HyperX

TOPO = HyperX(n=8, q=2)


def test_all_to_all_covers_everyone():
    app = tr.all_to_all(16)
    assert app.T == 15
    for r in range(16):
        dsts = set(app.sends_dst[r, :, 0].tolist())
        assert dsts == set(range(16)) - {r}
    assert app.window == 15  # asynchronous


def test_all_reduce_rabenseifner_structure():
    app = tr.all_reduce(16, vector_packets=64)
    assert app.T == 8  # 2 * log2(16)
    assert app.window == 1  # synchronous
    # partners are symmetric: if r sends to s at step t, s sends to r
    for t in range(app.T):
        d = app.sends_dst[:, t, 0]
        assert np.array_equal(d[d], np.arange(16))
    # scatter sizes halve: 32,16,8,4 then gather mirrors 4,8,16,32
    sizes = app.npkts[0, :, 0].tolist()
    assert sizes == [32, 16, 8, 4, 4, 8, 16, 32]
    with pytest.raises(ValueError):
        tr.all_reduce(12)


def test_stencil_neighbors():
    vn = tr.stencil(64, "von_neumann", rounds=2)
    assert vn.maxd == 4 and (vn.deg == 4).all()
    mo = tr.stencil(64, "moore", rounds=2)
    assert mo.maxd == 8 and (mo.deg == 8).all()
    # von Neumann neighbors are at grid distance 1 (torus wrap)
    gy = gx = 8
    for r in [0, 7, 63]:
        y, x = r // gx, r % gx
        for d in range(4):
            nb = vn.sends_dst[r, 0, d]
            ny, nx = nb // gx, nb % gx
            dy = min((y - ny) % gy, (ny - y) % gy)
            dx = min((x - nx) % gx, (nx - x) % gx)
            assert dy + dx == 1


def test_random_involution_is_involution():
    app = tr.random_involution(64, packets=4, seed=9)
    partner = app.sends_dst[:, 0, 0]
    assert np.array_equal(partner[partner], np.arange(64))
    assert not (partner == np.arange(64)).any()


def test_random_permutation_is_permutation_no_fixed_point():
    app = tr.random_permutation(64, packets=4, seed=3)
    perm = app.sends_dst[:, 0, 0]
    assert sorted(perm.tolist()) == list(range(64))
    assert not (perm == np.arange(64)).any()


def test_switch_permutation_groups():
    app = tr.random_switch_permutation(64, group=8, packets=4, seed=1)
    assert app.sampled.all()
    lo = app.lo[:, 0, 0]
    # each group of 8 ranks targets one 8-rank range, and it is not its own
    for g in range(8):
        blk = lo[8 * g : 8 * (g + 1)]
        assert len(set(blk.tolist())) == 1
        assert blk[0] != 8 * g
    # target groups form a permutation of the group set
    assert sorted(set((lo // 8).tolist())) == list(range(8))


def test_compose_rejects_overlap():
    part = allocate_partition("row", TOPO, 0)
    a1 = tr.uniform(64, packets=2)
    a2 = tr.uniform(64, packets=2)
    with pytest.raises(ValueError, match="disjoint"):
        tr.compose_workload(TOPO, [(a1, part), (a2, part)])


def test_compose_global_rank_space_and_pools():
    parts = machine_partitions("diagonal", TOPO, num_jobs=2)
    apps = [(tr.all_to_all(64), p) for p in parts]
    wl = tr.compose_workload(TOPO, apps, fabric_partitioning="per_app")
    assert wl.R == 128
    assert wl.num_pools == 2
    assert (wl.pool[:64] == 0).all() and (wl.pool[64:] == 1).all()
    # second app's destinations shifted into global rank space
    assert wl.sends_dst[64:, : wl.T, 0].min() >= 64


def test_background_noise_infinite():
    part = allocate_partition("row", TOPO, 0)
    free = np.setdiff1d(np.arange(TOPO.num_endpoints), part.endpoints)
    bg = tr.background_noise(TOPO, free)
    wl = tr.compose_workload(TOPO, [(tr.uniform(64, 2), part)], background=[bg],
                             warmup=100)
    assert wl.infinite.sum() == len(free)
    assert (wl.start[~wl.infinite] == 100).all()
    assert (wl.start[wl.infinite] == 0).all()
