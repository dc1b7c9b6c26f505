"""Allocation in q dimensions (DESIGN.md §4).

The q-D forms of the seven strategies: at q = 2 they equal the paper's 2D
formulas bit for bit; at q = 3 the n**(q-1) base blocks tile the machine,
keep each strategy's defining property and convexity class, and reach
every consumer (``allocate_blocks``, ``JobAllocator``, ``ScenarioSpec``).
The expected placements are restated here rank by rank, apart from the
vectorized code under test.
"""

import itertools

import numpy as np
import pytest

from repro.core.allocation import (
    ALLOCATIONS,
    JobAllocator,
    allocate_blocks,
    allocate_partition,
    endpoint_owner,
    machine_partitions,
    num_blocks,
)
from repro.core.hyperx import HyperX
from repro.core.properties import convexity_class

STRATS = sorted(ALLOCATIONS)


# ------------------------------------------------------------ q = 2 pins
def paper_2d(strategy, n, conc, p, size, seed):
    """The 2D formulas as the repository had them before the q-D forms:
    f(p, r_y, r_x) = (s_y, s_x, c), endpoint (s_y*n + s_x)*conc + c."""
    block = n * n
    k = -(-size // block)
    out = []
    pi = np.random.default_rng(seed).permutation(n**3)
    sigma = np.random.default_rng(seed).permutation(n * n)
    for r in range(size):
        b, rr = p * k + r // block, r % block
        r_y, r_x = rr // n, rr % n
        if strategy == "row":
            s_y, s_x, c = b % n, r_y, r_x
        elif strategy == "diagonal":
            s_y, s_x, c = r_y, (r_y + b) % n, r_x
        elif strategy == "full_spread":
            s_y, s_x, c = r_y, r_x, b % n
        elif strategy == "rectangular":
            s_y = (r_y % 2 + 2 * (b // 2)) % n
            s_x = (r_y // 2 + (n // 2) * (b % 2)) % n
            c = r_x
        elif strategy == "l_shape":
            if r_y < n // 2:
                s_y, s_x = (b + r_y) % n, b % n
            else:
                s_y, s_x = b % n, (b + r_y - n // 2 + 1) % n
            c = r_x
        elif strategy == "random_endpoint":
            t = int(pi[(b * n * n + r_y * n + r_x) % n**3])
            s_y, s_x, c = t // (n * n), (t // n) % n, t % n
        else:
            t = int(sigma[(b * n + r_y) % (n * n)])
            s_y, s_x, c = t // n, t % n, r_x
        out.append((s_y * n + s_x) * conc + c)
    return np.array(out)


@pytest.mark.parametrize("strat", STRATS)
@pytest.mark.parametrize("n", [4, 8])
def test_q2_equals_the_2d_formulas(strat, n):
    topo = HyperX(n=n, q=2)
    for p, size, seed in itertools.product(range(2 * n), (n * n, 5, 2 * n * n),
                                           (0, 7)):
        part = allocate_partition(strat, topo, p, size=size, seed=seed)
        want = paper_2d(strat, n, n, p, size, seed)
        np.testing.assert_array_equal(part.endpoints, want)
        np.testing.assert_array_equal(part.switches, np.unique(want // n))


# ------------------------------------------------------- the q-D table
def table_qd(strategy, n, q, conc, p, size, seed=0):
    """The q-D forms, one rank at a time: (switch coords, offset)."""
    out = []
    for r in range(size):
        b, rr = p + r // (n * n), r % (n * n)
        r_y, r_x = rr // n, rr % n
        dig = [(b // n**i) % n for i in range(q - 2, -1, -1)]  # p_{q-2}..p_0
        if strategy == "row":
            coords, c = dig + [r_y], r_x
        elif strategy == "diagonal":
            coords, c = [r_y] + [(r_y + d) % n for d in dig], r_x
        elif strategy == "full_spread":
            coords, c = dig[:-1] + [r_y, r_x], dig[-1]
        elif strategy == "rectangular":
            sides = [2] * (q - 1) + [n // 2 ** (q - 1)]
            tiles = [n // s for s in sides]
            box, rest = [0] * q, b
            for d in reversed(range(q)):
                box[d], rest = rest % tiles[d], rest // tiles[d]
            coords, rest = [], r_y
            for d in range(q):
                coords.append(box[d] * sides[d] + rest % sides[d])
                rest //= sides[d]
            c = r_x
        elif strategy == "l_shape":
            p0 = dig[-1]
            if r_y < n // 2:
                last2 = [(p0 + r_y) % n, p0]
            else:
                last2 = [p0, (p0 + r_y - n // 2 + 1) % n]
            coords, c = dig[:-1] + last2, r_x
        elif strategy == "random_endpoint":
            pi = np.random.default_rng(seed).permutation(n ** (q + 1))
            t = int(pi[(b * n * n + rr) % n ** (q + 1)])
            sw, c = t // n, t % n
            coords = [(sw // n**i) % n for i in range(q - 1, -1, -1)]
        else:
            sigma = np.random.default_rng(seed).permutation(n**q)
            sw = int(sigma[(b * n + r_y) % n**q])
            coords = [(sw // n**i) % n for i in range(q - 1, -1, -1)]
            c = r_x
        out.append((coords, c))
    return out


def endpoints_of(topo, placed):
    return np.array([topo.endpoint_id(coords, c) for coords, c in placed])


@pytest.mark.parametrize("strat", STRATS)
@pytest.mark.parametrize("n", [4, 8])
def test_q3_places_as_the_table_and_tiles_the_machine(strat, n):
    topo = HyperX(n=n, q=3)
    assert num_blocks(topo) == n * n
    parts = machine_partitions(strat, topo, num_jobs=n * n, seed=3)
    owner = endpoint_owner(parts, topo.num_endpoints)  # raises on overlap
    assert (owner >= 0).all()  # n**2 blocks of n**2 cover the n**4 machine
    for p in (0, 1, n + 3, n * n - 1):
        want = endpoints_of(topo, table_qd(strat, n, 3, n, p, n * n, seed=3))
        np.testing.assert_array_equal(parts[p].endpoints, want)


# convexity classes of the q-D forms at q = 3 (DESIGN.md §4)
CLASSES_Q3 = {
    "row": "convex", "diagonal": "non-convex", "full_spread": "convex",
    "rectangular": "convex", "l_shape": "weakly-convex",
    "random_endpoint": "non-convex", "random_switch": "non-convex",
}


@pytest.mark.parametrize("strat", STRATS)
def test_q3_convexity_classes(strat):
    topo = HyperX(n=8, q=3)
    for p in (0, 21, 63):
        part = allocate_partition(strat, topo, p, seed=1)
        assert convexity_class(topo, part.switches) == CLASSES_Q3[strat]


def test_q3_shapes():
    topo = HyperX(n=8, q=3)
    coords = lambda s: np.array(  # noqa: E731
        [topo.switch_coords(int(x)) for x in allocate_partition(
            s, topo, 21).switches])
    # p = 21: digits p_1 = 2, p_0 = 5
    row = coords("row")
    assert len(row) == 8 and (row[:, :2] == [2, 5]).all()  # a line
    plane = coords("full_spread")
    assert len(plane) == 64 and (plane[:, 0] == 2).all()   # plane s_0 = p_1
    cube = coords("rectangular")
    assert [len(np.unique(cube[:, d])) for d in range(3)] == [2, 2, 2]
    assert (np.ptp(cube, axis=0) == 1).all()               # a 2x2x2 cube
    ell = coords("l_shape")
    assert (ell[:, 0] == 2).all()                          # the L's plane


def test_q3_diagonal_is_pairwise_at_distance_q():
    for n, q in [(4, 3), (8, 3), (4, 4)]:
        topo = HyperX(n=n, q=q)
        for p in (0, 5, n ** (q - 1) - 1):
            sw = allocate_partition("diagonal", topo, p).switches
            assert len(sw) == n
            d = topo.distance_matrix()[np.ix_(sw, sw)]
            assert (d[~np.eye(n, dtype=bool)] == q).all()
            # every line of the machine meets the set at most once
            c = topo.all_switch_coords()[sw]
            for dim in range(q):
                rest = np.delete(c, dim, axis=1)
                assert len(np.unique(rest, axis=0)) == n


def test_rectangular_refuses_a_side_it_cannot_tile():
    for n, q in [(6, 3), (5, 2), (4, 4)]:
        with pytest.raises(ValueError, match="rectangular"):
            allocate_partition("rectangular", HyperX(n=n, q=q), 0)
    allocate_partition("rectangular", HyperX(n=8, q=4), 0)  # 2x2x2x1 boxes


def test_allocation_needs_two_dimensions():
    with pytest.raises(ValueError, match="q >= 2"):
        allocate_partition("row", HyperX(n=4, q=1), 0, size=4)


# ------------------------------------------------------------ consumers
@pytest.mark.parametrize("strat", STRATS)
def test_q3_allocate_blocks(strat):
    topo = HyperX(n=4, q=3)
    ids = [15, 2, 9]
    part = allocate_blocks(strat, topo, ids, seed=4, size=40)
    for i, b in enumerate(ids):
        want = endpoints_of(topo, table_qd(strat, 4, 3, 4, b, 16, seed=4))
        np.testing.assert_array_equal(part.endpoints[16 * i:16 * i + 16],
                                      want[:len(part.endpoints[16 * i:])])
    assert len(part.endpoints) == 40
    with pytest.raises(ValueError, match=r"\[0, 16\)"):
        allocate_blocks(strat, topo, [16])


def test_q3_job_allocator():
    topo = HyperX(n=4, q=3)
    alloc = JobAllocator(topo, strategy="diagonal")
    jobs = [alloc.allocate() for _ in range(16)]
    assert alloc.capacity() == 0
    assert len({int(s) for j in jobs for s in j.switches}) == 64
    with pytest.raises(RuntimeError):
        alloc.allocate()
    alloc.release(jobs[7].job_id)
    again = alloc.allocate()
    np.testing.assert_array_equal(again.endpoints, jobs[7].endpoints)
    big = JobAllocator(topo, strategy="rectangular")
    parts = [big.allocate(size=64) for _ in range(4)]   # 4 blocks each
    assert big.capacity() == 0
    endpoint_owner(parts, topo.num_endpoints)


@pytest.mark.parametrize("strat", STRATS)
def test_q3_scenario_places_as_the_table(strat):
    from repro.traffic import AppSpec, PhaseSpec, ScenarioSpec, build_workload

    topo = HyperX(n=8, q=3)
    spec = ScenarioSpec(apps=(AppSpec(phases=PhaseSpec("all_to_all"),
                                      placement=strat, ranks=64),))
    wl = build_workload(topo, spec)
    want = endpoints_of(topo, table_qd(strat, 8, 3, 8, 0, 64))
    np.testing.assert_array_equal(wl.rank_ep[:64], want)
