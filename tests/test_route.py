"""Routing-policy subsystem tests: registry, vectorized-table parity,
bit-identical min/omniwar pins vs the seed engine, VAL/UGAL delivery +
conservation (with and without fault masks), hop-indexed VC budget
invariants, and the one-compile-per-bucket pin for routing x fault grids."""

import numpy as np
import pytest

try:  # optional test extra (pip install -e .[test]); property tests need it
    from hypothesis import given, settings, strategies as hst
except ImportError:  # pragma: no cover - exercised only without hypothesis
    given = settings = hst = None

from repro import traffic as tr
from repro.core.allocation import allocate_partition
from repro.core.engine import SimEngine, get_engine, make_workload_tables
from repro.core.hyperx import HyperX
from repro.core.routing import LinkSpace
from repro import route
from repro.route import (
    RoutingPolicy,
    apply_faults,
    available_policies,
    fail_links,
    fail_switches,
    faults_from_endpoints,
    get_policy,
    intermediate_pool,
    is_connected,
    neighbor_tables,
    no_faults,
    random_link_faults,
    self_port_mask,
)

SMALL = HyperX(n=4, q=2)


def _a2a_workload(strategy: str, link_ok=None):
    part = allocate_partition(strategy, SMALL, 0)
    return tr.compose_workload(
        SMALL, [(tr.all_to_all(16), part)], link_ok=link_ok
    )


def _one_link_mask():
    return fail_links(SMALL, [(0, 1)])


def _two_link_mask():
    return fail_links(SMALL, [(0, 1), (5, 9)])


# ------------------------------------------------------------------ registry
def test_available_policies_lists_all_four():
    assert available_policies() == ("min", "omniwar", "ugal", "val")


def test_unknown_mode_raises_with_registered_names():
    with pytest.raises(ValueError) as e:
        get_policy("bogus")
    msg = str(e.value)
    for name in available_policies():
        assert name in msg
    with pytest.raises(ValueError):
        SimEngine(SMALL, mode="bogus")
    with pytest.raises(ValueError):
        get_engine(SMALL, mode="not_a_policy")


def test_register_duplicate_rejected():
    with pytest.raises(ValueError):
        route.register_policy(RoutingPolicy("min", False, False, False))


def test_vc_budget_declarations():
    q = SMALL.q
    # default deroute budget: one per dimension per minimal phase
    assert get_policy("min").default_deroutes(q) == q       # seed m
    assert get_policy("omniwar").default_deroutes(q) == q   # seed m
    assert get_policy("val").default_deroutes(q) == 2 * q
    assert get_policy("ugal").default_deroutes(q) == 2 * q
    assert get_policy("min").vc_budget(q, q) == 2 * q + 1       # seed V
    assert get_policy("omniwar").vc_budget(q, q) == 2 * q + 1   # seed V
    assert get_policy("val").vc_budget(q, 2 * q) == 4 * q + 1
    assert get_policy("ugal").vc_budget(q, 2 * q) == 4 * q + 1
    # the engine sizes its queue space from the declaration
    assert get_engine(SMALL, mode="val").static.V == 4 * q + 1
    assert get_engine(SMALL, mode="min").static.V == 2 * q + 1


# ------------------------------------------- vectorized-table parity (loops)
def _loop_neighbor_tables(topo: HyperX):
    """The seed engine's O(S*q*n) nested-loop construction, verbatim."""
    n, q, S = topo.n, topo.q, topo.num_switches
    coords_np = topo.all_switch_coords()
    nbr = np.empty((S, q * n), dtype=np.int64)
    in_port = np.empty((S, q * n), dtype=np.int64)
    for d in range(q):
        for v in range(n):
            nc = coords_np.copy()
            nc[:, d] = v
            ids = np.zeros(S, dtype=np.int64)
            for d2 in range(q):
                ids = ids * n + nc[:, d2]
            nbr[:, d * n + v] = ids
            in_port[:, d * n + v] = d * n + coords_np[:, d]
    return nbr, in_port


@pytest.mark.parametrize("topo", [SMALL, HyperX(n=3, q=3), HyperX(n=8, q=2)])
def test_neighbor_tables_match_loop_construction(topo):
    nbr, ipnb = neighbor_tables(topo.all_switch_coords(), topo.n, topo.q)
    ref_nbr, ref_ip = _loop_neighbor_tables(topo)
    np.testing.assert_array_equal(nbr, ref_nbr)
    np.testing.assert_array_equal(ipnb, ref_ip)


@pytest.mark.parametrize("topo", [SMALL, HyperX(n=3, q=3)])
def test_linkspace_dst_switch_matches_loop_construction(topo):
    ls = LinkSpace(topo)
    coords = topo.all_switch_coords()
    S = topo.num_switches
    ref = np.empty((S, topo.q, topo.n), dtype=np.int64)
    valid_ref = np.ones((S, topo.q, topo.n), dtype=bool)
    s = np.arange(S)
    for dim in range(topo.q):
        for v in range(topo.n):
            nc = coords.copy()
            nc[:, dim] = v
            ids = np.zeros(S, dtype=np.int64)
            for d2 in range(topo.q):
                ids = ids * topo.n + nc[:, d2]
            ref[:, dim, v] = ids
        valid_ref[s, dim, coords[:, dim]] = False
    np.testing.assert_array_equal(ls.dst_switch, ref)
    np.testing.assert_array_equal(ls.valid, valid_ref)


# ------------------------------------------------------------ fault masking
def test_fail_links_kills_both_directions():
    mask = _one_link_mask()
    coords = SMALL.all_switch_coords()
    n = SMALL.n
    d = int(np.flatnonzero(coords[0] != coords[1])[0])
    assert not mask[0, d * n + coords[1, d]]
    assert not mask[1, d * n + coords[0, d]]
    assert mask.sum() == mask.size - 2
    assert is_connected(SMALL, mask)


def test_fail_links_rejects_non_neighbours():
    with pytest.raises(ValueError):
        fail_links(SMALL, [(0, 5)])  # diagonal: Hamming distance 2


def test_fail_switches_removes_intermediate():
    healthy_pool, healthy_n = intermediate_pool(SMALL, no_faults(SMALL))
    assert healthy_n == SMALL.num_switches
    mask = fail_switches(SMALL, [3])
    assert not mask[3].any()
    pool, n_mid = intermediate_pool(SMALL, mask)
    assert n_mid == SMALL.num_switches - 1
    assert 3 not in pool.tolist()
    assert not is_connected(SMALL, mask)  # switch 3 is unreachable


def test_random_link_faults_rate_zero_and_bounds():
    assert random_link_faults(SMALL, 0.0).all()
    with pytest.raises(ValueError):
        random_link_faults(SMALL, 1.5)
    m1 = random_link_faults(SMALL, 0.2, seed=4)
    m2 = random_link_faults(SMALL, 0.2, seed=4)
    np.testing.assert_array_equal(m1, m2)  # deterministic in the seed


def test_faults_from_endpoints_deterministic_and_whole_switch():
    m1 = faults_from_endpoints(SMALL, [5, 9], seed=1)
    m2 = faults_from_endpoints(SMALL, [5, 9], seed=1)
    np.testing.assert_array_equal(m1, m2)
    assert not m1.all()  # each failed endpoint took a cable with it
    # all endpoints of switch 2 dead -> switch powered off
    eps = [2 * SMALL.concentration + c for c in range(SMALL.concentration)]
    mask = faults_from_endpoints(SMALL, eps, seed=1)
    assert not mask[2].any()


def test_workload_carries_mask_into_tables():
    mask = _one_link_mask()
    wl = apply_faults(_a2a_workload("row"), mask)
    prep = make_workload_tables(wl)
    np.testing.assert_array_equal(np.asarray(prep.tables.link_ok[0]), mask)
    assert int(prep.tables.n_mid[0]) == SMALL.num_switches
    healthy = make_workload_tables(_a2a_workload("row"))
    assert np.asarray(healthy.tables.link_ok).all()
    # same shape bucket: fault scenarios batch with healthy ones
    assert prep.tables.shape_bucket == healthy.tables.shape_bucket


def test_apply_faults_rejects_wrong_shape():
    with pytest.raises(ValueError):
        apply_faults(_a2a_workload("row"), np.ones((3, 3), dtype=bool))


# --------------------------------------------- seed-pinned min / omniwar
def test_min_omniwar_bit_identical_to_seed_outputs():
    """The registry-driven kernel must reproduce the recorded outputs of
    the seed (pre-subsystem) simulator exactly — same trajectories, same
    PRNG draws (policies without intermediates split 3 keys like the
    seed did).  Recorded under jax's default
    ``jax_threefry_partitionable=True`` (jax >= 0.5)."""
    wl = _a2a_workload("row")
    r = get_engine(SMALL, mode="omniwar").run(wl, seed=0, horizon=5000)
    assert (r.makespan, r.delivered, r.injected) == (31, 240, 240)
    assert r.avg_latency == pytest.approx(6.9625)
    assert r.avg_hops == pytest.approx(1.15)

    r = get_engine(SMALL, mode="min").run(wl, seed=0, horizon=5000)
    assert (r.makespan, r.delivered, r.injected) == (37, 240, 240)
    assert r.avg_latency == pytest.approx(9.920833333333333)
    assert r.avg_hops == pytest.approx(0.8)


def test_explicit_all_healthy_mask_is_identity():
    """A workload carrying an all-True mask must land in the same bucket
    and produce the same results as one carrying none."""
    wl = _a2a_workload("diagonal")
    wl_mask = apply_faults(wl, no_faults(SMALL))
    eng = get_engine(SMALL, mode="omniwar")
    assert eng.run(wl, seed=3, horizon=5000) == eng.run(
        wl_mask, seed=3, horizon=5000
    )


# --------------------------------- VAL / UGAL delivery + conservation
MASKS = {
    "healthy": None,
    "one_link": _one_link_mask,
    "two_links": _two_link_mask,
}


@pytest.mark.parametrize("mode", ["val", "ugal"])
@pytest.mark.parametrize("mask_name", list(MASKS))
def test_val_ugal_deliver_and_conserve(mode, mask_name):
    """Every injected packet is delivered exactly once (conservation) and
    all ranks complete — healthy and around dead links (escalation)."""
    mask = MASKS[mask_name]() if MASKS[mask_name] else None
    if mask is not None:
        assert is_connected(SMALL, mask)
    eng = get_engine(SMALL, mode=mode)
    wls = [_a2a_workload(s, link_ok=mask) for s in ("row", "diagonal")]
    grid = eng.run_grid(wls, seeds=[0, 1], horizon=20_000)
    for res in (grid[0][0], grid[1][1]):  # workload i with seed i
        assert res.completed
        assert res.delivered == 240          # == wl.target_packets
        assert res.injected == res.delivered  # no duplication, no loss
        assert res.max_hops < eng.static.V   # hop-indexed VC invariant


@pytest.mark.parametrize("mode", ["min", "omniwar", "val", "ugal"])
def test_hop_budget_invariant_under_faults(mode):
    """Observed worst-case hops stay inside the policy's declared VC
    budget (deadlock freedom, 2404.04315's constraint) even when routing
    around faults forces escalated deroutes."""
    eng = get_engine(SMALL, mode=mode)
    wl = _a2a_workload("row", link_ok=_two_link_mask())
    res = eng.run(wl, seed=2, horizon=20_000)
    assert res.completed
    policy = get_policy(mode)
    budget = policy.vc_budget(SMALL.q, policy.default_deroutes(SMALL.q))
    assert eng.static.V == budget
    assert res.max_hops < budget


def test_min_mode_fault_escalation_actually_deroutes():
    """Under min routing a dead minimal link forces non-minimal hops:
    the row partition's traffic is single-dimension (1 hop minimal), so
    routing around the dead (0, 1) cable must lengthen some path."""
    eng = get_engine(SMALL, mode="min")
    healthy = eng.run(_a2a_workload("row"), seed=0, horizon=20_000)
    assert healthy.max_hops == 1  # row a2a: strictly minimal, one dim
    faulty = eng.run(
        _a2a_workload("row", link_ok=_one_link_mask()), seed=0,
        horizon=20_000,
    )
    assert faulty.completed
    assert faulty.max_hops > healthy.max_hops  # escalated deroutes happened


def test_val_escape_does_not_bounce_between_cut_switches():
    """Regression: switch 11 keeps one of its three dimension-0 links.  A
    VAL packet bound for it from switch 3 must escape through switch 7,
    whose link to 11 is healthy, not to switch 15, which lost its own: a
    3 <-> 15 bounce spent the whole deroute budget and stranded it."""
    eng = get_engine(SMALL, mode="val")
    mask = fail_links(SMALL, [(11, 15), (3, 11)])
    res = eng.run(_a2a_workload("l_shape", link_ok=mask), seed=78,
                  horizon=20_000)
    assert res.completed
    assert res.delivered == res.injected == 240
    assert res.max_hops < eng.static.V


if hst is not None:
    @given(
        hst.sampled_from(["val", "ugal"]),
        hst.sampled_from(["row", "diagonal", "l_shape"]),
        hst.integers(0, 2 ** 16),
        hst.integers(0, 2),
    )
    @settings(max_examples=10, deadline=None)
    def test_delivery_conservation_property(mode, strategy, seed, n_faults):
        """Property: for any seed and up to two dead cables (the n=4, q=2
        Hamming graph has min cut 6, so it stays connected), VAL/UGAL
        deliver every packet exactly once within the VC budget."""
        mask = None
        if n_faults:
            rng = np.random.default_rng(seed)
            cables = SMALL.link_array()
            pick = rng.choice(len(cables), size=n_faults, replace=False)
            mask = fail_links(
                SMALL, [tuple(map(int, cables[i])) for i in pick]
            )
            assert is_connected(SMALL, mask)
        eng = get_engine(SMALL, mode=mode)
        wl = _a2a_workload(strategy, link_ok=mask)
        res = eng.run(wl, seed=seed % 97, horizon=20_000)
        assert res.completed
        assert res.delivered == res.injected == 240
        assert res.max_hops < eng.static.V
else:
    def test_delivery_conservation_property():
        pytest.importorskip("hypothesis")


# ------------------------------------------------ compile economics pins
def test_routing_fault_grid_one_compile_per_bucket():
    """A routing x strategy x fault x seed grid through run_grid is
    ONE trace and ONE device call per shape bucket: fault masks and
    intermediate pools are workload *data*, not compile keys."""
    engine = SimEngine(SMALL, mode="ugal")
    masks = [None, _one_link_mask(), _two_link_mask()]
    wls = [
        _a2a_workload(s, link_ok=m)
        for s in ("row", "diagonal") for m in masks
    ]
    grid = engine.run_grid(wls, seeds=(0, 1), horizon=20_000)
    assert engine.trace_count == 1
    assert engine.device_calls == 1
    assert all(r.completed for per_seed in grid for r in per_seed)
    # the batched grid returns exactly the per-scenario results
    assert grid[1][1] == engine.run(wls[1], seed=1, horizon=20_000)


# --------------------------------------------- scheduler churn integration
def test_snapshot_churn_faults_lower_to_masks():
    from repro.sched import FailureEvent, Job, OnlineScheduler
    from repro.sched.bridge import snapshot_workload

    jobs = [
        Job(job_id=0, arrival=0.0, blocks=2, service=30.0),
        Job(job_id=1, arrival=1.0, blocks=1, service=30.0),
    ]
    sched = OnlineScheduler(SMALL, strategy="diagonal")
    res = sched.run_stream(
        jobs, failures=(FailureEvent(time=5.0, endpoints=(40,)),)
    )
    churned = [s for s in res.snapshots if s.failed_endpoints]
    assert churned, "failure produced no churned snapshot"
    snap = churned[-1]
    assert snap.failed_endpoints == (40,)
    wl = snapshot_workload(SMALL, snap, churn_faults=True)
    assert wl.link_ok is not None and not wl.link_ok.all()
    assert is_connected(SMALL, wl.link_ok)
    plain = snapshot_workload(SMALL, snap)
    assert plain.link_ok is None
