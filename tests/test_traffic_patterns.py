"""Traffic-pattern subsystem tests: registry contract, bit-identical pins
for every migrated pattern, invariants of the new patterns (conservation,
bijectivity, involution, reciprocity — hypothesis-backed), phased
composition, the declarative scenario layer, and the one-compile-per-
bucket pin for pattern x strategy x seed grids."""

import hashlib

import numpy as np
import pytest

try:  # optional test extra (pip install -e .[test]); property tests need it
    from hypothesis import given, settings, strategies as hst
except ImportError:  # pragma: no cover - exercised only without hypothesis
    given = settings = hst = None

from repro import traffic as tr
from repro.core.allocation import allocate_partition
from repro.core.engine import SimEngine, get_engine
from repro.core.engine.workload_tables import shape_bucket
from repro.core.hyperx import HyperX
from repro.traffic import (
    AppSpec,
    AppTraffic,
    BackgroundSpec,
    PhaseSpec,
    ScenarioSpec,
    TrafficPattern,
    available_patterns,
    build_phases,
    build_workload,
    compose_workload,
    concat_phases,
    empty_tables,
    get_pattern,
    grid_shape,
    register_pattern,
)

SMALL = HyperX(n=4, q=2)

ALL_PATTERNS = (
    "all_reduce", "all_to_all", "incast", "random_involution",
    "random_permutation", "random_switch_permutation", "recursive_doubling",
    "ring_allreduce", "shuffle", "stencil_3d", "stencil_moore",
    "stencil_von_neumann", "tornado", "transpose", "uniform",
)


# ------------------------------------------------------------------ registry
def test_available_patterns_lists_all():
    assert available_patterns() == ALL_PATTERNS


def test_available_patterns_kind_filter():
    adv = available_patterns(kind="adversarial")
    assert "tornado" in adv and "transpose" in adv and "shuffle" in adv
    assert "all_to_all" not in adv


def test_unknown_pattern_raises_with_registered_names():
    with pytest.raises(ValueError) as e:
        get_pattern("bogus")
    msg = str(e.value)
    for name in ("all_to_all", "tornado", "stencil_3d"):
        assert name in msg


def test_register_duplicate_rejected():
    with pytest.raises(ValueError):
        register_pattern(TrafficPattern("uniform", tr.uniform))


def test_seed_only_threads_into_seeded_patterns():
    # unseeded builders must stay bit-identical whatever seed is passed
    a = get_pattern("all_to_all").build(16, seed=7)
    b = tr.all_to_all(16)
    np.testing.assert_array_equal(a.sends_dst, b.sends_dst)
    # seeded builders pick the seed up, explicit params win
    p1 = get_pattern("random_permutation").build(16, seed=3)
    p2 = tr.random_permutation(16, seed=3)
    np.testing.assert_array_equal(p1.sends_dst, p2.sends_dst)
    # a phase that pins its own seed wins over the derived scenario seed
    p3 = build_phases([("random_permutation", {"seed": 5})], 16, seed=3)
    np.testing.assert_array_equal(
        p3.sends_dst, tr.random_permutation(16, seed=5).sends_dst
    )


# --------------------------------------------- bit-identical migration pins
def _tables_hash(app: AppTraffic) -> str:
    m = hashlib.sha256()
    for a in (app.sends_dst, app.npkts, app.deg, app.recv_need,
              app.sampled, app.lo, app.hi):
        m.update(np.ascontiguousarray(a).tobytes())
    m.update(str(app.window).encode())
    return m.hexdigest()[:16]


# recorded from the seed builders (core/traffic.py + collective_sim.py
# private builders) at k=16, seed=0, before the registry migration
MIGRATION_PINS = {
    "uniform": "3e6e35f86624a759",
    "random_permutation": "87f3425aaeb94c51",
    "random_switch_permutation": "106b703ef8094c96",
    "all_to_all": "4b37b9a8e3a844ed",
    "all_reduce": "862e1f9ba9557703",
    "stencil_von_neumann": "a9a8b28907fa382e",
    "stencil_moore": "6be0387947ba6167",
    "random_involution": "762293eac51454c6",
    "ring_allreduce": "80f93c4ed4036548",
}
PIN_ARGS = {
    "random_switch_permutation": {"group": 4},
    "ring_allreduce": {"packets_per_step": 4},
}


@pytest.mark.parametrize("name", sorted(MIGRATION_PINS))
def test_migrated_pattern_bit_identical_to_seed(name):
    app = get_pattern(name).build(16, seed=0, **PIN_ARGS.get(name, {}))
    assert _tables_hash(app) == MIGRATION_PINS[name]
    assert app.name == name


def test_ring_allreduce_matches_former_private_builder():
    """Parity pin for the collective_sim dedup: the registry pattern must
    reproduce fabric/collective_sim.py's deleted _ring_allreduce_app."""
    k, pps = 8, 4
    T = 2 * (k - 1)
    dst, npk, deg, recv = empty_tables(k, T, 1)
    r = np.arange(k)
    for t in range(T):
        dst[:, t, 0] = (r + 1) % k
        npk[:, t, 0] = pps
        deg[:, t] = 1
        recv[:, t] = pps
    ref = AppTraffic("ring_allreduce", k, dst, npk, deg, recv, window=1)
    app = get_pattern("ring_allreduce").build(k, packets_per_step=pps)
    assert _tables_hash(app) == _tables_hash(ref)


def test_axis_collective_workload_uses_registry():
    from repro.fabric.collective_sim import axis_collective_workload
    from repro.fabric.placement import place_job

    p = place_job("diagonal", (8, 8), ("data", "model"))
    wl = axis_collective_workload(p, "model", "all_reduce", num_groups=2)
    assert wl.names == ["ring_allreduce"] * 2


# ----------------------------------------------------- total_packets fix
def test_total_packets_ignores_padded_slots():
    """Regression: the old mask (sends_dst >= -1) was vacuously true and
    counted npkts sitting under padded (-1) destination slots."""
    dst = np.array([[[1, -1]], [[0, -1]]], dtype=np.int64)
    npk = np.array([[[2, 7]], [[3, 9]]], dtype=np.int64)  # 7/9 under pads
    deg = np.ones((2, 1), dtype=np.int64)
    recv = np.zeros((2, 1), dtype=np.int64)
    app = AppTraffic("t", 2, dst, npk, deg, recv, window=1)
    assert app.total_packets == 5  # not 21


def test_total_packets_after_phase_padding():
    """Phased concat pads the narrower phase's destination slots; the
    padded slots must not contribute."""
    a = get_pattern("stencil_von_neumann").build(16, rounds=2)  # maxd 4
    b = get_pattern("all_to_all").build(16)                     # maxd 1
    phased = concat_phases([a, b])
    assert phased.maxd == 4
    assert phased.total_packets == a.total_packets + b.total_packets


# -------------------------------------------------- new-pattern invariants
def _sent_per_step(app: AppTraffic) -> np.ndarray:
    """(k, T) packets arriving at each rank per step tag (fixed dsts)."""
    got = np.zeros((app.k, app.T), dtype=np.int64)
    for r in range(app.k):
        for t in range(app.T):
            for d in range(app.deg[r, t]):
                got[app.sends_dst[r, t, d], t] += app.npkts[r, t, d]
    return got


@pytest.mark.parametrize("name,params", [
    ("all_to_all", {}),
    ("all_reduce", {}),
    ("recursive_doubling", {}),
    ("ring_allreduce", {}),
    ("incast", {"targets": 2}),
    ("stencil_3d", {"rounds": 3}),
])
def test_send_recv_conservation(name, params):
    """Every packet a synchronized kernel sends is expected by exactly one
    receiver at the same step tag: arrivals == recv_need, step by step."""
    app = get_pattern(name).build(16, **params)
    np.testing.assert_array_equal(_sent_per_step(app), app.recv_need)


@pytest.mark.parametrize("name", ["transpose", "shuffle", "tornado"])
def test_adversarial_patterns_are_bijective(name):
    app = get_pattern(name).build(64)
    send = app.deg[:, 0] > 0
    dsts = app.sends_dst[send, 0, 0]
    assert len(np.unique(dsts)) == send.sum()  # injective on senders
    assert not np.isin(np.flatnonzero(send), dsts[dsts == np.flatnonzero(send)]).any()


def test_transpose_involution_on_square_grid():
    app = get_pattern("transpose").build(64)  # 8x8 grid
    target = np.arange(64)
    send = app.deg[:, 0] > 0
    target[send] = app.sends_dst[send, 0, 0]
    np.testing.assert_array_equal(target[target], np.arange(64))
    # diagonal ranks idle: 8 fixed points on an 8x8 transpose
    assert (~send).sum() == 8


def test_shuffle_is_bit_rotation():
    app = get_pattern("shuffle").build(16)
    send = app.deg[:, 0] > 0
    assert not send[0] and not send[15]  # all-zeros/all-ones fixed points
    for r in np.flatnonzero(send):
        assert app.sends_dst[r, 0, 0] == ((r << 1) | (r >> 3)) & 15


def test_tornado_offset_and_no_self_sends():
    app = get_pattern("tornado").build(16)  # 4x4 grid, offsets (2, 2)
    r = np.arange(16)
    y, x = r // 4, r % 4
    expect = ((y + 2) % 4) * 4 + (x + 2) % 4
    np.testing.assert_array_equal(app.sends_dst[:, 0, 0], expect)
    assert (app.sends_dst[:, :, 0] != r[:, None]).all()
    with pytest.raises(ValueError):
        get_pattern("tornado").build(16, offsets=(0, 0))


def test_incast_focuses_on_sinks():
    app = get_pattern("incast").build(16, packets=4, targets=2)
    assert (app.deg[:2] == 0).all()            # sinks never send
    assert (app.sends_dst[2:, :, 0] < 2).all()  # everyone targets a sink
    assert app.recv_need[:2].sum() == app.total_packets
    with pytest.raises(ValueError):
        get_pattern("incast").build(16, targets=16)


def test_recursive_doubling_vs_rabenseifner():
    rd = get_pattern("recursive_doubling").build(16, vector_packets=64)
    rab = get_pattern("all_reduce").build(16, vector_packets=64)
    assert rd.T == 4 and rab.T == 8  # half the steps...
    assert rd.total_packets == 16 * 4 * 64  # ...but full-vector exchanges
    assert rd.total_packets > rab.total_packets
    for t in range(rd.T):
        d = rd.sends_dst[:, t, 0]
        np.testing.assert_array_equal(d[d], np.arange(16))  # partner symmetry


def test_stencil_3d_neighbor_reciprocity():
    app = get_pattern("stencil_3d").build(64, rounds=2)  # 4x4x4 torus
    assert app.maxd == 6 and (app.deg == 6).all()
    # r sends to s exactly as often as s sends to r, per round
    sent = np.zeros((64, 64), dtype=np.int64)
    for r in range(64):
        for d in range(6):
            sent[r, app.sends_dst[r, 0, d]] += 1
    np.testing.assert_array_equal(sent, sent.T)
    # every 3D von-Neumann neighbour is at torus grid distance 1
    gz = gy = gx = 4
    for r in (0, 21, 63):
        z, y, x = r // 16, (r // 4) % 4, r % 4
        for d in range(6):
            nb = app.sends_dst[r, 0, d]
            nz, ny, nx = nb // 16, (nb // 4) % 4, nb % 4
            dist = (min((z - nz) % gz, (nz - z) % gz)
                    + min((y - ny) % gy, (ny - y) % gy)
                    + min((x - nx) % gx, (nx - x) % gx))
            assert dist == 1
    with pytest.raises(ValueError):
        get_pattern("stencil_3d").build(4)  # a dim of size 1


def test_grid_shape_2d_matches_seed_and_3d_factors():
    assert grid_shape(64) == (8, 8)
    assert grid_shape(32) == (4, 8)   # the seed 2D split
    assert grid_shape(12) == (2, 6)
    assert grid_shape(64, ndim=3) == (4, 4, 4)
    assert grid_shape(16, ndim=3) == (2, 2, 4)
    with pytest.raises(ValueError):
        grid_shape(9, ndim=3)


if hst is not None:
    @given(hst.sampled_from([4, 16, 64]), hst.integers(0, 2 ** 16))
    @settings(max_examples=20, deadline=None)
    def test_involution_property(k, seed):
        app = get_pattern("random_involution").build(k, seed=seed, packets=2)
        partner = app.sends_dst[:, 0, 0]
        np.testing.assert_array_equal(partner[partner], np.arange(k))
        assert not (partner == np.arange(k)).any()

    @given(
        hst.sampled_from(["transpose", "shuffle", "tornado"]),
        hst.sampled_from([8, 16, 32, 64]),
    )
    @settings(max_examples=20, deadline=None)
    def test_bijectivity_property(name, k):
        app = get_pattern(name).build(k, packets=1)
        send = app.deg[:, 0] > 0
        dsts = app.sends_dst[send, 0, 0]
        assert len(np.unique(dsts)) == int(send.sum())
        assert (dsts != np.flatnonzero(send)).all()  # no self-sends

    @given(
        hst.sampled_from(["all_to_all", "recursive_doubling",
                          "ring_allreduce"]),
        hst.sampled_from([4, 8, 16]),
    )
    @settings(max_examples=15, deadline=None)
    def test_conservation_property(name, k):
        app = get_pattern(name).build(k)
        np.testing.assert_array_equal(_sent_per_step(app), app.recv_need)
else:  # pragma: no cover
    def test_property_suite_needs_hypothesis():
        pytest.importorskip("hypothesis")


# ------------------------------------------------------ phased composition
def test_concat_phases_shapes_order_window():
    a = get_pattern("stencil_von_neumann").build(16, rounds=3)  # window 1
    b = get_pattern("all_to_all").build(16)                     # window 15
    phased = concat_phases([a, b])
    assert phased.name == "stencil_von_neumann+all_to_all"
    assert phased.T == a.T + b.T
    assert phased.maxd == max(a.maxd, b.maxd)
    assert phased.window == 1  # min over phases
    np.testing.assert_array_equal(phased.sends_dst[:, : a.T, : a.maxd],
                                  a.sends_dst)
    np.testing.assert_array_equal(phased.sends_dst[:, a.T:, : b.maxd],
                                  b.sends_dst)
    # padded destination slots of the narrow phase stay pad
    assert (phased.sends_dst[:, a.T:, b.maxd:] == -1).all()
    assert concat_phases([a, b], window=4).window == 4
    with pytest.raises(ValueError):
        concat_phases([a, get_pattern("all_to_all").build(8)])
    with pytest.raises(ValueError):
        concat_phases([])


def test_single_phase_passthrough_is_bit_identical():
    app = build_phases(["all_to_all"], 16)
    ref = tr.all_to_all(16)
    assert _tables_hash(app) == _tables_hash(ref)


def test_phased_workload_runs_to_completion():
    """The canonical HPC iteration: stencil exchange rounds, then an
    all-reduce — one app, one ordered step table, every packet of both
    phases delivered."""
    part = allocate_partition("row", SMALL, 0)
    spec = ScenarioSpec(apps=(
        AppSpec(phases=(PhaseSpec("stencil_von_neumann", {"rounds": 2}),
                        PhaseSpec("all_reduce", {"vector_packets": 8})),
                placement=part),
    ))
    wl = build_workload(SMALL, spec)
    assert wl.names == ["stencil_von_neumann+all_reduce"]
    res = get_engine(SMALL, mode="omniwar").run(wl, seed=0, horizon=20_000)
    assert res.completed
    assert res.delivered == wl.target_packets


# -------------------------------------------------------- scenario layer
def test_build_workload_matches_manual_compose():
    part = allocate_partition("diagonal", SMALL, 0)
    spec = ScenarioSpec(apps=(AppSpec(phases="all_to_all", placement=part),))
    wl = build_workload(SMALL, spec)
    ref = compose_workload(SMALL, [(tr.all_to_all(16), part)])
    np.testing.assert_array_equal(wl.sends_dst, ref.sends_dst)
    np.testing.assert_array_equal(wl.npkts, ref.npkts)
    np.testing.assert_array_equal(wl.rank_ep, ref.rank_ep)
    np.testing.assert_array_equal(wl.window, ref.window)


def test_scenario_strategy_names_take_consecutive_blocks():
    spec = ScenarioSpec(apps=(
        AppSpec(phases="all_to_all", placement="row"),
        AppSpec(phases="all_to_all", placement="row"),
    ))
    wl = build_workload(SMALL, spec)
    assert wl.R == 32
    assert len(np.unique(wl.rank_ep)) == 32  # disjoint partitions


def test_scenario_background_and_warmup():
    part = allocate_partition("row", SMALL, 0)
    spec = ScenarioSpec(
        apps=(AppSpec(phases="uniform", placement=part),),
        background=BackgroundSpec(),
        warmup=50,
    )
    wl = build_workload(SMALL, spec)
    n_free = SMALL.num_endpoints - part.size
    assert wl.infinite.sum() == n_free
    assert (wl.start[~wl.infinite] == 50).all()
    assert wl.names[-1] == "bg:random_permutation"


def test_scenario_unknown_pattern_lists_registered():
    part = allocate_partition("row", SMALL, 0)
    with pytest.raises(ValueError, match="registered patterns"):
        build_workload(SMALL, ScenarioSpec(
            apps=(AppSpec(phases="nope", placement=part),)
        ))
    with pytest.raises(ValueError, match="registered patterns"):
        build_workload(SMALL, ScenarioSpec(
            apps=(AppSpec(phases="uniform", placement=part),),
            background=BackgroundSpec(pattern="nope"),
        ))


def test_scenario_seed_derivation():
    spec = ScenarioSpec(apps=(
        AppSpec(phases="random_permutation", placement="row"),
        AppSpec(phases="random_permutation", placement="row"),
    ), seed=7)
    wl = build_workload(SMALL, spec)
    # per-app derived seeds: the two permutations differ
    assert (wl.sends_dst[:16, 0, 0] - 0 != wl.sends_dst[16:, 0, 0] - 16).any()
    pinned = ScenarioSpec(apps=(
        AppSpec(phases="random_permutation", placement="row", seed=3),
    ))
    wl2 = build_workload(SMALL, pinned)
    np.testing.assert_array_equal(
        wl2.sends_dst[:, 0, 0],
        tr.random_permutation(16, seed=3).sends_dst[:, 0, 0],
    )


# ------------------------------------------------ compile economics pin
def test_pattern_grid_one_compile_per_bucket():
    """A pattern x strategy x seed grid over the NEW patterns through
    run_grid costs ONE trace and ONE device call per shape
    bucket: pattern tables are workload *data*, not compile keys."""
    engine = SimEngine(SMALL, mode="omniwar")
    patterns = ("transpose", "tornado", "shuffle", "incast", "stencil_3d")
    wls = [
        build_workload(SMALL, ScenarioSpec(apps=(
            AppSpec(phases=pat, placement=allocate_partition(s, SMALL, 0)),
        )))
        for s in ("row", "diagonal") for pat in patterns
    ]
    buckets = {shape_bucket(wl.R, wl.T, wl.maxd) for wl in wls}
    assert len(buckets) < len(wls)  # the axis genuinely shares buckets
    grid = engine.run_grid(wls, seeds=(0, 1), horizon=20_000)
    assert engine.trace_count == len(buckets)
    assert engine.device_calls == len(buckets)
    assert all(r.completed for per_seed in grid for r in per_seed)
    # the batched grid returns exactly the per-scenario results
    assert grid[2][1] == engine.run(wls[2], seed=1, horizon=20_000)


# ---------------------------------------------------------- seed surface
def test_traffic_keeps_seed_surface():
    """The seed's traffic names all live in ``repro.traffic``."""
    for name in ("AppTraffic", "Workload", "compose_workload",
                 "background_noise", "uniform", "all_to_all", "all_reduce",
                 "stencil", "random_involution", "empty_tables",
                 "grid_shape"):
        assert hasattr(tr, name), name
    for name in ("KERNELS", "STATIC_PATTERNS"):
        assert hasattr(tr.patterns, name), name
    assert set(tr.patterns.KERNELS) == {
        "all_to_all", "all_reduce", "stencil_von_neumann", "stencil_moore",
        "random_involution",
    }
