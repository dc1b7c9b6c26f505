"""Resilience subsystem tests (engine side): epoch-schedule lowering,
the E=1 bit-identity + trace-count pins vs the static fault path (across
``run`` AND ``run_grid``, all routing policies),
dynamic mid-flight mask flips, fault edge cases (fully-dead switch, dead
self-ports), telemetry fault counters, and the packet-conservation
property under arbitrary epoch schedules."""

import numpy as np
import pytest

try:  # optional test extra (pip install -e .[test]); property tests need it
    from hypothesis import given, settings, strategies as hst
except ImportError:  # pragma: no cover - exercised only without hypothesis
    given = settings = hst = None

from repro import traffic as tr
from repro.core.allocation import allocate_partition
from repro.core.engine import SimEngine
from repro.core.hyperx import HyperX
from repro.obs import TelemetrySpec
from repro.resil import (
    FaultSchedule,
    apply_schedule,
    exponential_lifetimes,
    sample_components,
    schedule_from_masks,
    scripted_campaign,
    static_schedule,
    to_epoch_schedule,
    to_failure_events,
)
from repro.route import (
    apply_faults,
    fail_links,
    fail_switches,
    no_faults,
    self_port_mask,
)

SMALL = HyperX(n=4, q=2)
POLICIES = ("min", "omniwar", "ugal", "val")


def _a2a(strategy="diagonal", link_ok=None, schedule=None):
    part = allocate_partition(strategy, SMALL, 0)
    wl = tr.compose_workload(
        SMALL, [(tr.all_to_all(16), part)], link_ok=link_ok
    )
    if schedule is not None:
        wl = apply_schedule(wl, schedule)
    return wl


def _conserved(r):
    assert r.injected == r.ejected + r.stranded
    assert sum(r.epoch_injected) == r.injected
    assert sum(r.epoch_delivered) == r.delivered
    assert r.delivered <= r.injected


# ---------------------------------------------------------- schedule objects
def test_fault_schedule_validation():
    mask = no_faults(SMALL)[None]
    with pytest.raises(ValueError, match="start at cycle 0"):
        FaultSchedule(epoch_start=np.array([5]), link_ok=mask)
    with pytest.raises(ValueError, match="strictly increasing"):
        FaultSchedule(
            epoch_start=np.array([0, 9, 9]),
            link_ok=np.repeat(mask, 3, axis=0),
        )
    with pytest.raises(ValueError, match="NE=2"):
        FaultSchedule(epoch_start=np.array([0, 4]), link_ok=mask)
    s = FaultSchedule(epoch_start=np.array([0, 10]),
                      link_ok=np.repeat(mask, 2, axis=0))
    assert s.NE == 2
    assert s.epoch_at(0) == 0 and s.epoch_at(9) == 0 and s.epoch_at(10) == 1
    assert s.mask_at(10_000).shape == (SMALL.num_switches, SMALL.q * SMALL.n)


def test_schedule_from_masks_prepends_healthy_epoch0():
    m = fail_links(SMALL, [(0, 1)])
    s = schedule_from_masks(SMALL, [(7, m)])
    assert s.NE == 2 and s.epoch_start.tolist() == [0, 7]
    assert s.link_ok[0].all()                 # synthesized healthy epoch 0
    assert (s.link_ok[1] == m).all()
    # duplicate start cycles: last-given mask wins (event sourcing)
    m2 = fail_links(SMALL, [(5, 9)])
    s2 = schedule_from_masks(SMALL, [(0, m), (0, m2)])
    assert s2.NE == 1 and (s2.link_ok[0] == m2).all()
    with pytest.raises(ValueError, match="mask shape"):
        schedule_from_masks(SMALL, [(0, np.ones((3, 3), dtype=bool))])


def test_apply_schedule_rejects_topology_mismatch():
    other = HyperX(n=3, q=2)
    with pytest.raises(ValueError, match="workload topology"):
        apply_schedule(_a2a(), static_schedule(other))


# ----------------------------------------------------- E=1 bit-identity pins
@pytest.mark.parametrize("mode", POLICIES)
def test_one_epoch_schedule_bit_identical_to_static_path(mode):
    """A 1-epoch schedule must lower to the engine's static fault path:
    every SimResult field exact, and no extra XLA trace (same bucket)."""
    engine = SimEngine(SMALL, mode=mode)
    mask = fail_links(SMALL, [(0, 1), (5, 9)])
    r_static = engine.run(_a2a(link_ok=mask), seed=3, horizon=5000)
    r_sched = engine.run(
        _a2a(schedule=static_schedule(SMALL, mask)), seed=3, horizon=5000
    )
    assert r_static == r_sched  # dataclass equality: every field exact
    assert engine.trace_count == 1  # E=1 shares the static compilation
    assert engine.device_calls == 2


@pytest.mark.parametrize("mode", POLICIES)
def test_e1_pin_run_grid_and_run(mode):
    """The E=1 pin holds through the grid and the single run: static-mask
    and 1-epoch-schedule workloads land in one bucket, one trace, and
    produce bit-identical results."""
    engine = SimEngine(SMALL, mode=mode)
    mask = fail_links(SMALL, [(0, 1)])
    wls = [
        _a2a(link_ok=mask),
        _a2a(schedule=static_schedule(SMALL, mask)),
    ]
    seeds = (0, 3)
    grid = engine.run_grid(wls, seeds=seeds, horizon=4000)
    assert engine.trace_count == 1
    assert engine.device_calls == 1
    assert grid[1] == grid[0]            # schedule lane == static lane
    assert engine.run_grid(wls, seeds=seeds, horizon=4000) == grid
    assert engine.trace_count == 1       # no re-trace for a repeat grid
    assert engine.run(wls[1], seed=3, horizon=4000) == grid[0][1]


def test_unscheduled_workload_tables_stay_single_epoch():
    engine = SimEngine(SMALL, mode="min")
    prep = engine.prepare(_a2a())
    assert prep.NE == 1
    assert prep.tables.NE == 1
    assert prep.tables.epoch_start.tolist() == [0]


# ------------------------------------------------------------ dynamic epochs
def test_mid_flight_flip_counts_per_epoch():
    """A fail/repair campaign opens three epochs; the per-epoch counters
    tile the totals and the run still completes after the repair."""
    events = scripted_campaign([
        (5, "fail", "link", (0, 1)),
        (15, "repair", "link", (0, 1)),
    ])
    sched = to_epoch_schedule(SMALL, events)
    assert sched.NE == 3
    assert sched.epoch_start.tolist() == [0, 5, 15]
    assert sched.link_ok[0].all() and sched.link_ok[2].all()
    assert not sched.link_ok[1].all()

    engine = SimEngine(SMALL, mode="min")
    r = engine.run(_a2a(schedule=sched), seed=0, horizon=8000)
    _conserved(r)
    assert len(r.epoch_delivered) == 3
    assert r.completed
    assert sum(1 for x in r.epoch_delivered if x > 0) >= 2


def test_epoch_padding_is_semantics_free():
    """NE pads to a power of two; a 3-epoch schedule (padded to 4) must
    attribute zero traffic to the pad epoch."""
    events = scripted_campaign([
        (30, "fail", "link", (2, 6)),
        (90, "repair", "link", (2, 6)),
    ])
    engine = SimEngine(SMALL, mode="omniwar")
    r = engine.run(_a2a(schedule=to_epoch_schedule(SMALL, events)),
                   seed=1, horizon=8000)
    _conserved(r)
    assert len(r.epoch_delivered) == 3  # trimmed back to the real NE


def test_fully_dead_switch_strands_but_conserves():
    """A switch that powers off mid-run strands its traffic; nothing is
    double-counted and the sim terminates cleanly at the horizon."""
    events = scripted_campaign([(20, "fail", "switch", (0,))])
    sched = to_epoch_schedule(SMALL, events)
    assert sched.NE == 2
    assert not sched.link_ok[1][0].any()      # all outgoing ports dead
    engine = SimEngine(SMALL, mode="min")
    target = _a2a().target_packets
    r = engine.run(_a2a(schedule=sched), seed=0, horizon=3000)
    _conserved(r)
    assert not r.completed
    assert r.stranded > 0
    assert r.delivered < target


def test_dead_self_ports_are_invariant():
    """Self-ports are never valid links; additionally marking them dead in
    every epoch mask must not change any simulated field."""
    coords = SMALL.all_switch_coords()
    valid = self_port_mask(coords, SMALL.n, SMALL.q)
    mask = fail_links(SMALL, [(0, 1)])
    sched_a = schedule_from_masks(SMALL, [(0, mask), (50, no_faults(SMALL))])
    sched_b = schedule_from_masks(
        SMALL, [(0, mask & valid), (50, no_faults(SMALL) & valid)]
    )
    engine = SimEngine(SMALL, mode="omniwar")
    ra = engine.run(_a2a(schedule=sched_a), seed=5, horizon=5000)
    rb = engine.run(_a2a(schedule=sched_b), seed=5, horizon=5000)
    assert ra == rb
    assert engine.trace_count == 1


def test_schedule_stacks_with_static_mask():
    """apply_schedule composes with a permanent wl.link_ok mask: the
    engine ANDs both, so a run with (static dead cable) + (healthy
    schedule) equals the static-only run."""
    mask = fail_links(SMALL, [(5, 9)])
    engine = SimEngine(SMALL, mode="ugal")
    r_static = engine.run(_a2a(link_ok=mask), seed=2, horizon=5000)
    r_both = engine.run(
        _a2a(link_ok=mask, schedule=static_schedule(SMALL)), seed=2,
        horizon=5000,
    )
    assert r_static == r_both


# -------------------------------------------------------- telemetry counters
def test_telemetry_counts_epoch_flips_and_dead_links():
    spec = TelemetrySpec(n_windows=8, window=512)
    events = scripted_campaign([
        (5, "fail", "link", (0, 1)),
        (15, "repair", "link", (0, 1)),
    ])
    engine = SimEngine(SMALL, mode="min", telemetry=spec)
    r = engine.run(_a2a(schedule=to_epoch_schedule(SMALL, events)),
                   seed=0, horizon=8000)
    tel = r.telemetry
    assert int(tel.epoch_flips.sum()) == 2      # one flip per boundary
    assert float(tel.mean_dead_links().max()) > 0.0
    assert tel.summary()["epoch_flips"] == 2
    r0 = engine.run(_a2a(), seed=0, horizon=8000)
    assert int(r0.telemetry.epoch_flips.sum()) == 0
    assert float(r0.telemetry.dead_links.sum()) == 0.0


# ----------------------------------------------------------- fault processes
def test_exponential_lifetimes_deterministic_and_alternating():
    comps = sample_components(SMALL, n_links=3, seed=7)
    assert len(comps) == 3 and all(k == "link" for k, _ in comps)
    ev1 = exponential_lifetimes(comps, mtbf=30, mttr=10, horizon=500, seed=7)
    ev2 = exponential_lifetimes(comps, mtbf=30, mttr=10, horizon=500, seed=7)
    assert ev1 == ev2
    assert ev1 == sorted(ev1)
    for comp in comps:
        kinds = [e.up for e in ev1 if (e.kind, e.ident) == comp]
        # per component: strict fail/repair alternation starting at a fail
        assert kinds == [bool(i % 2) for i in range(len(kinds))]
    with pytest.raises(ValueError, match="positive"):
        exponential_lifetimes(comps, mtbf=-1, mttr=10, horizon=100)


def test_to_epoch_schedule_coarsens_deterministically():
    comps = sample_components(SMALL, n_links=8, seed=3)
    events = exponential_lifetimes(comps, mtbf=20, mttr=8, horizon=2000,
                                   seed=3)
    full = to_epoch_schedule(SMALL, events, max_epochs=1024)
    coarse = to_epoch_schedule(SMALL, events, max_epochs=6)
    assert full.NE > 6 >= coarse.NE
    assert coarse.epoch_start[0] == 0
    assert (np.diff(coarse.epoch_start) > 0).all()
    # coarse boundaries are a subset of the full replay's boundaries
    assert set(coarse.epoch_start.tolist()) <= set(full.epoch_start.tolist())
    with pytest.raises(ValueError, match="max_epochs"):
        to_epoch_schedule(SMALL, events, max_epochs=0)


def test_scripted_campaign_validates_and_switch_mask_matches():
    with pytest.raises(ValueError, match="unknown action"):
        scripted_campaign([(0, "explode", "link", (0, 1))])
    with pytest.raises(ValueError, match="unknown component kind"):
        scripted_campaign([(0, "fail", "cable", (0, 1))])
    sched = to_epoch_schedule(
        SMALL, scripted_campaign([(10, "fail", "switch", (3,))])
    )
    assert (sched.link_ok[1] == fail_switches(SMALL, [3])).all()


def test_to_failure_events_pairs_repairs():
    events = scripted_campaign([
        (5, "fail", "endpoint", (2,)),
        (9, "repair", "endpoint", (2,)),
        (20, "fail", "endpoint", (7,)),
        (11, "fail", "link", (0, 1)),   # non-endpoint kinds are skipped
    ])
    fes = to_failure_events(events, time_scale=0.5)
    assert len(fes) == 2
    assert (fes[0].time, fes[0].endpoints, fes[0].repair_at) == (2.5, (2,), 4.5)
    assert (fes[1].time, fes[1].endpoints, fes[1].repair_at) == (10.0, (7,), None)


# ------------------------------------------------------- conservation property
if given is not None:
    _CABLES = [(0, 1), (0, 4), (5, 9), (2, 6), (10, 11), (12, 8)]

    @settings(max_examples=8, deadline=None)
    @given(
        starts=hst.lists(hst.integers(1, 400), min_size=0, max_size=3,
                         unique=True),
        picks=hst.lists(hst.sets(hst.integers(0, len(_CABLES) - 1)),
                        min_size=4, max_size=4),
        seed=hst.integers(0, 3),
    )
    def test_packet_conservation_any_epoch_schedule(starts, picks, seed):
        """injected == ejected + stranded under ANY epoch schedule —
        including ones that disconnect parts of the machine."""
        entries = [
            (t, fail_links(SMALL, [_CABLES[i] for i in sorted(pick)]))
            for t, pick in zip([0] + sorted(starts), picks)
        ]
        sched = schedule_from_masks(SMALL, entries)
        engine = _property_engine()
        r = engine.run(_a2a(schedule=sched), seed=seed, horizon=2500)
        _conserved(r)
        assert len(r.epoch_delivered) == sched.NE
else:  # pragma: no cover - hypothesis not installed
    def test_packet_conservation_any_epoch_schedule():
        pytest.importorskip("hypothesis")


_PROPERTY_ENGINE = None


def _property_engine():
    """One engine for every hypothesis example: compilations are reused
    across examples (buckets key on padded NE only)."""
    global _PROPERTY_ENGINE
    if _PROPERTY_ENGINE is None:
        _PROPERTY_ENGINE = SimEngine(SMALL, mode="min")
    return _PROPERTY_ENGINE
