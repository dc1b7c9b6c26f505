"""Online scheduler subsystem tests: ledger invariants, replay
determinism, event-loop behavior, FleetRuntime drop-in, and the
batched-SimEngine interference bridge (one compile per shape bucket)."""

import dataclasses

import numpy as np
import pytest

try:  # optional test extra (pip install -e .[test]); property tests need it
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - exercised only without hypothesis
    given = settings = st = None

from repro.core.allocation import ALLOCATIONS, allocate_blocks, allocate_partition
from repro.core.hyperx import HyperX
from repro.runtime import FleetRuntime
from repro.sched import (
    BlockLedger,
    FailureEvent,
    Job,
    OnlineScheduler,
    evaluate_snapshots,
    heavy_tailed_stream,
    load_trace,
    poisson_stream,
    save_trace,
)
from repro.sched.bridge import pick_snapshots, snapshot_workload

STRATS = sorted(ALLOCATIONS)
SMALL = HyperX(n=4, q=2)
PAPER = HyperX(n=8, q=2)


# ------------------------------------------------------------ allocate_blocks
@pytest.mark.parametrize("strat", STRATS)
def test_allocate_blocks_matches_consecutive(strat):
    """Consecutive block lists reproduce allocate_partition exactly."""
    a = allocate_partition(strat, PAPER, 0, size=128, seed=3)
    b = allocate_blocks(strat, PAPER, [0, 1], size=128, seed=3)
    np.testing.assert_array_equal(a.endpoints, b.endpoints)


@pytest.mark.parametrize("strat", STRATS)
def test_allocate_blocks_arbitrary_sets_disjoint(strat):
    """Any disjoint block subsets yield disjoint endpoint sets."""
    p1 = allocate_blocks(strat, PAPER, [0, 5], seed=7)
    p2 = allocate_blocks(strat, PAPER, [2, 7], seed=7)
    assert len(np.unique(p1.endpoints)) == 128
    assert not np.intersect1d(p1.endpoints, p2.endpoints).size


def test_allocate_blocks_validates():
    with pytest.raises(ValueError):
        allocate_blocks("row", PAPER, [])
    with pytest.raises(ValueError):
        allocate_blocks("row", PAPER, [0, 0])
    with pytest.raises(ValueError):
        allocate_blocks("row", PAPER, [8])
    with pytest.raises(ValueError):
        allocate_blocks("row", PAPER, [0], size=65)


# ------------------------------------------------------------------- ledger
@pytest.mark.parametrize("strat", STRATS)
def test_ledger_fills_machine_disjoint(strat):
    led = BlockLedger(SMALL, strategy=strat)
    for _ in range(SMALL.n):
        led.place(1)
    led.check_conservation()
    assert led.capacity() == 0
    with pytest.raises(RuntimeError):
        led.place(1)


def test_ledger_policies_and_scatter():
    led = BlockLedger(SMALL, strategy="row", policy="first_fit")
    a = led.place(1)           # slot 0
    b = led.place(2)           # slots 1-2
    led.release(a.job_id)
    led.release(b.job_id)      # free: 0,1,2,3 contiguous
    c = led.place(2)           # first fit -> 0,1
    assert led.jobs[c.job_id].slots == (0, 1)
    led.place(1)               # slot 2
    led.release(c.job_id)      # free: 0,1 and 3 -> fragmented
    assert led.fragmentation() > 0
    d = led.place(3)           # no contiguous run of 3 -> scatter
    assert not led.jobs[d.job_id].contiguous
    led.check_conservation()


def test_ledger_best_fit_prefers_tight_run():
    led = BlockLedger(PAPER, strategy="row", policy="best_fit")
    holes = [led.place(1, job_id=100 + i) for i in range(8)]
    # free slots: a run of 2 (slots 1-2) and a run of 4 (slots 4-7)
    for jid in (101, 102, 104, 105, 106, 107):
        led.release(jid)
    part = led.place(2)
    assert led.jobs[part.job_id].slots == (1, 2)  # tightest run, not lowest-4
    del holes


def test_ledger_mixed_strategies_stay_disjoint():
    """Jobs placed under different strategies coexist because the slot
    views are derived from endpoint-level ground truth: a Rectangular job
    only sees rectangular blocks whose endpoints are actually free."""
    led = BlockLedger(PAPER, strategy="row")
    a = led.place(1)                           # row 0
    b = led.place(2, strategy="rectangular")   # rect blocks avoiding row 0
    assert led.jobs[b.job_id].slots == (2, 3)  # p=0,1 cover rows 0-1: held
    c = led.place(2)                           # row frame: rows 2-3 now held
    assert led.jobs[c.job_id].slots == (4, 5)
    led.check_conservation()  # raises on overlap
    assert not np.intersect1d(a.endpoints, b.endpoints).size
    assert not np.intersect1d(b.endpoints, c.endpoints).size


def test_ledger_failure_and_repair_cycle():
    led = BlockLedger(SMALL, strategy="row")
    part = led.place(1)
    dead = int(part.endpoints[0])
    affected = led.fail_endpoints([dead])
    assert affected == [part.job_id]
    led.check_conservation()
    # replace on the survivors: a different slot, disjoint from the dead ep
    newp = led.replace_job(part.job_id)
    assert dead not in newp.endpoints
    led.check_conservation()
    led.repair_endpoints([dead])
    led.check_conservation()
    assert led.free[dead]  # repaired and unheld -> back in the pool


if st is not None:
    @given(
        st.sampled_from(STRATS),
        st.lists(
            st.tuples(st.integers(1, 3), st.booleans()), min_size=1, max_size=24
        ),
        st.integers(0, 99),
    )
    @settings(max_examples=40, deadline=None)
    def test_ledger_conservation_property(strat, ops, seed):
        """Property: across random alloc/free cycles the ledger conserves
        endpoints and all placed partitions stay pairwise disjoint."""
        led = BlockLedger(SMALL, strategy=strat, seed=seed)
        placed = []
        for blocks, do_free in ops:
            if do_free and placed:
                led.release(placed.pop(0))
            else:
                try:
                    placed.append(led.place(blocks).job_id)
                except RuntimeError:
                    pass
            led.check_conservation()
            held = sum(len(led.jobs[j].slot_endpoints) for j in placed)
            assert led.capacity() + held == SMALL.num_endpoints
else:
    def test_ledger_conservation_property():
        pytest.importorskip("hypothesis")


# ------------------------------------------------------------- job streams
def test_stream_replay_bit_identical(tmp_path):
    a = poisson_stream(50, rate=0.5, seed=42)
    b = poisson_stream(50, rate=0.5, seed=42)
    assert a == b  # generation is deterministic in the seed
    path = str(tmp_path / "trace.csv")
    save_trace(a, path)
    assert load_trace(path) == a  # CSV round-trip is exact
    c = heavy_tailed_stream(50, seed=42)
    assert c == heavy_tailed_stream(50, seed=42)
    assert a != c


def test_scheduler_replay_bit_identical():
    """The whole scheduling run is deterministic given (stream, config)."""
    jobs = poisson_stream(80, rate=0.5, seed=9)
    runs = [
        OnlineScheduler(SMALL, strategy="diagonal").run_stream(jobs)
        for _ in range(2)
    ]
    assert [dataclasses.asdict(r) for r in runs[0].records] == \
           [dataclasses.asdict(r) for r in runs[1].records]
    assert runs[0].summary() == runs[1].summary()


if st is not None:
    @given(st.sampled_from(STRATS), st.integers(0, 999))
    @settings(max_examples=15, deadline=None)
    def test_scheduled_partitions_always_disjoint(strat, seed):
        """Property: at every scheduling event, placed partitions are
        pairwise disjoint and the ledger conserves endpoints (checked
        inside the loop via check_invariants)."""
        jobs = poisson_stream(
            30, rate=0.8, mean_service=4.0,
            block_weights=((1, 0.5), (2, 0.3), (3, 0.2)), seed=seed,
        )
        sched = OnlineScheduler(SMALL, strategy=strat, seed=seed)
        res = sched.run_stream(jobs, check_invariants=True)
        assert len(res.finished()) == 30
else:
    def test_scheduled_partitions_always_disjoint():
        pytest.importorskip("hypothesis")


# ---------------------------------------------------------------- event loop
def test_two_job_wait():
    """A job that cannot coexist with a running one waits exactly until
    the departure."""
    jobs = [
        Job(job_id=0, arrival=0.0, blocks=3, service=10.0),
        Job(job_id=1, arrival=1.0, blocks=2, service=5.0),
    ]
    res = OnlineScheduler(SMALL, strategy="diagonal").run_stream(jobs)
    r0, r1 = res.records
    assert r0.wait == 0.0
    assert r1.start == 10.0 and r1.wait == 9.0
    assert res.span == 15.0


def test_backfill_jumps_short_job_ahead():
    """EASY: a short small job backfills around a blocked big head job
    without delaying the head's reservation."""
    jobs = [
        Job(job_id=0, arrival=0.0, blocks=3, service=10.0),
        Job(job_id=1, arrival=1.0, blocks=4, service=5.0),   # blocked head
        Job(job_id=2, arrival=2.0, blocks=1, service=6.0),   # backfills
    ]
    res = OnlineScheduler(SMALL, strategy="row", backfill=True).run_stream(jobs)
    r = {x.job_id: x for x in res.records}
    assert r[2].start == 2.0          # fits the spare slot immediately
    assert r[1].start == 10.0         # head starts exactly at its shadow time
    no_bf = OnlineScheduler(SMALL, strategy="row", backfill=False).run_stream(jobs)
    r2 = {x.job_id: x for x in no_bf.records}
    assert r2[1].start == 10.0
    assert r2[2].start == 15.0        # FCFS: waits behind the whole-machine head


def test_backfill_does_not_delay_reservation():
    """A long backfill candidate that would consume the head's reserved
    slots is NOT started."""
    jobs = [
        Job(job_id=0, arrival=0.0, blocks=3, service=10.0),
        Job(job_id=1, arrival=1.0, blocks=4, service=5.0),    # blocked head
        Job(job_id=2, arrival=2.0, blocks=1, service=100.0),  # too long
    ]
    res = OnlineScheduler(SMALL, strategy="row").run_stream(jobs)
    r = {x.job_id: x for x in res.records}
    # the head needs every slot at its shadow time (t=10); job 2 outlives
    # the shadow and would steal one, so it must NOT be backfilled
    assert r[1].start == 10.0
    assert r[2].start == 15.0  # only after the whole-machine head departs


def test_failure_migration_and_requeue():
    """Failures re-place affected jobs (migration); when the survivors
    cannot host one, it is evicted and re-queued with remaining service."""
    jobs = [Job(job_id=0, arrival=0.0, blocks=2, service=20.0)]
    fail = FailureEvent(time=5.0, endpoints=(0,), repair_at=None)
    res = OnlineScheduler(SMALL, strategy="row").run_stream(
        jobs, failures=[fail], check_invariants=True
    )
    rec = res.records[0]
    assert rec.migrations == 1 and rec.requeues == 0
    assert rec.finish == 20.0  # migration is instantaneous (checkpoint model)

    # now kill a whole row's endpoints under every slot: job must requeue
    # until repair returns capacity
    big = [Job(job_id=0, arrival=0.0, blocks=4, service=20.0)]
    all_but_one_slot = tuple(range(16, 64))  # rows 1..3 of the n=4 machine
    ev = FailureEvent(time=5.0, endpoints=all_but_one_slot, repair_at=30.0)
    res = OnlineScheduler(SMALL, strategy="row").run_stream(
        big, failures=[ev], check_invariants=True
    )
    rec = res.records[0]
    assert rec.requeues == 1
    assert rec.finish == pytest.approx(45.0)  # 5 run + repair at 30 + 15 left


def test_oversized_job_rejected():
    with pytest.raises(ValueError):
        OnlineScheduler(SMALL).run_stream(
            [Job(job_id=0, arrival=0.0, blocks=5, service=1.0)]
        )


# --------------------------------------------------------- runtime drop-in
def test_fleet_runtime_accepts_block_ledger():
    """The ledger is a JobAllocator-compatible fleet allocator: repair and
    elastic shrink run through it, conserving endpoints throughout."""
    ledger = BlockLedger(PAPER, strategy="diagonal")
    rt = FleetRuntime((16, 16), ("data", "model"), strategy="diagonal",
                      allocator=ledger)
    assert rt.topo == PAPER
    dead = int(rt.placement.endpoints.reshape(-1)[0])
    ev = rt.fail([dead])
    assert ev["action"] == "reallocated"
    ledger.check_conservation()
    ev = rt.fail(np.arange(300))  # degrade -> elastic shrink
    assert "rescaled" in ev["action"]
    assert rt.healthy_devices() == 128
    ledger.check_conservation()


def test_ledger_seed_mutation_keeps_disjointness():
    """FleetRuntime's stochastic fallback mutates allocator.seed between
    placements; the slot-view cache must follow the seed or cached views
    disagree with what allocate_blocks actually places (overlap)."""
    led = BlockLedger(SMALL, strategy="random_switch", seed=0)
    a = led.place(1)
    led.seed = 1000  # what FleetRuntime._try_allocate does
    b = led.place(1)
    assert not np.intersect1d(a.endpoints, b.endpoints).size
    # partition endpoints must be exactly the held slot endpoints
    np.testing.assert_array_equal(
        np.sort(b.endpoints), np.sort(led.jobs[b.job_id].slot_endpoints)
    )
    led.check_conservation()


def test_shared_ledger_repair_spares_other_tenants():
    """A FleetRuntime repair on a shared ledger must only release the
    runtime's own job, never other tenants' allocations."""
    ledger = BlockLedger(SMALL, strategy="row")
    tenant = ledger.place(1, job_id=777)  # e.g. a stream job
    rt = FleetRuntime((3, 16), ("data", "model"), strategy="row",
                      allocator=ledger)
    dead = int(rt.placement.endpoints.reshape(-1)[0])
    ev = rt.fail([dead])
    assert ev["job_affected"]
    assert 777 in ledger.jobs  # the co-tenant survived the repair
    assert not ledger.free[tenant.endpoints].any()  # still held
    ledger.check_conservation()
    ledger.release(777)  # and its lifecycle still works


def test_ledger_topo_mismatch_rejected():
    with pytest.raises(ValueError):
        FleetRuntime((8, 8), ("data", "model"), topo=SMALL,
                     allocator=BlockLedger(PAPER))


def test_ledger_refuses_q3_until_its_slot_views_are_generalised():
    with pytest.raises(ValueError, match="q=3"):
        BlockLedger(HyperX(n=4, q=3))


# ------------------------------------------------------- interference bridge
def _small_stream_snapshots(strategies, num_jobs=200):
    jobs = poisson_stream(
        num_jobs, rate=0.45, mean_service=8.0,
        block_weights=((1, 0.6), (2, 0.4)), seed=7,
    )
    out = {}
    for strat in strategies:
        res = OnlineScheduler(SMALL, strategy=strat).run_stream(jobs)
        assert len(res.finished()) == num_jobs
        out[strat] = res.snapshots
    return out


def test_200_job_stream_all_strategies_end_to_end():
    """The acceptance scenario at test scale: a 200-job stream runs end to
    end for all 7 strategies and every summary emits the full metric set."""
    snaps = _small_stream_snapshots(STRATS)
    assert set(snaps) == set(STRATS)
    for strat in STRATS:
        wl = snapshot_workload(SMALL, pick_snapshots(snaps[strat], 1)[0])
        assert wl.R >= 32  # at least two co-resident jobs lowered


def test_snapshot_grid_one_compile_per_bucket():
    """Trace-counter pin: a strategy x snapshot x seed grid through the
    bridge costs one XLA trace and one device call per shape bucket.
    (The bridge reports deltas, because get_engine memoizes engines
    across the session.)"""
    from repro.core.engine import get_engine

    snaps = _small_stream_snapshots(("row", "diagonal", "full_spread"))
    selected = {k: pick_snapshots(v, 2) for k, v in snaps.items()}
    rows, stats = evaluate_snapshots(
        SMALL, selected, seeds=(0, 1), horizon=20_000
    )
    # memoised: one engine per configuration
    assert stats["engine"] is get_engine(SMALL, mode="omniwar", num_pools=1)
    buckets = {r["bucket"] for r in rows}
    assert stats["traces"] == len(buckets)
    assert stats["device_calls"] == len(buckets)
    assert len(rows) == 3 * 2 * 2  # strategies x snapshots x seeds
    assert all(r["completed"] for r in rows)
