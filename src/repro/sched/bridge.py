"""Interference bridge: co-resident snapshots through the batched SimEngine.

A :class:`~repro.sched.scheduler.Snapshot` freezes the set of jobs sharing
the machine at one scheduling event.  This module lowers snapshots through
the declarative scenario layer (:mod:`repro.traffic.scenario`) to
:class:`~repro.traffic.workload.Workload`s (each job runs its registry
kernel on its *actually placed* partition) and executes the whole
strategy x snapshot x seed grid through ``SimEngine.run_grid`` — the
engine groups workloads by shape bucket internally, so the entire grid
costs **one compilation and one device call per shape bucket** regardless
of how many strategies, snapshots, or seeds it spans (the trace-counter
test pins this).

Fault-aware routing closes the loop with the scheduler's failure churn: a
snapshot records the endpoints the ledger had marked failed, and
``churn_faults=True`` lowers them to link-fault masks
(:func:`repro.route.faults.faults_from_endpoints` — failure domains are
co-packaged, so a dead node takes an adjacent cable with it).  Masks ride
in the workload tables, so fault scenarios batch like any other axis.
:func:`evaluate_snapshots_by_routing` runs the same snapshot grid once per
registered routing policy (one engine — one compile set — per policy).
"""

from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np

from repro.core.engine import SimResult, get_engine
from repro.core.engine.workload_tables import shape_bucket
from repro.core.hyperx import HyperX
from repro.obs import trace as obs_trace
from repro.route import apply_faults, faults_from_endpoints
from repro.sched.scheduler import Snapshot
from repro.traffic import AppSpec, ScenarioSpec, build_workload, get_pattern
from repro.traffic.workload import Workload


def snapshot_workload(
    topo: HyperX,
    snap: Snapshot,
    fabric_partitioning: str = "shared",
    churn_faults: bool = False,
) -> Workload:
    """Lower one snapshot: every co-resident job's kernel on its partition.

    Job kernels resolve through the traffic-pattern registry, so any
    registered pattern name (including phased ``"a+b"`` compositions) is
    a valid job kernel.  ``churn_faults`` additionally lowers the
    snapshot's failed endpoints (the scheduler's churn, frozen at
    snapshot time) into a link-fault mask the routing policies must
    steer around.
    """
    apps = []
    for job_id, kernel, part in snap.jobs:
        phases = kernel.split("+")
        for name in phases:
            try:
                get_pattern(name)
            except ValueError as e:
                raise KeyError(f"job {job_id}: {e}") from None
        apps.append(AppSpec(phases=tuple(phases), placement=part))
    wl = build_workload(topo, ScenarioSpec(
        apps=tuple(apps), fabric_partitioning=fabric_partitioning,
    ))
    if churn_faults and snap.failed_endpoints:
        wl = apply_faults(
            wl, faults_from_endpoints(topo, snap.failed_endpoints)
        )
    return wl


def pick_snapshots(
    snapshots: Sequence[Snapshot],
    max_snapshots: int,
    min_jobs: int = 2,
) -> list[Snapshot]:
    """Evenly sample up to ``max_snapshots`` snapshots with >= min_jobs."""
    eligible = [s for s in snapshots if s.num_jobs >= min_jobs]
    if len(eligible) <= max_snapshots:
        return eligible
    idx = np.linspace(0, len(eligible) - 1, max_snapshots).round().astype(int)
    return [eligible[i] for i in sorted(set(idx.tolist()))]


def evaluate_snapshots(
    topo: HyperX,
    snapshots_by_key: Mapping[str, Sequence[Snapshot]],
    seeds: Sequence[int] = (0,),
    horizon: int = 60_000,
    mode: str = "omniwar",
    fabric_partitioning: str = "shared",
    churn_faults: bool = False,
) -> tuple[list[dict], dict]:
    """Evaluate snapshot grids for many strategies in batched device calls.

    ``snapshots_by_key`` maps a label (typically the strategy name) to its
    snapshots.  ALL workloads across all keys go through one engine and one
    ``run_grid`` call, so same-shape-bucket scenarios of different
    strategies share both the compilation and the dispatch.

    Returns (rows, stats): one row per (key, snapshot, seed) with the
    SimResult metrics plus co-residency context; ``stats`` holds the
    ``engine`` plus the ``traces`` / ``device_calls`` this evaluation
    *added* (deltas — engines are memoised per config and may already
    carry counts from earlier sweeps).
    """
    keys, snaps, workloads = [], [], []
    for key, group in snapshots_by_key.items():
        for snap in group:
            wl = snapshot_workload(
                topo, snap, fabric_partitioning, churn_faults=churn_faults
            )
            keys.append(key)
            snaps.append(snap)
            workloads.append(wl)
    if not workloads:
        return [], {"engine": None, "traces": 0, "device_calls": 0}
    num_pools = {wl.num_pools for wl in workloads}
    if len(num_pools) != 1:
        raise ValueError(
            f"snapshots lower to mixed VC pool counts {sorted(num_pools)}; "
            "evaluate per fabric_partitioning mode"
        )
    engine = get_engine(topo, mode=mode, num_pools=num_pools.pop())
    traces0, calls0 = engine.trace_count, engine.device_calls
    # device-sharded lanes: on a multi-device host the snapshot x seed grid
    # splits across devices; on one device this is the nested-vmap call
    with obs_trace.span("bridge.evaluate_snapshots", mode=mode,
                        workloads=len(workloads), seeds=len(seeds)):
        per_wl = engine.run_grid(workloads, seeds=seeds, horizon=horizon)
    rows = []
    for key, snap, wl, per_seed in zip(keys, snaps, workloads, per_wl):
        bucket = shape_bucket(wl.R, wl.T, wl.maxd)
        for seed, res in zip(seeds, per_seed):
            assert isinstance(res, SimResult)
            rows.append({
                "key": key,
                "routing": mode,
                "time": round(snap.time, 3),
                "co_jobs": snap.num_jobs,
                "failed_eps": len(snap.failed_endpoints) if churn_faults else 0,
                "ranks": wl.R,
                "bucket": "x".join(map(str, bucket)),
                "seed": int(seed),
                "makespan": res.makespan if res.completed else -1,
                "avg_latency": round(res.avg_latency, 3),
                "avg_hops": round(res.avg_hops, 4),
                "completed": res.completed,
            })
    return rows, {
        "engine": engine,
        "traces": engine.trace_count - traces0,
        "device_calls": engine.device_calls - calls0,
    }


def evaluate_snapshots_by_routing(
    topo: HyperX,
    snapshots_by_key: Mapping[str, Sequence[Snapshot]],
    modes: Sequence[str] = ("min", "omniwar", "val", "ugal"),
    seeds: Sequence[int] = (0,),
    horizon: int = 60_000,
    fabric_partitioning: str = "shared",
    churn_faults: bool = True,
) -> tuple[list[dict], dict]:
    """The snapshot interference grid, once per routing policy.

    Each policy is its own engine (its VC budget changes the queue
    space), so the cost is one compile set per mode — within a mode the
    whole strategy x snapshot x seed grid still batches per shape
    bucket.  ``churn_faults`` (default on) sources link faults from each
    snapshot's recorded failure churn, making this the
    routing x strategy x fault grid of DESIGN.md §Routing.

    Returns (rows, stats_by_mode): rows carry a ``routing`` column;
    ``stats_by_mode[mode]`` is the per-mode stats dict of
    :func:`evaluate_snapshots`.
    """
    rows: list[dict] = []
    stats_by_mode: dict[str, dict] = {}
    for mode in modes:
        mode_rows, stats = evaluate_snapshots(
            topo, snapshots_by_key, seeds=seeds, horizon=horizon,
            mode=mode, fabric_partitioning=fabric_partitioning,
            churn_faults=churn_faults,
        )
        rows.extend(mode_rows)
        stats_by_mode[mode] = stats
    return rows, stats_by_mode
