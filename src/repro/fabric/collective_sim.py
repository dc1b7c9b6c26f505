"""Simulate mesh collectives on the HyperX fabric — cost-model validation.

The CollectiveModel (collective_model.py) *prices* collectives analytically
from partition bandwidth.  This module grounds that price: it expresses a
mesh-axis collective as a step-table workload (ring all-reduce = the
paper's neighbour-exchange; all-to-all = the paper's All-to-All kernel)
over the placement's actual endpoints, runs it through the cycle-level
simulator engine, and returns measured makespans.  Benchmarks compare
analytic vs simulated ordering across allocation strategies — closing the
loop between the paper's simulator evidence and the framework's launcher
policy.

Strategy comparisons run through ``SimEngine.run_grid``: every strategy's
workload shares one shape bucket, so the whole comparison is a single
compilation and one device call — sharded across all local devices when
the host has more than one.
"""

from __future__ import annotations

import numpy as np

from repro.core.allocation import Partition
from repro.core.engine import get_engine
from repro.core.hyperx import HyperX
from repro.fabric.placement import HyperXPlacement
from repro.traffic import AppSpec, PhaseSpec, ScenarioSpec, build_workload
from repro.traffic.workload import Workload

# registry patterns expressing each mesh-axis collective (the former
# private _ring_allreduce_app/_alltoall_app builders, deduplicated onto
# repro.traffic.patterns — parity-pinned in tests/test_traffic_patterns.py)
COLLECTIVE_PHASES = {
    "all_reduce": PhaseSpec("ring_allreduce", {"packets_per_step": 4}),
    "all_to_all": PhaseSpec("all_to_all"),
}


def _axis_groups(placement: HyperXPlacement, axis: str,
                 num_groups: int | None) -> np.ndarray:
    groups = placement.axis_groups(axis)
    return groups if num_groups is None else groups[:num_groups]


def _result_row(placement: HyperXPlacement, axis: str, kind: str,
                num_groups: int | None, res) -> dict:
    groups = _axis_groups(placement, axis, num_groups)
    return {
        "strategy": placement.strategy, "axis": axis, "kind": kind,
        "groups": len(groups), "group_size": groups.shape[1],
        "makespan": res.makespan if res.completed else -1,
        "completed": res.completed,
        "avg_hops": round(res.avg_hops, 3),
    }


def axis_collective_workload(
    placement: HyperXPlacement,
    axis: str,
    kind: str = "all_reduce",
    num_groups: int | None = None,
) -> Workload:
    """Express ``kind`` over (a subset of) the axis groups as one workload.

    All groups run simultaneously — exactly how a mesh collective executes —
    so inter-group link contention is captured, which is what
    distinguishes allocation strategies (the paper's Lesson 2/3).  The
    collective itself is a registry pattern (``COLLECTIVE_PHASES``), so
    any registered kernel can be dropped in per axis.
    """
    topo: HyperX = placement.topo
    groups = _axis_groups(placement, axis, num_groups)
    k = groups.shape[1]
    phase = COLLECTIVE_PHASES[kind]
    apps = []
    for g in groups:
        part = Partition(
            strategy=placement.strategy, topo=topo, job_id=-1, size=k,
            endpoints=np.asarray(g, dtype=np.int64),
            switches=np.unique(np.asarray(g) // topo.concentration),
        )
        apps.append(AppSpec(phases=phase, placement=part, ranks=k))
    return build_workload(topo, ScenarioSpec(apps=tuple(apps)))


def simulate_axis_collective(
    placement: HyperXPlacement,
    axis: str,
    kind: str = "all_reduce",
    num_groups: int | None = None,
    seed: int = 0,
    horizon: int = 120_000,
    mode: str = "omniwar",
    link_ok=None,
) -> dict:
    """Run ``kind`` concurrently over (a subset of) the axis groups.

    ``mode`` selects any registered routing policy; ``link_ok`` optionally
    injects a link-fault mask (see :mod:`repro.route.faults`).
    """
    wl = axis_collective_workload(placement, axis, kind, num_groups)
    if link_ok is not None:
        from repro.route import apply_faults

        wl = apply_faults(wl, link_ok)
    engine = get_engine(placement.topo, mode=mode, num_pools=wl.num_pools)
    res = engine.run(wl, seed=seed, horizon=horizon)
    return _result_row(placement, axis, kind, num_groups, res)


def compare_strategies_simulated(
    mesh_shape=(16, 16),
    axis_names=("data", "model"),
    axis: str = "model",
    kind: str = "all_to_all",
    strategies=("row", "diagonal", "full_spread", "rectangular",
                "l_shape", "random_endpoint", "random_switch"),
    num_groups: int | None = 8,
    seed: int = 0,
    mode: str = "omniwar",
) -> list[dict]:
    """Measured makespan of one mesh collective per allocation strategy.

    All strategies execute as one batched ``run_grid`` device call (their
    workloads share a shape bucket).  ``mode`` selects the routing policy.
    """
    from repro.fabric.placement import place_job

    placements = [place_job(s, mesh_shape, axis_names, seed=seed)
                  for s in strategies]
    wls = [axis_collective_workload(p, axis, kind, num_groups)
           for p in placements]
    engine = get_engine(placements[0].topo, mode=mode,
                        num_pools=wls[0].num_pools)
    # run_grid: strategy lanes shard across devices when the host has them
    per_wl = engine.run_grid(wls, seeds=[seed], horizon=120_000)
    out = [_result_row(p, axis, kind, num_groups, res[0])
           for p, res in zip(placements, per_wl)]
    out.sort(key=lambda d: d["makespan"] if d["makespan"] > 0 else 10**9)
    return out
