"""Declarative scenarios: pattern x placement x background x phases.

A :class:`ScenarioSpec` names *what runs where* — each app an ordered
list of registry phases on a placement (an explicit
:class:`~repro.core.allocation.Partition` or an allocation-strategy
name), plus optional background noise and a link-fault mask — and
:func:`build_workload` lowers it through the registry and
:func:`~repro.traffic.workload.compose_workload` into the single
machine-level :class:`~repro.traffic.workload.Workload` every consumer
(engine, sched bridge, collective sim, benchmarks) executes.

Seeds: ``ScenarioSpec.seed`` derives a per-app seed (``seed + app
index``) that is threaded only into *seeded* patterns and only when the
app does not fix its own — so two random-permutation apps in one
scenario draw different permutations by default, while unseeded kernels
stay bit-identical to their direct builders.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import numpy as np

from repro.core.allocation import Partition, allocate_partition
from repro.core.hyperx import HyperX
from repro.obs import trace as obs_trace
from repro.traffic.base import AppTraffic, build_phases, get_pattern
from repro.traffic.workload import Workload, background_noise, compose_workload


@dataclasses.dataclass(frozen=True)
class PhaseSpec:
    """One phase: a registered pattern name + builder params."""

    pattern: str
    params: Mapping[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class AppSpec:
    """One application: ordered phases on a placement.

    ``phases`` accepts a pattern name, a :class:`PhaseSpec`, or a
    sequence of either (run in order, see
    :func:`~repro.traffic.base.concat_phases` for the window semantics).
    ``placement`` is an explicit Partition or an allocation-strategy
    name; strategy names are resolved against the scenario's topology
    with a per-strategy job counter, so two ``"row"`` apps land on
    consecutive base blocks.  ``ranks`` defaults to the partition size
    (or one base block n^2 for strategy names).
    """

    phases: Any  # str | PhaseSpec | Sequence[str | PhaseSpec]
    placement: Partition | str
    ranks: int | None = None
    window: int | None = None
    seed: int | None = None

    def phase_list(self) -> tuple[PhaseSpec, ...]:
        ph = self.phases
        if isinstance(ph, (str, PhaseSpec)):
            ph = (ph,)
        return tuple(
            PhaseSpec(p) if isinstance(p, str) else p for p in ph
        )


@dataclasses.dataclass(frozen=True)
class BackgroundSpec:
    """Background noise over the machine's free endpoints.

    ``endpoints`` overrides the default choice (everything no target app
    occupies).  The pattern must accept a ``packets`` parameter.
    """

    pattern: str = "random_permutation"
    packets: int = 1
    seed: int | None = None
    endpoints: np.ndarray | None = None


@dataclasses.dataclass(frozen=True)
class ScenarioSpec:
    """A full machine scenario, declaratively."""

    apps: Sequence[AppSpec]
    background: BackgroundSpec | None = None
    fabric_partitioning: str = "shared"
    warmup: int = 0
    link_ok: np.ndarray | None = None
    # optional repro.resil.epochs.FaultSchedule: time-varying fault
    # epochs lowered into the engine tables (ANDed with link_ok)
    fault_schedule: object | None = None
    seed: int = 0


def _resolve_placement(
    topo: HyperX,
    spec: AppSpec,
    strategy_counts: dict[str, int],
) -> Partition:
    if isinstance(spec.placement, Partition):
        return spec.placement
    job_id = strategy_counts.get(spec.placement, 0)
    strategy_counts[spec.placement] = job_id + 1
    with obs_trace.stage("alloc.place", strategy=spec.placement,
                         q=topo.q) as note:
        part = allocate_partition(spec.placement, topo, job_id,
                                  size=spec.ranks)
        note(ranks=part.size, switches=len(part.switches))
    return part


def build_app(spec: AppSpec, part: Partition, default_seed: int) -> AppTraffic:
    """Lower one AppSpec on its resolved partition to a step table."""
    k = spec.ranks if spec.ranks is not None else part.size
    seed = default_seed if spec.seed is None else spec.seed
    phases = [(p.pattern, p.params) for p in spec.phase_list()]
    return build_phases(phases, k, seed=seed, window=spec.window)


def build_workload(topo: HyperX, spec: ScenarioSpec) -> Workload:
    """Lower a ScenarioSpec to the one machine Workload it describes."""
    if not spec.apps:
        raise ValueError("scenario has no apps")
    strategy_counts: dict[str, int] = {}
    apps: list[tuple[AppTraffic, Partition]] = []
    for i, a in enumerate(spec.apps):
        part = _resolve_placement(topo, a, strategy_counts)
        apps.append((build_app(a, part, default_seed=spec.seed + i), part))

    backgrounds: list[tuple[AppTraffic, Partition]] = []
    if spec.background is not None:
        bg = spec.background
        get_pattern(bg.pattern)  # fail fast with the registered list
        if bg.endpoints is not None:
            free = np.asarray(bg.endpoints, dtype=np.int64)
        else:
            used = np.concatenate(
                [part.endpoints[: app.k] for app, part in apps]
            )
            free = np.setdiff1d(np.arange(topo.num_endpoints), used)
        if len(free) == 0:
            raise ValueError("no free endpoints left for background noise")
        bg_seed = bg.seed if bg.seed is not None else spec.seed + 99
        backgrounds.append(background_noise(
            topo, free, packets=bg.packets, seed=bg_seed, pattern=bg.pattern,
        ))

    return compose_workload(
        topo, apps, background=backgrounds,
        fabric_partitioning=spec.fabric_partitioning,
        warmup=spec.warmup, link_ok=spec.link_ok,
        fault_schedule=spec.fault_schedule,
    )
