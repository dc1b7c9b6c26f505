"""Shared helpers for the benchmark suite (one module per paper artifact).

The simulation-backed benchmarks build their scenario grids as *workloads
first*, then execute them through :func:`sweep`, which groups same-config
scenarios and dispatches each group as **one** vmapped device call via
``SimEngine.run_grid`` — a strategy grid that used to be a serial Python
loop of per-scenario compiles is now one compile + one call per shape
bucket.

Module-level knobs set by ``benchmarks.run``:

  * ``NUM_SEEDS`` — every scenario is fanned across this many seeds (the
    seed axis rides in the same batched call); rows report means over
    completed seeds;
  * ``CSV_DIR``  — when set, :func:`emit` also writes each table to
    ``<CSV_DIR>/<name>.csv`` so perf trajectories land in versionable
    files.
"""

from __future__ import annotations

import csv
import io
import os
import re
import sys

import numpy as np

from repro.core.hyperx import HyperX
from repro.core.allocation import allocate_partition, machine_partitions
from repro.core.engine import SimResult, get_engine
from repro.obs import TelemetrySpec
from repro.obs import trace as obs_trace
from repro.traffic import (
    AppSpec,
    BackgroundSpec,
    PhaseSpec,
    ScenarioSpec,
    Workload,
    build_workload,
    get_pattern,
)

STRATEGIES = [
    "row", "diagonal", "full_spread", "rectangular", "l_shape",
    "random_endpoint", "random_switch",
]

PAPER_TOPO = HyperX(n=8, q=2)

NUM_SEEDS = 1          # set by benchmarks.run --seeds
CSV_DIR: str | None = None  # set by benchmarks.run --csv
QUICK = True           # set by benchmarks.run --quick/--full
ROUTING = "omniwar"    # set by benchmarks.run --routing (any registered policy)
PATTERN = "all_to_all"  # set by benchmarks.run --pattern (any registered pattern)


def resolve_routing(mode: str | None = None) -> str:
    """Routing-policy switch, same contract as :func:`resolve_quick`:
    ``benchmarks.run --routing`` sets :data:`ROUTING` once and the
    simulation-backed modules resolve through it unless a caller
    overrides explicitly."""
    return ROUTING if mode is None else mode


def resolve_pattern(kind: str | None = None) -> str:
    """Traffic-pattern switch, same contract as :func:`resolve_routing`:
    ``benchmarks.run --pattern`` sets :data:`PATTERN` once and the
    pattern-parameterized modules (e.g. ``traffic_grid``) resolve
    through it unless a caller overrides explicitly."""
    return PATTERN if kind is None else kind


def resolve_quick(quick) -> bool:
    """Shared CI-sizing switch.  Benchmark modules take ``run(quick=None)``
    and resolve through this, so :data:`QUICK` (set once by
    ``benchmarks.run``) is the single source of truth unless a caller
    overrides explicitly — no more half-quick/half-full grids."""
    return QUICK if quick is None else bool(quick)


def render_csv(rows: list[dict]) -> str:
    """Render dict rows as CSV text (header from the first row's keys)."""
    out = io.StringIO()
    w = csv.DictWriter(out, fieldnames=list(rows[0].keys()))
    w.writeheader()
    for r in rows:
        w.writerow(r)
    return out.getvalue()


def write_grid_csv(rows: list[dict], name: str,
                   csv_dir: str | None = None) -> str:
    """The one CSV-writing path for every grid benchmark.

    Prints the table with a '# <name>' header (the harness contract) and,
    when ``csv_dir`` is set, also writes it to ``<csv_dir>/<slug>.csv``.
    Returns the rendered CSV text.  ``emit`` delegates here with the
    suite-wide ``CSV_DIR``; call this directly to target another dir.
    """
    if not rows:
        print(f"# {name}: no rows")
        return ""
    text = render_csv(rows)
    print(f"# {name}")
    sys.stdout.write(text)
    sys.stdout.flush()
    if csv_dir:
        os.makedirs(csv_dir, exist_ok=True)
        slug = re.sub(r"[^A-Za-z0-9_.-]+", "_", name.split(" ")[0]).strip("_")
        with open(os.path.join(csv_dir, f"{slug}.csv"), "w", newline="") as f:
            f.write(text)
    return text


def emit(rows: list[dict], name: str):
    """Print rows as CSV with a '# <name>' header (the harness contract).

    When ``CSV_DIR`` is set the same table is also written to
    ``<CSV_DIR>/<slug>.csv``.
    """
    write_grid_csv(rows, name, csv_dir=CSV_DIR)


# ------------------------------------------------------------------ traffic
def pattern_phase(kind: str) -> PhaseSpec:
    """Registry phase for ``kind`` with the suite's historical params
    (switch-permutation groups sized to the paper machine's switches)."""
    if kind == "random_switch_permutation":
        return PhaseSpec(kind, {"group": PAPER_TOPO.n})
    return PhaseSpec(kind)


def kernel_app(kind: str, k: int, seed: int = 0):
    """One registry pattern over ``k`` ranks (kept for spot checks)."""
    phase = pattern_phase(kind)
    return get_pattern(kind).build(k, seed=seed, **dict(phase.params))


# ------------------------------------------------------- workload builders
def escalation_workload(strategy: str, kind: str, replicas: int, k: int = 64,
                        seed: int = 0) -> Workload:
    """k-rank app x replicas on the paper machine; all replicas targets."""
    per_job = k
    parts = machine_partitions(strategy, PAPER_TOPO,
                               num_jobs=512 // per_job, job_size=per_job)
    spec = ScenarioSpec(apps=tuple(
        AppSpec(phases=pattern_phase(kind), placement=parts[j], ranks=k,
                seed=seed + j)
        for j in range(replicas)
    ))
    return build_workload(PAPER_TOPO, spec)


def interference_workload(strategy: str, kind: str, k: int = 64,
                          fabric: str = "shared", with_bg: bool = True,
                          warmup: int = 400, seed: int = 0) -> Workload:
    """One target job (+ optional random-permutation background)."""
    part = allocate_partition(strategy, PAPER_TOPO, 0, size=k)
    spec = ScenarioSpec(
        apps=(AppSpec(phases=pattern_phase(kind), placement=part, ranks=k,
                      seed=seed),),
        background=BackgroundSpec(seed=seed + 99) if with_bg else None,
        fabric_partitioning=fabric,
        warmup=warmup if with_bg else 0,
    )
    return build_workload(PAPER_TOPO, spec)


def phased_workload(strategy: str, kinds, k: int = 64, seed: int = 0,
                    window: int | None = None) -> Workload:
    """One job running an ordered phase list (e.g. stencil + all-reduce)."""
    part = allocate_partition(strategy, PAPER_TOPO, 0, size=k)
    spec = ScenarioSpec(apps=(
        AppSpec(phases=tuple(pattern_phase(kd) for kd in kinds),
                placement=part, ranks=k, seed=seed, window=window),
    ))
    return build_workload(PAPER_TOPO, spec)


# --------------------------------------------------------- batched execution
def sweep(workloads: list[Workload], mode: str | None = None,
          horizon: int = 60_000, seeds=None,
          topo: HyperX = PAPER_TOPO) -> list[list[SimResult]]:
    """Run every (workload, seed) pair batched; returns [workload][seed].

    Workloads are grouped by engine configuration (pool count) and shape
    bucket; each group executes through ``SimEngine.run_grid``, which
    flattens the grid into device-sharded lanes (``shard_map`` across
    all local devices; the nested-vmap call on one device) — so
    every grid benchmark gains multi-device execution with no changes.
    The routing policy defaults to the suite-wide ``--routing`` choice.
    """
    mode = resolve_routing(mode)
    if seeds is None:
        seeds = list(range(NUM_SEEDS))
    seeds = list(seeds)
    by_pools: dict[int, list[int]] = {}
    for i, wl in enumerate(workloads):
        by_pools.setdefault(wl.num_pools, []).append(i)
    results: list[list[SimResult] | None] = [None] * len(workloads)
    with obs_trace.span("bench.sweep", mode=mode,
                        workloads=len(workloads), seeds=len(seeds)):
        for num_pools, idxs in by_pools.items():
            engine = get_engine(topo, mode=mode, num_pools=num_pools)
            per_wl = engine.run_grid(
                [workloads[i] for i in idxs], seeds=seeds, horizon=horizon
            )
            for i, res in zip(idxs, per_wl):
                results[i] = res
    return results  # type: ignore[return-value]


def telemetry_probe(strategies=("diagonal", "rectangular"),
                    kind: str | None = None, k: int = 64,
                    horizon: int = 60_000, seed: int = 0,
                    spec: TelemetrySpec | None = None) -> dict:
    """Run a small telemetry-enabled grid and log one ``sim.telemetry``
    event per strategy.

    This is the suite's traced-run payload (``benchmarks.run --trace``):
    the per-link utilization / occupancy / latency series behind the
    report generator's heatmap and latency tables.  Telemetry joins the
    engine compile key, so these engines are separate cache entries from
    the untraced sweeps and leave their compile counts untouched.
    Returns ``{strategy: Telemetry}``.
    """
    kind = resolve_pattern(kind)
    spec = spec or TelemetrySpec()
    out = {}
    for strategy in strategies:
        wl = interference_workload(strategy, kind, k=k, with_bg=False,
                                   warmup=0, seed=seed)
        engine = get_engine(PAPER_TOPO, mode=resolve_routing(None),
                            num_pools=wl.num_pools, telemetry=spec)
        with obs_trace.span("bench.telemetry_probe", strategy=strategy,
                            kernel=kind):
            res = engine.run(wl, seed=seed, horizon=horizon)
        obs_trace.log_telemetry(strategy, res.telemetry, kernel=kind, k=k)
        out[strategy] = res.telemetry
    return out


def summarize(per_seed: list[SimResult]) -> dict:
    """Mean metrics over completed seeds (-1 when any seed hit the horizon)."""
    done = [r for r in per_seed if r.completed]
    completed = len(done) == len(per_seed)
    if not done:
        return {"makespan": -1, "makespan_cycles": -1, "avg_latency": -1.0,
                "avg_hops": -1.0, "completed": False, "seeds": len(per_seed)}
    return {
        "makespan": round(float(np.mean([r.makespan for r in done])), 1)
        if completed else -1,
        "makespan_cycles": round(
            float(np.mean([r.makespan_cycles for r in done])), 1)
        if completed else -1,
        "avg_latency": round(float(np.mean([r.avg_latency for r in done])), 2),
        "avg_hops": round(float(np.mean([r.avg_hops for r in done])), 3),
        "completed": completed,
        "seeds": len(per_seed),
    }


# -------------------------------------------- single-scenario conveniences
def escalation_makespan(strategy: str, kind: str, replicas: int, k: int = 64,
                        mode: str | None = None, seed: int = 0,
                        horizon: int = 60000) -> dict:
    """One escalation scenario (kept for spot checks; sweeps use sweep())."""
    wl = escalation_workload(strategy, kind, replicas, k=k, seed=seed)
    res = get_engine(PAPER_TOPO, mode=resolve_routing(mode),
                     num_pools=wl.num_pools).run(
        wl, seed=seed, horizon=horizon)
    return {
        "strategy": strategy, "kernel": kind, "replicas": replicas, "k": k,
        "makespan": res.makespan if res.completed else -1,
        "makespan_cycles": res.makespan_cycles if res.completed else -1,
        "avg_latency": round(res.avg_latency, 2),
        "avg_hops": round(res.avg_hops, 3),
        "completed": res.completed,
    }


def interference_makespan(strategy: str, kind: str, k: int = 64,
                          fabric: str = "shared", with_bg: bool = True,
                          warmup: int = 400, seed: int = 0,
                          horizon: int = 80000) -> dict:
    wl = interference_workload(strategy, kind, k=k, fabric=fabric,
                               with_bg=with_bg, warmup=warmup, seed=seed)
    res = get_engine(PAPER_TOPO, mode=resolve_routing(),
                     num_pools=wl.num_pools).run(
        wl, seed=seed, horizon=horizon)
    return {
        "strategy": strategy, "kernel": kind, "k": k, "fabric": fabric,
        "bg": with_bg,
        "makespan": res.makespan if res.completed else -1,
        "completed": res.completed,
    }
