"""Online job-stream scheduling on the paper machine (Section 7 "lessons
learned", operationalized).

Scenarios (all seven allocation strategies see the SAME deterministic
stream, so per-strategy deltas are placement effects, not arrival noise):

  * ``poisson``    — Poisson arrivals, exponential service, ~85% offered
    load: queueing + fragmentation under light-tailed churn;
  * ``heavy_tail`` — bounded-Pareto service times (full mode only);
  * ``churn``      — the poisson stream plus endpoint failures/repairs:
    the SAME physical failures knock out different block slots under
    different strategies, so utilization/wait/migrations finally diverge
    per strategy (on a healthy machine slot dynamics are strategy-blind
    and only the realized-PB/locality columns differ).

Interference: co-resident snapshots from the poisson run are lowered to
machine workloads and the whole strategy x snapshot x seed grid executes
through ``SimEngine.run_grid`` — one compile + one device call per
shape bucket (the compile-stats table reports the counters).
"""

from __future__ import annotations

import numpy as np

from benchmarks import common
from benchmarks.common import PAPER_TOPO, STRATEGIES, emit, resolve_quick

from repro.core.engine.workload_tables import shape_bucket
from repro.sched import (
    FailureEvent,
    OnlineScheduler,
    evaluate_snapshots,
    evaluate_snapshots_by_routing,
    heavy_tailed_stream,
    poisson_stream,
    snapshot_workload,
)
from repro.sched.bridge import pick_snapshots

NUM_JOBS = 240  # 200+ job stream (the acceptance scenario) even in quick


def _snap_bucket(topo, snap):
    """Shape bucket a snapshot's workload lands in, from the real lowering
    (cheap numpy — no device tables are built)."""
    wl = snapshot_workload(topo, snap)
    return shape_bucket(wl.R, wl.T, wl.maxd)


def _select_snapshots(topo, per_strategy: dict, per_strat_count: int,
                      quick: bool):
    """Sample snapshots per strategy; in quick mode restrict to the most
    common shape bucket so CI pays for at most one compilation."""
    if quick:
        eligible = {k: [s for s in snaps if s.num_jobs >= 2]
                    for k, snaps in per_strategy.items()}
        buckets = {k: [_snap_bucket(topo, s) for s in snaps]
                   for k, snaps in eligible.items()}
        counts: dict = {}
        for bs in buckets.values():
            for b in bs:
                counts[b] = counts.get(b, 0) + 1
        if not counts:
            return {k: [] for k in per_strategy}
        target = max(counts, key=counts.get)
        per_strategy = {
            k: [s for s, b in zip(eligible[k], buckets[k]) if b == target]
            for k in per_strategy
        }
    return {
        k: pick_snapshots(snaps, per_strat_count)
        for k, snaps in per_strategy.items()
    }


def run(quick=None):
    quick = resolve_quick(quick)
    topo = PAPER_TOPO
    # offered load ~ rate * mean_service * E[blocks] / n  ~ 0.85
    jobs = poisson_stream(NUM_JOBS, rate=0.45, mean_service=8.0, seed=11)
    streams = {"poisson": (jobs, ())}
    if not quick:
        streams["heavy_tail"] = (
            heavy_tailed_stream(NUM_JOBS, rate=0.45, service_scale=3.0, seed=12),
            (),
        )
    # churn: endpoint failures mid-stream; repair returns half of them.
    # The same physical endpoints hit different block slots per strategy.
    rng = np.random.default_rng(5)
    dead = rng.choice(topo.num_endpoints, size=6, replace=False)
    span_est = NUM_JOBS / 0.45
    streams["churn"] = (jobs, (
        FailureEvent(time=0.25 * span_est, endpoints=tuple(int(e) for e in dead[:4]),
                     repair_at=0.55 * span_est),
        FailureEvent(time=0.40 * span_est, endpoints=tuple(int(e) for e in dead[4:])),
    ))

    rows = []
    poisson_snaps = {}
    churn_snaps = {}
    for scen, (stream, failures) in streams.items():
        for strat in STRATEGIES:
            sched = OnlineScheduler(topo, strategy=strat, policy="first_fit")
            res = sched.run_stream(stream, failures=failures)
            rows.append({"scenario": scen, **res.summary()})
            if scen == "poisson":
                poisson_snaps[strat] = res.snapshots
            elif scen == "churn":
                churn_snaps[strat] = res.snapshots
    emit(rows, "sched_stream_summary (online scheduling, 7 strategies)")

    # scheduling-policy ablation: placement policy x backfilling (the
    # strategy is fixed; these knobs are the scheduler's own)
    ablation = []
    for policy in ("first_fit", "best_fit"):
        for backfill in ((True,) if quick else (True, False)):
            res = OnlineScheduler(
                topo, strategy="diagonal", policy=policy, backfill=backfill,
            ).run_stream(jobs)
            s = res.summary()
            ablation.append({
                "policy": policy, "backfill": backfill,
                "utilization": s["utilization"], "mean_wait": s["mean_wait"],
                "p95_wait": s["p95_wait"], "frag_mean": s["frag_mean"],
                "scattered_frac": s["scattered_frac"],
            })
    emit(ablation, "sched_policy_ablation (diagonal)")

    # interference: strategy x snapshot x seed through the batched engine
    selected = _select_snapshots(topo, poisson_snaps, 2 if quick else 6, quick)
    seeds = list(range(common.NUM_SEEDS))
    snap_rows, stats = evaluate_snapshots(
        topo, selected, seeds=seeds, horizon=30_000 if quick else 60_000,
        mode=common.ROUTING,
    )
    emit(snap_rows, "sched_snapshots_interference (co-resident jobs, batched)")
    if stats["engine"] is not None:
        buckets = sorted({r["bucket"] for r in snap_rows})
        emit([{
            "workloads": len(snap_rows) // max(len(seeds), 1),
            "seeds": len(seeds),
            "shape_buckets": len(buckets),
            "traces": stats["traces"],
            "device_calls": stats["device_calls"],
        }], "sched_compile_stats (one compile + call per bucket)")

    # routing x churn-fault grid: snapshots taken while endpoints were
    # failed lower to link-fault masks (failure domains are co-packaged);
    # each routing policy then runs the SAME degraded machine.  Quick mode
    # keeps two policies / two strategies so CI pays for ~one extra
    # compile (the omniwar engine + bucket is shared with the table above).
    faulty = {
        k: [s for s in snaps if s.failed_endpoints]
        for k, snaps in churn_snaps.items()
    }
    if quick:
        faulty = {k: faulty.get(k, []) for k in ("diagonal", "rectangular")}
    modes = ("omniwar", "ugal") if quick else ("min", "omniwar", "val", "ugal")
    selected_f = _select_snapshots(topo, faulty, 1 if quick else 3, quick)
    churn_rows, stats_by_mode = evaluate_snapshots_by_routing(
        topo, selected_f, modes=modes, seeds=seeds,
        horizon=30_000 if quick else 60_000, churn_faults=True,
    )
    emit(churn_rows, "sched_routing_churn (routing x strategy x churn faults)")
    emit([
        {"routing": m, "traces": st["traces"],
         "device_calls": st["device_calls"]}
        for m, st in stats_by_mode.items() if st["engine"] is not None
    ], "sched_routing_compile_stats (one compile set per policy)")
    return rows


if __name__ == "__main__":
    run()
