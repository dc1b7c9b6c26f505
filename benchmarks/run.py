"""Run every benchmark (one per paper table/figure + the roofline bench).

    PYTHONPATH=src python -m benchmarks.run [--quick|--full]
                                            [--seeds N] [--csv DIR]
                                            [--only NAME]
                                            [--routing POLICY]
                                            [--trace DIR]

--quick trims replica counts / kernel sets (1-core CPU friendly); --full
runs the complete paper grids.  Default: quick.
--seeds N fans every simulated scenario across N seeds — the seed axis is
batched through ``SimEngine.run_grid`` (same device call as the strategy
axis), and rows report means over seeds.
--csv DIR additionally writes every emitted table to DIR/<name>.csv so
perf trajectories land in versionable files.
--routing POLICY runs every simulation-backed module (fig8, table4,
table3, sched_stream, collective_sim_bench, ...) under that routing
policy (any name registered in ``repro.route``; default omniwar).  Two
modules are pinned by design: ``fig7_min_escalation`` is the paper's
MIN artifact, and ``routing_grid`` always sweeps all policies.
--pattern NAME focuses the pattern-parameterized modules (``traffic_grid``)
on that traffic pattern (any name registered in ``repro.traffic``;
default all_to_all).
--trace DIR activates the :mod:`repro.obs` tracer for the whole run:
every module executes inside a ``bench.<name>`` span, engine dispatches
and scheduler events land in ``DIR/events.jsonl`` next to the run
manifest, a telemetry-enabled probe grid records per-link utilization
series, and the fleet report (``DIR/report/report.md`` + CSVs) is
rendered at the end.
The persistent XLA compile cache is on for every run:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.
"""

import argparse
import sys
import time
import traceback


MODULES = [
    "table1_properties",
    "fig4_scalability",
    "fig7_min_escalation",
    "fig8_static_interference",
    "table3_escalation",
    "table4_interference",
    "fig11_fabric_partitioning",
    "routing_grid",
    "traffic_grid",
    "resilience_grid",
    "sched_stream",
    "collective_sim_bench",
    "roofline_bench",
]


def main(argv=None):
    from repro.route import available_policies
    from repro.traffic import available_patterns

    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true",
                   help="CI-sized grids (the default; --full overrides)")
    p.add_argument("--full", action="store_true")
    p.add_argument("--only", default=None)
    p.add_argument("--seeds", type=int, default=1,
                   help="seeds per scenario, fanned through run_grid")
    p.add_argument("--csv", default=None, metavar="DIR",
                   help="also write each table to DIR/<name>.csv")
    p.add_argument("--routing", default="omniwar",
                   choices=available_policies(),
                   help="routing policy for the simulation-backed modules")
    p.add_argument("--pattern", default="all_to_all",
                   choices=available_patterns(),
                   help="focus pattern for the pattern-parameterized modules")
    p.add_argument("--trace", default=None, metavar="DIR",
                   help="write a JSONL event trace + run manifest to DIR "
                        "and render the fleet report there")
    args = p.parse_args(argv)
    if args.quick and args.full:
        p.error("--quick and --full are mutually exclusive")
    quick = not args.full

    from repro.core.engine import enable_persistent_cache
    enable_persistent_cache()

    from benchmarks import common
    common.NUM_SEEDS = max(1, args.seeds)
    common.CSV_DIR = args.csv
    common.QUICK = quick
    common.ROUTING = args.routing
    common.PATTERN = args.pattern

    from repro.obs import report as obs_report
    from repro.obs import trace as obs_trace

    if args.trace:
        obs_trace.configure(
            args.trace, quick=quick, seeds=common.NUM_SEEDS,
            routing=args.routing, pattern=args.pattern,
            only=args.only or "all",
        )

    mods = [m for m in MODULES if args.only is None or args.only in m]
    t00 = time.time()
    timings: list[tuple[str, float]] = []
    failures: list[tuple[str, str]] = []
    try:
        for name in mods:
            # one raising module must not abort the suite: record it,
            # keep going, and make the whole run exit nonzero at the end
            t0 = time.time()
            try:
                mod = __import__(f"benchmarks.{name}", fromlist=["run"])
                with obs_trace.span(f"bench.{name}"):
                    mod.run(quick=quick)
            except Exception as e:
                failures.append((name, f"{type(e).__name__}: {e}"))
                traceback.print_exc()
                print(f"# [{name}] FAILED: {type(e).__name__}: {e}\n")
                obs_trace.event("bench.failed", module=name, error=str(e))
            timings.append((name, time.time() - t0))
            # per-module wall time as a gauge so fleet rollups can chart
            # where suite time goes without re-parsing stdout
            obs_trace.gauge("bench.module", round(timings[-1][1], 4),
                            module=name,
                            failed=bool(failures and failures[-1][0] == name))
            if not failures or failures[-1][0] != name:
                print(f"# [{name}] {timings[-1][1]:.1f}s\n")
        if args.trace:
            # telemetry-enabled probe grid: the per-link utilization /
            # latency series the fleet report renders into heatmap tables
            with obs_trace.span("bench.telemetry"):
                common.telemetry_probe(
                    horizon=20_000 if quick else 60_000)
    finally:
        if args.trace:
            obs_trace.disable()
    if args.trace:
        paths = obs_report.write_report(args.trace)
        print(f"# trace report: {paths['report']}")
    total = time.time() - t00
    # wall-time summary: where the suite's time actually goes, slowest first
    failed = {name for name, _ in failures}
    print("# timing summary (wall s)")
    for name, t in sorted(timings, key=lambda it: -it[1]):
        flag = "  FAILED" if name in failed else ""
        print(f"#   {name:<28s} {t:7.1f}s  "
              f"{100 * t / max(total, 1e-9):5.1f}%{flag}")
    print(f"# total {total:.1f}s over {len(timings)} modules"
          + (f", {len(failures)} FAILED" if failures else ""))
    for name, err in failures:
        print(f"# FAILED {name}: {err}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
