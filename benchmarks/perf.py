"""Perf trajectory harness — times the engine and gates regressions.

    PYTHONPATH=src python -m benchmarks.perf [--quick|--full]
                                             [--out PATH] [--rev REV]
                                             [--compare [BASE.json]]
                                             [--threshold 0.10]
                                             [--grids a,b,...]
                                             [--kernel lax|pallas]
                                             [--chunk K] [--canon]

Runs the canonical grids (strategy / pattern / fault sweeps on the paper
machine) through a **fresh** ``SimEngine`` each — so compile time is
honestly attributed — and records, per grid:

  * ``compile_s``     — first-call wall time minus steady-state run time;
  * ``device_s``      — steady-state wall time of one full grid dispatch;
  * ``cycles``        — simulated flit-cycles summed over all lanes
    (post-warmup; horizon-clamped for incomplete lanes — deterministic,
    since simulation results are regression-pinned bitwise);
  * ``cycles_per_s``  — cycles / device_s, the headline throughput;
  * ``lanes``, ``lanes_per_s``, ``buckets``, ``traces``.

The snapshot lands in ``BENCH_<rev>.json`` at the repo root (``--out``
overrides) together with host metadata (backend, device count, lane
dispatch backend, jax version) and a full ``manifest`` provenance block
(:func:`repro.obs.trace.manifest_dict` — the same schema trace
directories carry, so BENCH files and traces join on ``config_hash``) —
the persistent perf trajectory ROADMAP calls for.  Every run also
*appends* one line to ``BENCH_history.jsonl`` at the repo root (rev,
UTC date, engine knobs, per-grid metrics) — the cumulative trajectory.

``--compare BASE.json`` re-measures and exits nonzero when any grid's
``device_s`` regresses more than ``--threshold`` (default 10%) against
the baseline; a bare ``--compare`` (no path) gates against the *latest
prior entry* of ``BENCH_history.jsonl`` instead.  This is the CI perf
gate (``BENCH_baseline.json`` is regenerated on the CI machine itself;
refresh the committed copy with ``--baseline`` when a speedup lands).
Exit codes: 2 = regression past the gate; 3 = the baseline file (or
history) is missing or corrupt (validated *before* any measurement).

Engine knobs under measurement: ``--arb`` / ``--kernel`` (Pallas
arbitration / fused route+arbitrate megakernel), ``--chunk K``
(early-exit granularity of the cycle loop), ``--canon`` (pow2 batch-axis
canonicalization; its compile-key hit rate lands in the snapshot).  The
persistent XLA compile cache is always on: ``JAX_COMPILATION_CACHE_DIR``
when set, else ``<repo>/.jax_cache`` (see ``repro.core.engine.cache``).
Device time by cycle stage and host time by engine stage are the chip
benchmark's (``python3 bench/run.py ... --trace 1``), not this file's.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax

from benchmarks.common import (
    PAPER_TOPO,
    STRATEGIES,
    escalation_workload,
    interference_workload,
    write_grid_csv,
)

from repro.core.engine import PACKET_FLITS, SimEngine, enable_persistent_cache
from repro.obs.trace import manifest_dict
from repro.route import apply_faults, random_link_faults

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HISTORY_PATH = os.path.join(REPO_ROOT, "BENCH_history.jsonl")
SCHEMA = 1
DEFAULT_THRESHOLD = 0.10
EXIT_REGRESSION = 2
EXIT_BAD_BASELINE = 3


# ------------------------------------------------------------ canonical grids
def _grid_escalation(quick: bool):
    strategies = ("row", "diagonal", "full_spread") if quick else STRATEGIES
    wls = [escalation_workload(s, "all_to_all", replicas=1)
           for s in strategies]
    return wls, (0,), "omniwar", 30_000


def _grid_traffic(quick: bool):
    patterns = ("tornado", "transpose") if quick else (
        "tornado", "transpose", "shuffle", "stencil_3d")
    strategies = ("row", "diagonal") if quick else (
        "row", "diagonal", "full_spread", "rectangular")
    wls = [interference_workload(s, p, with_bg=False)
           for p in patterns for s in strategies]
    return wls, (0,), "omniwar", 30_000


def _grid_routing_faults(quick: bool):
    strategies = ("row", "diagonal") if quick else (
        "row", "diagonal", "full_spread", "rectangular")
    mask = random_link_faults(PAPER_TOPO, 0.02, seed=77)
    wls = []
    for s in strategies:
        wl = interference_workload(s, "all_to_all", with_bg=False)
        wls.append(wl)
        wls.append(apply_faults(wl, mask))
    seeds = (0,) if quick else (0, 1)
    return wls, seeds, "omniwar", 6_000


GRIDS = {
    "escalation_a2a": _grid_escalation,
    "traffic_adversarial": _grid_traffic,
    "routing_faults": _grid_routing_faults,
}


# ----------------------------------------------------------------- measuring
def measure_grid(workloads, seeds, mode, horizon,
                 topo=PAPER_TOPO, arb: str = "lax", kernel: str = "lax",
                 chunk: int = 1, canon: bool = False) -> dict:
    """Time one grid through a fresh engine: compile vs steady-state.

    The engine is constructed directly (bypassing the ``get_engine``
    memo) so the first ``run_grid`` call pays — and therefore measures —
    the real compilation cost; an identical second call measures the
    steady-state device time.  ``_to_result`` materialises every output
    on the host, so the wall clock brackets full device execution.
    ``wall_first_s`` / ``wall_repeat_s`` record the two raw calls — the
    pair the persistent compile cache moves (a cache-warm process pays
    steady-state on its *first* call).
    """
    num_pools = {w.num_pools for w in workloads}
    if len(num_pools) != 1:
        raise ValueError(f"grid mixes VC pool counts {sorted(num_pools)}")
    engine = SimEngine(topo, mode=mode, num_pools=num_pools.pop(), arb=arb,
                       kernel=kernel, chunk=chunk, canon=canon)
    preps = [engine.prepare(w) for w in workloads]
    buckets = {p.tables.shape_bucket for p in preps}

    t0 = time.perf_counter()
    results = engine.run_grid(preps, seeds=seeds, horizon=horizon)
    t1 = time.perf_counter()
    engine.run_grid(preps, seeds=seeds, horizon=horizon)
    t2 = time.perf_counter()

    device_s = t2 - t1
    compile_s = max((t1 - t0) - device_s, 0.0)
    lanes = len(workloads) * len(seeds)
    cycles = sum(
        (r.makespan if r.completed else horizon) * PACKET_FLITS
        for per_seed in results for r in per_seed
    )
    stats = engine.bucket_stats()
    return {
        "lanes": lanes,
        "buckets": len(buckets),
        "traces": engine.trace_count,
        "lane_backend": engine.lane_backend,
        "compile_s": round(compile_s, 3),
        "device_s": round(device_s, 3),
        "wall_first_s": round(t1 - t0, 3),
        "wall_repeat_s": round(t2 - t1, 3),
        "cycles": int(cycles),
        "cycles_per_s": round(cycles / max(device_s, 1e-9), 1),
        "lanes_per_s": round(lanes / max(device_s, 1e-9), 2),
        "bucket_hits": stats["hits"],
        "bucket_misses": stats["misses"],
        "bucket_hit_rate": round(stats["hit_rate"], 3),
    }


def current_rev() -> str:
    rev = os.environ.get("BENCH_REV")
    if rev:
        return rev
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=REPO_ROOT,
            capture_output=True, text=True, check=True,
        ).stdout.strip()
    except Exception:
        return "dev"


def run_suite(quick: bool = True, grids=None, arb: str = "lax",
              kernel: str = "lax", chunk: int = 1, canon: bool = False) -> dict:
    """Measure every requested grid; returns the BENCH json payload."""
    names = list(GRIDS) if not grids else [g for g in GRIDS if g in grids]
    knobs = {"arb": arb, "kernel": kernel, "chunk": chunk, "canon": canon}
    bench = {
        "schema": SCHEMA,
        "rev": current_rev(),
        "quick": quick,
        "backend": jax.default_backend(),
        "devices": jax.local_device_count(),
        "jax": jax.__version__,
        **knobs,
        # full provenance block — same shape as a trace dir's manifest.json,
        # so BENCH snapshots and traces join on config_hash
        "manifest": manifest_dict(rev=current_rev(), quick=quick, **knobs),
        "grids": {},
    }
    for name in names:
        wls, seeds, mode, horizon = GRIDS[name](quick)
        print(f"# measuring {name} ({len(wls)} workloads x "
              f"{len(seeds)} seeds)...", file=sys.stderr)
        bench["grids"][name] = measure_grid(
            wls, seeds, mode, horizon, arb=arb, kernel=kernel, chunk=chunk,
            canon=canon)
    return bench


# -------------------------------------------------------------------- history
def append_history(bench: dict, path: str | None = None) -> dict:
    """Append one run to the cumulative ``BENCH_history.jsonl`` trajectory.

    One JSON object per line: rev, UTC date, engine knobs, and the
    per-grid metric table (sans host manifest — the BENCH_<rev>.json
    snapshot keeps full provenance).  Returns the appended entry.
    """
    path = path or HISTORY_PATH
    entry = {
        k: bench[k]
        for k in ("schema", "rev", "quick", "backend", "devices", "jax",
                  "arb", "kernel", "chunk", "canon")
        if k in bench
    }
    entry["date"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    entry["grids"] = bench["grids"]
    with open(path, "a") as f:
        f.write(json.dumps(entry, sort_keys=True) + "\n")
    return entry


def latest_history(path: str | None = None,
                   quick: bool | None = None) -> dict | None:
    """The most recent prior history entry (optionally matching ``quick``).

    Unparsable lines are skipped, matching the report loader's contract:
    a truncated final line from a killed run must not poison the gate.
    """
    path = path or HISTORY_PATH
    if not os.path.exists(path):
        return None
    last = None
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                entry = json.loads(line)
            except json.JSONDecodeError:
                continue
            if not isinstance(entry, dict) or not isinstance(
                    entry.get("grids"), dict):
                continue
            if quick is not None and entry.get("quick") != quick:
                continue
            last = entry
    return last


# ------------------------------------------------------------------ comparing
def compare_benchmarks(new: dict, base: dict,
                       threshold: float = DEFAULT_THRESHOLD) -> list[dict]:
    """Per-grid device-time comparison; returns rows with a 'regressed' flag.

    A grid regresses when its steady-state ``device_s`` exceeds the
    baseline's by more than ``threshold`` (compile time is reported but
    not gated — it is far noisier and dominated by XLA version churn).
    Grids present on only one side are reported but never gate.
    """
    rows = []
    for name in sorted(set(new.get("grids", {})) | set(base.get("grids", {}))):
        g_new = new.get("grids", {}).get(name)
        g_base = base.get("grids", {}).get(name)
        if g_new is None or g_base is None:
            rows.append({
                "grid": name, "base_device_s": g_base and g_base["device_s"],
                "new_device_s": g_new and g_new["device_s"],
                "ratio": "", "regressed": False,
                "note": "missing on one side",
            })
            continue
        ratio = g_new["device_s"] / max(g_base["device_s"], 1e-9)
        rows.append({
            "grid": name,
            "base_device_s": g_base["device_s"],
            "new_device_s": g_new["device_s"],
            "ratio": round(ratio, 3),
            "regressed": ratio > 1.0 + threshold,
            "note": "",
        })
    return rows


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--quick", action="store_true",
                   help="CI-sized grids (the default; --full overrides)")
    p.add_argument("--full", action="store_true")
    p.add_argument("--out", default=None, metavar="PATH",
                   help="output json (default: <repo>/BENCH_<rev>.json)")
    p.add_argument("--rev", default=None,
                   help="revision label (default: git short sha)")
    p.add_argument("--compare", nargs="?", const="history", default=None,
                   metavar="BASE",
                   help="baseline BENCH json; exit nonzero on regression "
                        "(bare --compare gates against the latest prior "
                        "BENCH_history.jsonl entry)")
    p.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                   help="regression gate on device_s (default 0.10 = 10%%)")
    p.add_argument("--grids", default=None,
                   help=f"comma list from {sorted(GRIDS)}")
    p.add_argument("--arb", default="lax", choices=("lax", "pallas"),
                   help="arbitration backend to measure")
    p.add_argument("--kernel", default="lax", choices=("lax", "pallas"),
                   help="route+arbitrate block: lax reference or the fused "
                        "Pallas megakernel")
    p.add_argument("--chunk", type=int, default=1, metavar="K",
                   help="cycle-loop early-exit granularity (all_done "
                        "checked every K cycles; K=1 = reference)")
    p.add_argument("--canon", action="store_true",
                   help="pow2-canonicalize batch-axis lengths (compile "
                        "sharing across nearby grid sizes)")
    p.add_argument("--history", default=None, metavar="PATH",
                   help="history jsonl to append/compare "
                        "(default <repo>/BENCH_history.jsonl)")
    p.add_argument("--baseline", action="store_true",
                   help="also refresh <repo>/BENCH_baseline.json")
    args = p.parse_args(argv)
    if args.quick and args.full:
        p.error("--quick and --full are mutually exclusive")
    if args.chunk < 1:
        p.error("--chunk must be >= 1")
    if args.rev:
        os.environ["BENCH_REV"] = args.rev
    grids = args.grids.split(",") if args.grids else None
    unknown = set(grids or []) - set(GRIDS)
    if unknown:
        p.error(f"unknown grids {sorted(unknown)}; have {sorted(GRIDS)}")
    enable_persistent_cache()

    base = None
    base_label = args.compare
    if args.compare == "history":
        # gate against the latest prior trajectory entry of matching size
        base = latest_history(args.history, quick=not args.full)
        if base is None:
            print("# perf: --compare requested but "
                  f"{args.history or HISTORY_PATH} has no prior "
                  f"{'quick' if not args.full else 'full'} entry",
                  file=sys.stderr)
            return EXIT_BAD_BASELINE
        base_label = f"history:{base.get('rev')}@{base.get('date')}"
    elif args.compare:
        # validate the baseline BEFORE measuring: a missing or corrupt
        # file should fail in milliseconds with a distinct exit code, not
        # after minutes of measurement with a traceback
        try:
            with open(args.compare) as f:
                base = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"# perf: cannot read baseline {args.compare}: {e}",
                  file=sys.stderr)
            return EXIT_BAD_BASELINE
        if not isinstance(base, dict) or not isinstance(
                base.get("grids"), dict):
            print(f"# perf: baseline {args.compare} is not a BENCH "
                  "snapshot (missing 'grids' table)", file=sys.stderr)
            return EXIT_BAD_BASELINE

    bench = run_suite(quick=not args.full, grids=grids, arb=args.arb,
                      kernel=args.kernel, chunk=args.chunk, canon=args.canon)
    out = args.out or os.path.join(REPO_ROOT, f"BENCH_{bench['rev']}.json")
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(bench, f, indent=2, sort_keys=True)
        f.write("\n")
    if args.baseline:
        with open(os.path.join(REPO_ROOT, "BENCH_baseline.json"), "w") as f:
            json.dump(bench, f, indent=2, sort_keys=True)
            f.write("\n")
    append_history(bench, args.history)
    rows = [{"grid": g, **m} for g, m in bench["grids"].items()]
    write_grid_csv(rows, f"perf ({bench['rev']}, {bench['backend']} x "
                         f"{bench['devices']} dev) -> {out}")

    if base is not None:
        cmp_rows = compare_benchmarks(bench, base, threshold=args.threshold)
        write_grid_csv(cmp_rows,
                       f"perf_compare (vs {base_label}, "
                       f"gate +{args.threshold:.0%} device_s)")
        regressed = [r["grid"] for r in cmp_rows if r["regressed"]]
        if regressed:
            print(f"# PERF REGRESSION: {', '.join(regressed)} exceeded the "
                  f"+{args.threshold:.0%} device-time gate", file=sys.stderr)
            return EXIT_REGRESSION
        print("# perf gate passed", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
