"""Drive one cell of ``BENCHMARK.json`` once and print its result line.

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name:

  * the configuration's ``file`` in ``BENCHMARK.json`` -- the
    configuration as it is run;
  * ``<paths[0]>/traffic/<traffic>.json`` -- the mix, read by
    ``generator``, which drives it with ``bench/kinds/<kind>.py``;
  * ``bench/metrics/<metric>.py`` -- a reader ``read(run) -> float|None``
    for each metric (``None``: nothing to read, the metric is left out).

A run: set-up (inputs, engine, warm-up of every compile key; counted from
process start), then a closed-loop window of calls for ``--seconds``
(with ``--trace 1``: one call, under the profiler), then the check against
the plain reference once the program's state is released.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import sys
import time

import generator

BENCH = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""

    setup_s: float
    window_start: float          # host clock
    window_end: float            # host clock: end of the last call
    calls: list                  # generator.CallRecord, in order
    trace: object = None         # trace_reduce.Trace in a traced run


class CompileCounter:
    """Counts JAX traces and backend compiles (persistent-cache loads
    included) as they happen in this process."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event in self.EVENTS:
            self.count += 1


def load_reader(name: str):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(entries: list, cell: str) -> list:
    return [m for m in entries if cell in m.get("workloads", [cell])]


def require_accelerator(chips: int):
    """The first device is a TPU and there are at least ``chips`` of them;
    otherwise exit non-zero before anything runs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: no TPU (first device is {devs[0].platform}); "
                 "there is no CPU fallback")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, found {len(devs)}")
    return devs[:chips]


def memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def load_cell(root: str, name: str) -> tuple[dict, dict, dict, dict]:
    """``BENCHMARK.json`` of the checkout at ``root``, the cell ``name``,
    its configuration file and its traffic mix."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        sys.exit(f"bench: unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, bench["paths"][0], "traffic",
                           f"{cell['traffic']}.json")) as f:
        mix = json.load(f)
    return bench, cell, config, mix


def main(argv, root: str, t_start: float, devices=None) -> int:
    p = argparse.ArgumentParser(description="Run one benchmark cell once.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    bench, cell, config, mix = load_cell(root, args.workload)

    import jax

    # the compile cache lives at a fixed path inside the checkout, apart
    # from the directory the program's own entry points use
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".bench_cache", "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    t_jax = time.perf_counter()
    devs = devices if devices is not None else \
        require_accelerator(cell["chips"])
    t_dev = time.perf_counter()
    counter = CompileCounter()

    work = generator.build(config, mix, args.seed)
    facts = work.setup()
    setup_s = time.perf_counter() - t_start
    facts.update(import_s=round(t_jax - t_start, 3),
                 devices_s=round(t_dev - t_jax, 3))
    print(f"setup: {json.dumps(facts)} setup_s={setup_s:.3f}", flush=True)

    capture = None
    if args.trace:
        import trace_reduce

        capture = trace_reduce.Capture()
    compiles0 = counter.count
    calls = []
    w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            i = len(calls)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.call", index=i):
                rec = work.call(i)
            rec.start, rec.end = t0, time.perf_counter()
            calls.append(rec)
            if args.trace or rec.end - w0 >= args.seconds:
                break
    trace = capture.stop() if capture else None
    compiles = counter.count - compiles0
    print(f"window: {len(calls)} calls, {rec.end - w0:.3f} s, "
          f"compiles in window: {compiles}", flush=True)

    run = Run(setup_s=setup_s, window_start=w0, window_end=rec.end,
              calls=calls, trace=trace)
    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in metrics_for(bench[section], args.workload):
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak(devs)}
    out = {}
    if trace is not None:
        lo, hi = trace.spans("bench.window")[0]
        device["busy_s"] = trace.busy_ns(lo, hi) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        out["breakdown"] = {"device_ops": trace.top_ops(lo, hi),
                            "idle_gaps": trace.idle_gaps(lo, hi)}

    work.release()
    numbers, info = work.check(calls)
    correct = all(v <= lim for v, lim in numbers.values())
    print(f"check: {json.dumps(info)}", flush=True)
    for name, (v, lim) in numbers.items():
        print(f"check {name} {v} limit {lim}", file=sys.stderr)
    sys.stderr.flush()
    result = {
        "correct": correct,
        "attempted": sum(c.lanes for c in calls),
        "failed": sum(c.failed for c in calls),
        "metrics": metrics,
        "device": device,
        **out,
        "check": {n: {"value": v, "limit": lim}
                  for n, (v, lim) in numbers.items()},
    }
    print(json.dumps(result), flush=True)
    return 0
