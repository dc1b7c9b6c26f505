"""engine_lower_ms.sweep: host time per ``run_grid`` call spent lowering
workloads to device tables and stacking them, in ms: the engine's own
``engine.prepare`` and ``engine.stack`` spans inside the traced
``bench.call`` spans, averaged over the calls (program spans, on the
profiler's clock).  A program without those spans gives nothing."""


def read(run):
    if run.trace is None:
        return None
    calls = run.trace.spans("bench.call")
    spans = [(s, e) for name in ("engine.prepare", "engine.stack")
             for s, e in run.trace.spans(name)
             if any(lo <= s and e <= hi for lo, hi in calls)]
    if not spans:
        return None
    return sum(e - s for s, e in spans) / 1e6 / len(calls)
