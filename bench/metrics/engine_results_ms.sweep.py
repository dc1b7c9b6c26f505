"""engine_results_ms.sweep: host time per ``run_grid`` call spent turning
the device outputs into ``SimResult``s, in ms: the engine's own
``engine.to_result`` spans inside the traced ``bench.call`` spans,
averaged over the calls (program spans, on the profiler's clock).  A
program without those spans gives nothing."""


def read(run):
    if run.trace is None:
        return None
    calls = run.trace.spans("bench.call")
    spans = [(s, e) for s, e in run.trace.spans("engine.to_result")
             if any(lo <= s and e <= hi for lo, hi in calls)]
    if not spans:
        return None
    return sum(e - s for s, e in spans) / 1e6 / len(calls)
