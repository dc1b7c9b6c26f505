"""Record the small v5e trace that the trace-reduction tests read.

    python bench/tests/record_trace.py      # on a machine with a TPU

One ``run_grid`` call of the ``table4_bg.omniwar`` cell, clamped to two
loop iterations (``horizon=2``: the window's executable, a tiny trace),
under the profiler and inside the benchmark's ``bench.window`` /
``bench.call`` spans.  Written gzipped to
``bench/tests/data/v5e_table4_h2.xplane.pb.gz``.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(HERE, "data", "v5e_table4_h2.xplane.pb.gz")


def main() -> int:
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import jax

    import generator
    import harness
    import trace_reduce

    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".bench_cache", "jax"))
    harness.require_accelerator(1)
    _, _, config, mix = harness.load_cell(ROOT, "table4_bg.omniwar")
    work = generator.build(config, mix, seed=1)
    work.setup()
    work.horizon = 2
    capture = trace_reduce.Capture()
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.call", index=0):
            rec = work.call(0)
    trace = capture.stop(keep=OUT)
    lo, hi = trace.spans("bench.window")[0]
    print(json.dumps({"file": os.path.relpath(OUT, ROOT),
                      "bytes": os.path.getsize(OUT),
                      "lanes": rec.lanes, "iterations": rec.iterations,
                      "busy_ns": trace.busy_ns(lo, hi),
                      "window_ns": hi - lo}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
