"""device_idle_share.sweep: share of the traced window in which no program
ran on the device, in % (device trace: union of the XLA module intervals
within the benchmark's ``bench.window`` span)."""


def read(run):
    if run.trace is None or not run.trace.busy:
        return None
    lo, hi = run.trace.spans("bench.window")[0]
    return 100.0 * (1.0 - run.trace.busy_ns(lo, hi) / (hi - lo))
