"""loop_us_per_lane_cycle.sweep: device time per executed lane-iteration
of the cycle loop, in microseconds: device busy time inside the traced
``run_grid`` calls over (lanes x loop iterations) of those calls (device
trace)."""


def read(run):
    if run.trace is None or not run.trace.busy:
        return None
    spans = run.trace.spans("bench.call")
    busy = sum(run.trace.busy_ns(lo, hi) for lo, hi in spans)
    executed = sum(c.lanes * c.iterations for c in run.calls[:len(spans)])
    return busy / 1e3 / executed if busy > 0 else None
