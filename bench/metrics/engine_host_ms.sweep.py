"""engine_host_ms.sweep: host time per ``run_grid`` call in which the
device ran nothing, in ms: the call's span minus the device busy time
inside it, averaged over the traced calls (device trace with the
benchmark's ``bench.call`` spans on the same clock).  It holds the
engine's table lowering and stacking, transfers and result conversion."""


def read(run):
    if run.trace is None or not run.trace.busy:
        return None
    spans = run.trace.spans("bench.call")
    idle = [(hi - lo - run.trace.busy_ns(lo, hi)) / 1e6 for lo, hi in spans]
    return sum(idle) / len(idle) if idle else None
