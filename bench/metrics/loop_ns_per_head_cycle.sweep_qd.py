"""loop_ns_per_head_cycle.sweep_qd: device time per queue head and executed
lane-iteration of the cycle loop, in ns: device busy time inside the
traced ``run_grid`` calls over (lanes x loop iterations x queue heads per
lane) of those calls (device trace; the heads per lane are the engine's
``heads_per_lane``, carried on each call record).  Comparable across
machine sizes, since the cycle loop's work grows with the heads.  A
program that does not give its heads per lane gives nothing."""


def read(run):
    if run.trace is None or not run.trace.busy:
        return None
    spans = run.trace.spans("bench.call")
    calls = run.calls[:len(spans)]
    heads = [getattr(c, "heads", None) for c in calls]
    if not calls or None in heads:
        return None
    busy = sum(run.trace.busy_ns(lo, hi) for lo, hi in spans)
    executed = sum(c.lanes * c.iterations * h for c, h in zip(calls, heads))
    return busy / executed if busy > 0 else None
