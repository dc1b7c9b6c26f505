"""loop_ns_per_head_port_cycle.sweep_wide: device time per queue head,
network port slot and executed lane-iteration of the cycle loop, in ns:
device busy time inside the traced ``run_grid`` calls over (lanes x loop
iterations x queue heads per lane x network port slots of a head's row)
of those calls (device trace; heads and port slots, q * n, are carried on
each call record).  Route's candidate rows, the jitter draw and
arbitrate's link views grow with the heads times the port slots, the
axis along which the radix-64 machine differs from the others.  A
program that does not give its heads per lane gives nothing."""


def read(run):
    if run.trace is None or not run.trace.busy:
        return None
    spans = run.trace.spans("bench.call")
    calls = run.calls[:len(spans)]
    sizes = [(getattr(c, "heads", None), getattr(c, "net_ports", None))
             for c in calls]
    if not calls or any(None in s for s in sizes):
        return None
    busy = sum(run.trace.busy_ns(lo, hi) for lo, hi in spans)
    executed = sum(c.lanes * c.iterations * h * p
                   for c, (h, p) in zip(calls, sizes))
    return busy / executed if busy > 0 else None
