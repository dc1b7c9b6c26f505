"""lane_useful_share.sweep: share of the executed lane-iterations that
simulated a lane's own cycles, in %: sum of lane own cycles over
(lanes x loop iterations) summed over the window's calls.  The batched
loop runs every lane until the slowest one finishes (program counters:
the engine's SimResults)."""


def read(run):
    executed = sum(c.lanes * c.iterations for c in run.calls)
    return 100.0 * sum(c.own_cycles for c in run.calls) / executed
