"""sim_lane_cycles_per_s: simulated lane-cycles completed per second.

Sum over every lane of every call in the window of that lane's own
simulated cycles (warm-up + makespan, or the horizon when it did not
finish), over the host time from the window's start to the end of the
last call.  Padded lanes and the iterations a finished lane idles inside
the batched loop do not count (host clock)."""


def read(run):
    cycles = sum(c.own_cycles for c in run.calls)
    return cycles / (run.window_end - run.window_start)
