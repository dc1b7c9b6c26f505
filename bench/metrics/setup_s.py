"""setup_s: seconds from process start to the window's start -- imports,
device start-up, building the inputs, loading or compiling every program
the cell uses, and the warm-up calls (host clock)."""


def read(run):
    return run.setup_s
