"""Process start to the window opening: loading, weights, compiling
and warming every shape, and (closed loop) the ramp that fills the slots."""


def read(run):
    return run.setup_s
