"""Process start to the first request of the window: loading, weights,
warm-up, the ramp and, in a run that compiles, compilation."""


def read(run):
    return run.setup_s
