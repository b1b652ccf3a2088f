"""Process start to the opening of the window: weights, programs (compiled
or loaded from the cache), warm executions, traffic and slot fill."""


def read(run):
    return run.setup_s
