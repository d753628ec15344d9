"""Process start to window open: weights, warm-up and the ramp."""


def read(run):
    return run.setup_s
