"""Set-up: process start to the opening of the window (weights, engine,
warm-up and every compile), on the host clock."""


def read(run):
    return run.setup_s
