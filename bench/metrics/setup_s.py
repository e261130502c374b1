"""Process start -> window start: weights, pool, compiles or cache loads,
warm-up."""


def read(run):
    return run.setup_s
