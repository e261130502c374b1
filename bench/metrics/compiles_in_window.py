"""XLA compiles inside the window that the persistent cache did not serve."""


def read(run):
    return run.compiles
