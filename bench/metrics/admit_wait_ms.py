"""Mean of the ``queued_us`` counter of the program's ``hotswap.admit`` spans
in the traced window, in ms: how long a request waited from ``submit`` to the
start of its admission."""
from bench.metrics._common import mean_program_stat


def read(run):
    us = mean_program_stat(run, ("hotswap.admit",), "queued_us")
    return None if us is None else us / 1e3
