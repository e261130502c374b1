"""1 - (union of device-operation intervals / window), from the trace, in %."""
from bench.metrics._common import idle_share_percent


def read(run):
    return idle_share_percent(run)
