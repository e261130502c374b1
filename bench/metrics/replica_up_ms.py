"""Mean time of the harness span around ``ServingEngine.from_pool``, in ms."""
from bench.metrics._common import mean_span_ms


def read(run):
    return mean_span_ms(run, "bench.from_pool")
