"""Mean time of the program's ``hotswap.restore`` spans in the traced window,
in ms: a pool image's leaves fetched and its device leaves in HBM."""
from bench.metrics._common import mean_program_span_ms


def read(run):
    return mean_program_span_ms(run, "hotswap.restore")
