"""Mean time of the program's ``hotswap.decode`` spans in the traced window,
in ms: one batched decode step, until its logits are on the host."""
from bench.metrics._common import mean_program_span_ms


def read(run):
    return mean_program_span_ms(run, "hotswap.decode")
