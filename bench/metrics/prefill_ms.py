"""Mean time of the program's ``hotswap.prefill`` spans in the traced window,
in ms: one prompt's forward pass, until its last logits are on the host."""
from bench.metrics._common import mean_program_span_ms


def read(run):
    return mean_program_span_ms(run, "hotswap.prefill")
