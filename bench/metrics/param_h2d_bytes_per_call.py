"""Mean of the ``h2d_bytes`` counter of the program's ``hotswap.prefill`` and
``hotswap.decode`` spans in the traced window, in bytes: parameter bytes not
on the device, which each such call copies host to device."""
from bench.metrics._common import mean_program_stat


def read(run):
    return mean_program_stat(run, ("hotswap.prefill", "hotswap.decode"),
                             "h2d_bytes")
