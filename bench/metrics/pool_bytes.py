"""Bytes the dependency pool holds in all its tiers at the window's end, as
the harness measures them (``bench/lib/footprint.py``)."""


def read(run):
    return run.pool_bytes
