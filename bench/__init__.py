"""Chip benchmark of the HotSwap reproduction: one cell per run, driven by
``BENCHMARK.json`` at the root of the checkout (see ``bench/run.py``)."""
