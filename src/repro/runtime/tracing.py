"""The program's spans: named host intervals on the profiler's clock.

``span(name, **stats)`` is a ``jax.profiler.TraceAnnotation`` named
``hotswap.<name>``. Under ``jax.profiler.trace`` (or ``start_trace``) it lands
on the host line of the thread that opened it, on the same clock as the
device's operations, and its ``stats`` are kept as the event's stats. With the
profiler off it records nothing and costs about a microsecond.

Rules every span follows:

* stats are ints or short strings, known when the span opens;
* a span opens on the thread that called into the program (never on the BULK
  stream thread), so spans nest on one host line;
* a span that covers device work ends where the host holds the result.
"""
from __future__ import annotations

import jax

PREFIX = "hotswap."


def span(name: str, **stats):
    """A context manager that marks ``hotswap.<name>`` with ``stats``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **stats)
