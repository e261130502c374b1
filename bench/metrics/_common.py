"""Arithmetic that several metric readers share."""
from __future__ import annotations

import statistics
from typing import Optional

from bench.lib.stats import percentile


def latencies(run):
    return [i.latency for i in run.window.invocations if i.latency is not None]


def latency_percentile(run, q: float) -> Optional[float]:
    lat = latencies(run)
    return percentile(lat, q) if lat else None


def mean_span_ms(run, name: str) -> Optional[float]:
    xs = [s.seconds for s in run.spans if s.name == name]
    return 1e3 * statistics.fmean(xs) if xs else None


def idle_share_percent(run) -> Optional[float]:
    if run.trace is None or not run.trace["busy_intervals"]:
        return None
    return 100.0 * run.trace["idle_share"]
