"""Arithmetic that several metric readers share."""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from bench.lib import trace as tr
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


def program_spans(run, name: str) -> List[Tuple[float, float, Dict]]:
    """(start, end, stats) of the program's spans called ``name`` that open
    inside the traced window; none in a run without a trace."""
    window = None if run.events is None else tr.window_of(run.events)
    if window is None:
        return []
    lo, hi = window
    return [(a, b, stats) for n, a, b, stats in run.events.program_spans
            if n == name and lo <= a < hi]


def mean_program_span_ms(run, name: str) -> Optional[float]:
    xs = [(b - a) / 1e6 for a, b, _ in program_spans(run, name)]
    return statistics.fmean(xs) if xs else None


def mean_program_stat(run, names: Sequence[str], stat: str) -> Optional[float]:
    """Mean of counter ``stat`` over the program's spans called any of
    ``names`` inside the traced window."""
    xs = [stats[stat] for name in names for _, _, stats in program_spans(run, name)
          if stat in stats]
    return statistics.fmean(xs) if xs else None
