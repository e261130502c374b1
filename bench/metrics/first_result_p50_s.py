"""Median, over every invocation that arrived in the window, of arrival ->
first result on the host (a function's return value; a request's first token)."""
from bench.metrics._common import latency_percentile


def read(run):
    return latency_percentile(run, 50)
