"""The readers of the program's spans and counters, on synthetic traces:
nothing to read gives ``None``, and spans in the window give their mean."""
from types import SimpleNamespace

import pytest

from bench import run as bench_run
from bench.lib import trace as tr

MS = 1e6            # ns

# (reader, spans inside the window, the mean they give)
CASES = [
    ("restore_ms", [("hotswap.restore", 10 * MS, 30 * MS, {"bytes": 8}),
                    ("hotswap.restore", 40 * MS, 80 * MS, {"bytes": 8})], 30.0),
    ("admit_wait_ms", [("hotswap.admit", 10 * MS, 11 * MS, {"queued_us": 1500}),
                       ("hotswap.admit", 12 * MS, 13 * MS, {"queued_us": 500})],
     1.0),
    ("prefill_ms", [("hotswap.prefill", 10 * MS, 13 * MS, {"h2d_bytes": 0}),
                    ("hotswap.prefill", 20 * MS, 21 * MS, {"h2d_bytes": 0})], 2.0),
    ("decode_ms", [("hotswap.decode", 10 * MS, 14.5 * MS, {"active": 8}),
                   ("hotswap.decode", 20 * MS, 20.5 * MS, {"active": 8})], 2.5),
    ("param_h2d_bytes_per_call",
     [("hotswap.prefill", 10 * MS, 13 * MS, {"h2d_bytes": 100}),
      ("hotswap.decode", 20 * MS, 21 * MS, {"h2d_bytes": 300}),
      ("hotswap.decode", 30 * MS, 31 * MS, {"h2d_bytes": 200})], 200.0),
]
READERS = [c[0] for c in CASES]


def run_with(program_spans, traced=True):
    if not traced:
        return SimpleNamespace(events=None, trace=None)
    ev = tr.TraceEvents(host_spans=[("bench.window#0", 5 * MS, 95 * MS)],
                        program_spans=program_spans)
    return SimpleNamespace(events=ev, trace=tr.reduce_window(ev, 5 * MS, 95 * MS))


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_gives_none(name):
    read = bench_run.reader(name).read
    assert read(run_with([], traced=False)) is None
    assert read(run_with([])) is None
    other = [("hotswap.step", 10 * MS, 20 * MS, {"queued": 0})]
    assert read(run_with(other)) is None


@pytest.mark.parametrize("name,spans,want", CASES, ids=READERS)
def test_mean_of_the_spans_in_the_window(name, spans, want):
    read = bench_run.reader(name).read
    # the same spans before the window opens and after it closes, which
    # would move the mean if they were read
    outside = [(n, t, t + 1 * MS, {k: 10**9 for k in st})
               for t in (0, 96 * MS) for n, _, _, st in spans]
    assert read(run_with(sorted(spans + outside, key=lambda s: s[1]))) == \
        pytest.approx(want)
