"""Trace reduction: busy union, idle share, and the breakdown."""
import gzip
from pathlib import Path

import pytest

from bench.lib import trace as tr

FIXTURE = Path(__file__).resolve().parent / "data" / "trace_fn_cold_events.json.gz"


def synthetic():
    ev = tr.TraceEvents()
    ev.device_ops["/device:TPU:0"] = [("fusion", 10, 20), ("dot", 15, 30),
                                      ("copy", 50, 60), ("late", 95, 120)]
    ev.host_spans = [("bench.window#9", 0, 100), ("bench.step#1", 5, 40),
                     ("bench.admission#2", 40, 70)]
    return ev


def test_merge_and_covered():
    m = tr.merge([(10, 20), (15, 30), (50, 60), (95, 120)], 0, 100)
    assert m == [(10, 30), (50, 60), (95, 100)]
    assert tr.covered(m, 0, 100) == 35
    assert tr.covered(m, 25, 55) == 10


def test_reduce_window():
    r = tr.reduce_window(synthetic(), 0, 100)
    assert r["busy_s"] == pytest.approx(35e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["idle_share"] == pytest.approx(0.65)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["dot"] == pytest.approx(15e-9) and ops["late"] == pytest.approx(5e-9)
    gaps = dict(r["breakdown"]["idle_gaps"])
    # step 5-40 busy 20 -> idle 15; admission 40-70 busy 10 -> idle 20;
    # window alone 0-5 and 70-100 busy 5 -> idle 30
    assert gaps == pytest.approx({"bench.step": 15e-9, "bench.admission": 20e-9,
                                  "bench.window": 30e-9})


def test_busy_inside_a_span_and_window_lookup():
    ev = synthetic()
    r = tr.reduce_window(ev, 0, 100)
    assert tr.device_busy_in(r, 5, 40) == 20
    assert tr.window_of(ev) == (0, 100)
    assert [s[0] for s in ev.spans("bench.step")] == ["bench.step#1"]


def test_op_name_drops_the_instruction_text():
    hlo = "%while.9 = (s32[]{:T(128)}, bf16[1,64,512]) while((s32[]) %t), body=%b"
    assert tr.op_name(hlo) == "while.9"
    assert tr.op_name(hlo, "jit_prefill_logits(123)") == "jit_prefill_logits/while.9"


def test_events_round_trip_json():
    ev = synthetic()
    back = tr.TraceEvents.from_json(ev.to_json())
    assert back.device_ops == ev.device_ops and back.host_spans == ev.host_spans


def test_recorded_chip_trace():
    """A trace of fnbench.cold_models recorded on a TPU v5e: device operations
    exist, lie inside the window and are a small share of it."""
    ev = tr.TraceEvents.from_json(gzip.decompress(FIXTURE.read_bytes()).decode())
    lo, hi = tr.window_of(ev)
    r = tr.reduce_window(ev, lo, hi)
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["idle_share"] < 1
    assert len(r["breakdown"]["device_ops"]) <= 10
    assert len(r["breakdown"]["idle_gaps"]) <= 10
    assert sum(t for _, t in r["breakdown"]["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    assert {n for n, _ in r["breakdown"]["idle_gaps"]} <= {
        "bench.window", "bench.cold_start"}
