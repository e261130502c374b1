"""Share of the HBM roofline reached by the engine's decode steps, in %.

Over the engine steps that admitted nothing: the bytes a decode step must read
(every parameter once, and the keys and values of each active sequence's live
positions, ``bench/lib/counts.py``) at the chip's peak HBM bandwidth, over the
device-busy time inside those steps' spans in the trace."""
from bench.lib import trace as tr


def read(run):
    if run.trace is None or run.shape is None or run.peaks is None:
        return None
    spans = {name.split("#", 1)[1]: (a, b)
             for name, a, b in run.events.spans("bench.engine_step")}
    need_s = busy_ns = 0.0
    dtype = run.config["served_dtype"]
    for st in run.window.steps:
        span = spans.get(str(st["span"]))
        if st["admitted"] or not st["contexts"] or span is None:
            continue
        need = run.shape.decode_step_bytes(st["contexts"], dtype, dtype)
        need_s += need / run.peaks["hbm_bytes_per_s"]
        busy_ns += tr.device_busy_in(run.trace, *span)
    if busy_ns <= 0:
        return None
    return 100.0 * need_s / (busy_ns / 1e9)
