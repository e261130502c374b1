"""Model FLOPs of every prompt and output token the engine processed in the
window, over the window's seconds, as a share of the chip's bfloat16 peak, in %.

The bfloat16 peak is the right one for a float32 model: at default precision a
TPU runs float32 matrix products as bfloat16 passes. Prompts count a causal
prefill with logits at the last position; each decode step counts one token
per active sequence with its attended length (``bench/lib/counts.py``). A step
that straddles the window's end counts in proportion."""


def read(run):
    if run.shape is None or run.peaks is None or not run.window.steps:
        return None
    w = run.window
    flops = 0.0
    for st in w.steps:
        t0, t1 = st["t0"], st["t1"]
        if t1 <= w.start or t0 >= w.deadline:
            continue
        work = sum(run.shape.prefill_flops(p) for p in st["admitted"]) + sum(
            run.shape.token_flops(c, logits=True) for c in st["contexts"])
        inside = (min(t1, w.deadline) - max(t0, w.start)) / max(t1 - t0, 1e-12)
        flops += work * min(inside, 1.0)
    return 100.0 * flops / w.seconds / run.peaks["bf16_flops_per_s"]
