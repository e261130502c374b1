"""Helpers for tests that drive whole runs of a cell on the CPU."""
import time

from bench import run as bench_run

CELL = "qwen1.5-0.5b.scale_from_zero"


def tiny(config, mix):
    """The endpoint configuration and mix at a size a test run can hold."""
    config = dict(config, hidden_size=64, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=4,
                  intermediate_size=128, vocab_size=512)
    mix = dict(mix, prompt_lengths=[8, 16, 24], length_weights=[0.5, 0.25, 0.25],
               max_new_tokens=8, max_seq_len=32, deck=4, slots=4, burst=4)
    return config, mix


def run(name=CELL, seconds=3.0, trace=False, controls=(), seed=2**40 + 11):
    """One run of cell ``name`` of BENCHMARK.json on the CPU, at a tiny size
    (the look for a chip skipped)."""
    man, cell, config, mix, limits = bench_run.load_cell(name)
    config, mix = tiny(config, mix)
    return bench_run.run_cell(config, mix, cell,
                              bench_run.metrics_of(man, cell, trace), limits,
                              seed, seconds, trace, require_tpu=False,
                              t_process=time.monotonic(), controls=controls)
