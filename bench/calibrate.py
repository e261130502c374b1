"""Readings that the correctness limits are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10 \
        --control <precision>[,<precision>...]

In one process, for each seed: one run of the cell at its own load, printing
the numbers its check compares and the same numbers for each control: the
plain reference in the program's place, computed in a ``--control``
precision (``bfloat16`` throughout, or its weights rounded to ``int8`` or
``float8_e4m3fn``). The limit of a number lies between the largest reading of
the program and the smallest of the control. One JSON line per seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run as bench_run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(bench_run.ROOT), str(bench_run.ROOT / "src")]
    man, cell, config, mix, limits = bench_run.load_cell(args.workload)
    bench_run.devices_for(cell["chips"], require_tpu=True)
    bench_run.enable_compile_cache()
    metrics = bench_run.metrics_of(man, cell, False)
    controls = tuple(args.control.split(","))
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        out = bench_run.run_cell(config, mix, cell, metrics, limits, seed,
                                 args.seconds, False, t_process=t0,
                                 controls=controls)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"], "failed": out["failed"],
                          "checks": out["checks"],
                          "control": out["control_checks"],
                          "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                          "memory_peak_bytes": out["device"]["memory_peak_bytes"],
                          "seconds": time.monotonic() - t0}), flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
