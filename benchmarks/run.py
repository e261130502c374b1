"""Benchmark driver — one benchmark per paper table/figure + assignment artifacts.

    PYTHONPATH=src python -m benchmarks.run [--only coldstart,...]

Emits ``name,us_per_call,derived`` CSV rows (stdout) and JSON artifacts under
results/.  Mapping to the paper:

    bench_coldstart  ->  Figs. 3, 5, 6 (cold/warm, phase breakdown)
    bench_policies   ->  prewarm x placement tournament vs the hindsight
                         oracle (Pareto front + per-cell oracle gap), the
                         per-spec oracle-dominance audit, and — full scale
                         only — Table 2 (bulk / lazy / no-pageserver /
                         no-lazy)
    bench_metadata   ->  Table 3 (metadata vs image size)
    bench_sharing    ->  Fig. 7 + 88% memory headline (Azure-trace simulation);
                         with this run's coldstart costs too when coldstart
                         ran before it in the same invocation
    bench_fleet      ->  multi-worker fleet sweep (workers x capacity x skew x
                         sharing), placement + pre-warm policy comparison,
                         queue-accurate P50/P95/P99 per rate quartile
                         (NaN/negative latencies fail the run)
    bench_kernels    ->  kernel-path microbenches + VMEM accounting
    bench_roofline   ->  assignment §Roofline table (from dry-run artifacts)

``--smoke`` shrinks the simulation suites (sharing, fleet, policies) to CI
size (the scale switch is ``benchmarks.common.set_smoke`` — one definition
for the driver and CI) and writes ``results/BENCH_smoke.json``: the
canonical perf baseline (per-bench wall clock + headline metrics, including
the oracle-dominance gap minima) that CI's ``bench`` job uploads and
band-checks (``tools/ci/check_bench.py``). The measurement suites
(coldstart, kernels, ...) always do real work; ``policies`` drops its live
Table-2 stack under ``--smoke``.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks.common import save_json, set_smoke
from repro.core.compile_cache import enable_compile_cache

BENCHES = ["coldstart", "policies", "metadata", "sharing", "fleet", "kernels",
           "roofline"]

#: Version of the ``BENCH_smoke.json`` artifact layout.
BENCH_SCHEMA_VERSION = 1


def _headline(outs: dict) -> dict:
    """The paper-band metrics CI guards, pulled from the bench outputs that
    produced them (absent benches simply contribute nothing)."""
    head: dict = {}
    fleet = outs.get("fleet") or {}
    if "degenerate" in fleet:
        head["memory_saving_vs_prebaking"] = \
            fleet["degenerate"]["memory_saving_vs_prebaking"]
    if "page_model" in fleet:
        head["dependency_loading_speedup"] = \
            fleet["page_model"]["dependency_loading_speedup_paper_scale"]
    if "azure_scale" in fleet:
        head["azure_scale_n_invocations"] = \
            fleet["azure_scale"]["n_invocations"]
        head["azure_scale_wall_clock_s"] = \
            fleet["azure_scale"]["wall_clock_s"]
    if "azure_scale_xl" in fleet:
        head["azure_scale_xl_n_invocations"] = \
            fleet["azure_scale_xl"]["n_invocations"]
        head["azure_scale_xl_wall_clock_s"] = \
            fleet["azure_scale_xl"]["wall_clock_s"]
    if "stream_ingest" in fleet:
        # out-of-core ingestion headline: invocation count is deterministic
        # (trend-gated exactly); wall clock is trend-gated with slack
        head["stream_ingest_n_invocations"] = \
            fleet["stream_ingest"]["n_invocations"]
        head["stream_ingest_wall_clock_s"] = \
            fleet["stream_ingest"]["wall_clock_s"]
    if "sanitize_overhead" in fleet:
        # repro-san cost headline (check_bench fails above 3x)
        head["sanitize_overhead_ratio"] = \
            fleet["sanitize_overhead"]["ratio"]
    sharing = outs.get("sharing") or {}
    if "paper_costs" in sharing:
        head["sharing_memory_saving_vs_prebaking"] = \
            sharing["paper_costs"]["memory_saving_vs_prebaking"]
    policies = outs.get("policies") or {}
    if "oracle_gap" in policies:
        # the dominance headline: minimum oracle gap over every tournament
        # cell and audited spec x method (check_bench fails on < 0 or NaN)
        gap = policies["oracle_gap"]
        head["oracle_gap"] = {
            "min_total_gap_s": gap["min_total_gap_s"],
            "min_p99_gap_s": gap["min_p99_gap_s"],
            "n_cells": gap["n_cells"],
        }
    return head


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help=f"comma-separated subset of {BENCHES}")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized runs for the simulation suites "
                         "(sharing, fleet); pair with --only")
    args = ap.parse_args()
    enable_compile_cache()
    set_smoke(args.smoke)
    todo = args.only.split(",") if args.only else BENCHES

    print("name,us_per_call,derived")
    failures = 0
    cells: dict = {}
    outs: dict = {}
    for name in todo:
        mod_name = f"benchmarks.bench_{name}"
        t0 = time.perf_counter()
        try:
            mod = __import__(mod_name, fromlist=["run"])
            if name == "sharing" and "coldstart" in outs:
                # measured costs come only from this invocation's coldstart
                outs[name] = mod.run(outs["coldstart"])
            else:
                outs[name] = mod.run()
            wall = time.perf_counter() - t0
            cells[name] = {"ok": True, "wall_clock_s": wall}
            print(f"# {name}: ok ({wall:.1f}s)", file=sys.stderr)
        except Exception:
            failures += 1
            cells[name] = {"ok": False,
                           "wall_clock_s": time.perf_counter() - t0}
            print(f"# {name}: FAILED\n{traceback.format_exc()}", file=sys.stderr)
    if args.smoke:
        path = save_json("BENCH_smoke", {
            "bench_schema_version": BENCH_SCHEMA_VERSION,
            "smoke": True,
            "cells": cells,
            "headline": _headline(outs),
        })
        print(f"# wrote {path}", file=sys.stderr)
    sys.exit(int(failures > 0))


if __name__ == "__main__":
    main()
