"""The result line a run prints, and the refusals before any work."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

from bench import run as bench_run
from bench.tests._cells import CELL, run

ROOT = Path(__file__).resolve().parents[2]


def test_result_line_has_the_contract_keys():
    out = run()
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"first_result_p50_s", "tokens_per_s",
                                   "pool_bytes", "setup_s"}
    assert out["metrics"]["pool_bytes"]["value"] > 0
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(out)


def test_traced_run_reports_per_layer_metrics_and_breakdown():
    out = run(trace=True)
    assert {"compiles_in_window", "replica_up_ms",
            "engine_step_ms"} <= set(out["metrics"])
    assert "first_result_p50_s" not in out["metrics"]
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(out["breakdown"]["idle_gaps"]) <= 10


def test_metrics_of_a_cell():
    man = bench_run.manifest()
    cell = bench_run.cell_entry(man, CELL)
    assert [m["name"] for m in bench_run.metrics_of(man, cell, False)] == [
        "first_result_p50_s", "tokens_per_s", "pool_bytes", "setup_s"]
    layer = [m["name"] for m in bench_run.metrics_of(man, cell, True)]
    assert {"replica_up_ms", "compiles_in_window", "serve_mfu"} <= set(layer)


def test_no_chip_exits_without_a_result(capsys):
    assert bench_run.main(["--workload", CELL, "--seed", "1",
                           "--seconds", "1", "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""


def test_without_the_program_exits_without_a_result(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELL, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
