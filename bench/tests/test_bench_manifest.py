"""BENCHMARK.json against the benchmark's contract, and every name in it
resolved to a file of the harness."""
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in MAN["workloads"]]
E2E = {m["name"]: m for m in MAN["end_to_end"]}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection",
               "head", "expansion", "experts_per_tok")


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "bench/run.py"]
    assert MAN["paths"] == ["bench"]
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51
    assert len(json.dumps(MAN).encode()) <= 64 * 1024


def test_full_check_fits_with_24_cells():
    runs = 2 + 14 * 24
    assert runs * (MAN["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("entry", MAN["configs"] + MAN["workloads"]
                         + MAN["end_to_end"] + MAN["per_layer"],
                         ids=lambda e: e["name"])
def test_names_and_units(entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]
            assert "\t" not in entry[key]


@pytest.mark.parametrize("cell", MAN["workloads"], ids=lambda c: c["name"])
def test_cell_resolves(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] == 1
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    confs = {c["name"]: c for c in MAN["configs"]}
    conf = json.loads((ROOT / confs[cell["config"]]["file"]).read_text())
    assert (BENCH / "systems" / f"{conf['system']}.py").is_file()
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    assert (BENCH / "traffic" / f"{mix['kind']}.py").is_file()
    assert (BENCH / "loops" / f"{mix['loop']}.py").is_file()
    limits = json.loads((BENCH / "limits" / f"{cell['name']}.json").read_text())
    assert set(limits) == {"token_gap", "logit_rel_err"}
    assert all(v > 0 for v in limits.values())


@pytest.mark.parametrize("conf", MAN["configs"], ids=lambda c: c["name"])
def test_config_file(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    path = ROOT / conf["file"]
    assert path.is_file() and conf["file"].startswith("bench/")
    data = json.loads(path.read_text())
    assert data["reduced"] == conf["reduced"]
    for key in conf["reduced"]:
        assert NAME.match(key)
        assert not key.endswith(("_dim", "_rank"))
        assert not any(w in key for w in WIDTH_WORDS)
    assert any(c["config"] == conf["name"] for c in MAN["workloads"])


@pytest.mark.parametrize("metric", MAN["end_to_end"] + MAN["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_has_a_reader(metric):
    assert (BENCH / "metrics" / f"{metric['name']}.py").is_file()
    for cell in metric.get("workloads", []):
        assert cell in CELLS


@pytest.mark.parametrize("metric", MAN["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_bounds(metric):
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.25
    if metric["name"] == "setup_s":
        assert metric["bound"] == 0.25


@pytest.mark.parametrize("metric", MAN["per_layer"], ids=lambda m: m["name"])
def test_per_layer_moves_a_metric_its_cells_report(metric):
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    target = E2E[metric["moves"]]
    for cell in metric["workloads"]:
        assert cell in cells_of(target)
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_enough(cell):
    e2e = [m["name"] for m in MAN["end_to_end"] if cell in cells_of(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell in m["workloads"] for m in MAN["per_layer"])


def test_layers_are_named_alike():
    layers = {m["layer"] for m in MAN["per_layer"]}
    assert layers <= {"migration", "executables", "serving engine",
                      "model step", "device"}


def test_paths_hold_only_the_benchmark():
    for path in BENCH.rglob("*"):
        rel = path.relative_to(ROOT).as_posix()
        if "__pycache__" in rel:
            continue
        assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel
