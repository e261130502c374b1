"""BENCHMARK.json against the benchmark's contract, and every name in it
resolved to a file of the harness."""
import json
import re
from pathlib import Path
from typing import Dict, List

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in MAN["workloads"]]
E2E = {m["name"]: m for m in MAN["end_to_end"]}
# What a configuration may cut, by the model-configs guide's section 4: the
# depth, and the chip's share of a layer (its routed experts, its heads, its
# slice of the vocabulary). Every other key, every width among them, is kept.
DEPTH = ("num_hidden_layers", "first_k_dense_replace")
ROUTED_EXPERTS = ("n_routed_experts", "num_experts", "num_local_experts")
HEADS = ("num_attention_heads", "num_key_value_heads")
REDUCIBLE = DEPTH + ROUTED_EXPERTS + HEADS + ("vocab_size",)


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


def cut_faults(reduced: List[str], data: Dict) -> List[str]:
    """How the cut that ``reduced`` lists breaks the guide's rules, given the
    configuration file's ``data``: a key outside :data:`REDUCIBLE`, a missing
    ``published`` value or ``deployment``, a held count not below its
    published one, or a floor not kept."""
    if not reduced:
        return []
    faults = [f"{k}: not a depth, nor a held count of routed experts or "
              f"heads, nor the vocabulary" for k in reduced if k not in REDUCIBLE]
    faults += [f"{k}: not a name" for k in reduced if not NAME.match(k)]
    pub = data.get("published")
    if not isinstance(pub, dict) or any(k not in pub for k in reduced):
        return faults + ["published: must give each reduced key's published value"]
    dep = data.get("deployment")
    if not (isinstance(dep, str) and dep.strip()):
        faults.append("deployment: must say over how many chips each layer is divided")
    held = {k: data.get(k) for k in reduced if k in REDUCIBLE}
    for k, v in held.items():
        if not (isinstance(v, int) and 0 <= v < pub[k]):
            faults.append(f"{k}: held {v} is no cut of the published {pub[k]}")
    if faults:
        return faults
    for k in held.keys() & set(ROUTED_EXPERTS):
        if held[k] < min(8, pub[k]):
            faults.append(f"{k}: {held[k]} routed experts held, fewer than 8")
    if "vocab_size" in held and 8 * held["vocab_size"] < pub["vocab_size"]:
        faults.append(f"vocab_size: {held['vocab_size']} rows, under an eighth "
                      f"of {pub['vocab_size']}")
    if "first_k_dense_replace" in held and held["first_k_dense_replace"] < 1:
        faults.append("first_k_dense_replace: the leading dense layers count once")
    if held.keys() & set(DEPTH):
        layers, dense = (data["num_hidden_layers"],
                         data.get("first_k_dense_replace", 0))
        pub_after = (pub.get("num_hidden_layers", layers)
                     - pub.get("first_k_dense_replace", dense))
        if layers - dense < min(4, pub_after):
            faults.append(f"num_hidden_layers: {layers - dense} layers after the "
                          f"leading dense ones, fewer than 4")
    return faults


def config_faults(conf: Dict, data: Dict) -> List[str]:
    """What breaks the contract in a manifest entry ``conf`` of ``configs``
    and its configuration file's ``data``."""
    faults = []
    if set(conf) != {"name", "source", "file", "reduced", "why"}:
        faults.append(f"manifest keys {sorted(conf)}")
    if not conf["file"].startswith("bench/"):
        faults.append(f"file {conf['file']} outside bench/")
    if data.get("reduced") != conf["reduced"]:
        faults.append("reduced: the file's differs from the manifest's")
    return faults + cut_faults(conf["reduced"], data)


@pytest.mark.parametrize("conf", MAN["configs"], ids=lambda c: c["name"])
def test_config_file(conf):
    path = ROOT / conf["file"]
    assert path.is_file()
    assert config_faults(conf, json.loads(path.read_text())) == []
    assert any(c["config"] == conf["name"] for c in MAN["workloads"])


def moonlight_share(**change):
    """Moonlight-16B-A3B cut as the guide's floors allow: one dense layer and
    four expert layers, 8 of 64 routed experts, an eighth of the vocabulary,
    as one of eight chips that share each layer holds it."""
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    data = {"hidden_size": 2048, "moe_intermediate_size": 1408,
            "intermediate_size": 11264, "kv_lora_rank": 512,
            "qk_rope_head_dim": 64, "num_experts_per_tok": 6,
            "n_shared_experts": 2, "num_attention_heads": 16,
            "num_key_value_heads": 16, "first_k_dense_replace": 1,
            "num_hidden_layers": 5, "n_routed_experts": 8, "vocab_size": 20480,
            "published": {"num_hidden_layers": 27, "n_routed_experts": 64,
                          "vocab_size": 163840, "first_k_dense_replace": 1,
                          "num_attention_heads": 16, "num_key_value_heads": 16},
            "deployment": "each layer divided over 8 chips: 8 experts and an "
                          "eighth of the vocabulary on each"}
    data.update(change)
    data["reduced"] = data.pop("reduced", reduced)
    conf = {"name": "moonlight-16b-a3b", "source": "https://huggingface.co/"
            "moonshotai/Moonlight-16B-A3B/blob/main/config.json",
            "file": "bench/configs/moonlight-16b-a3b.json",
            "reduced": data["reduced"], "why": "latent attention, experts"}
    return conf, data


def test_depth_and_share_cut_passes():
    assert config_faults(*moonlight_share()) == []


@pytest.mark.parametrize("key,held,published,accepted", [
    ("num_hidden_layers", 5, 27, True),
    ("first_k_dense_replace", 1, 3, True),
    ("n_routed_experts", 8, 64, True),
    ("num_experts", 8, 60, True),
    ("num_local_experts", 8, 32, True),
    ("num_attention_heads", 2, 16, True),
    ("num_key_value_heads", 2, 16, True),
    ("vocab_size", 20480, 163840, True),
    ("hidden_size", 1024, 2048, False),
    ("intermediate_size", 5632, 11264, False),
    ("moe_intermediate_size", 704, 1408, False),
    ("shared_expert_intermediate_size", 704, 5632, False),
    ("kv_lora_rank", 256, 512, False),
    ("q_lora_rank", 256, 1536, False),
    ("qk_rope_head_dim", 32, 64, False),
    ("head_dim", 64, 128, False),
    ("num_experts_per_tok", 2, 6, False),
    ("n_shared_experts", 1, 2, False),
    ("sliding_window", 1024, 4096, False),
    ("max_position_embeddings", 4096, 8192, False),
])
def test_reduced_key_allow_list(key, held, published, accepted):
    reduced = ["num_hidden_layers"] + ([key] if key != "num_hidden_layers" else [])
    conf, data = moonlight_share(reduced=reduced)
    data[key] = held
    data["published"][key] = published
    assert (config_faults(conf, data) == []) is accepted


@pytest.mark.parametrize("key,floor", [
    ("n_routed_experts", 8),
    ("vocab_size", 163840 // 8),
    ("num_hidden_layers", 5),         # 4 after the one leading dense layer
])
def test_floor_fails_one_step_below(key, floor):
    assert config_faults(*moonlight_share(**{key: floor})) == []
    faults = config_faults(*moonlight_share(**{key: floor - 1}))
    assert faults and all(f.startswith(key) for f in faults), faults


def test_first_dense_layer_is_kept():
    reduced = ["num_hidden_layers", "first_k_dense_replace"]
    conf, data = moonlight_share(reduced=reduced, first_k_dense_replace=1,
                                 num_hidden_layers=6)
    data["published"]["first_k_dense_replace"] = 3
    assert config_faults(conf, data) == []
    data["first_k_dense_replace"] = 0
    assert config_faults(conf, data) != []


@pytest.mark.parametrize("drop", ["published", "deployment"])
def test_a_cut_states_its_source_and_deployment(drop):
    conf, data = moonlight_share()
    del data[drop]
    assert any(f.startswith(drop) for f in config_faults(conf, data))


def test_an_uncut_configuration_needs_no_published_values():
    conf, data = moonlight_share(reduced=[])
    del data["published"], data["deployment"]
    assert config_faults(conf, data) == []


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
