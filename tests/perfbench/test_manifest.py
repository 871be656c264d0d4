"""The manifest against its contract, and the harness's data files against
the manifest: names, units, `moves`, one file per configuration, traffic mix
and per-layer metric. Nothing here holds the manifest to a length, an order
or a last entry: a later PR adds to it by files and entries alone."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import sys

sys.path.insert(0, ROOT)
from benchmark.harness import common, contract  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    RAW = json.load(f)
MAN = common.manifest()

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = {c["name"]: c for c in MAN["workloads"]}
E2E = {m["name"]: m for m in MAN["end_to_end"]}


def reporting(metric):
    """The cells that report an end-to-end metric."""
    return contract.reporting(MAN, metric)


def test_top_level_keys_and_sizes():
    assert set(RAW) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    used = {w["config"] for w in RAW["workloads"]}
    assert {c["name"] for c in RAW["configs"]} == used  # each used by a cell
    cells = {w["name"] for w in RAW["workloads"]}
    for m in RAW["end_to_end"] + RAW["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    assert 1 <= MAN["run_seconds"] <= 51 and isinstance(MAN["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in MAN["paths"])
    # a full check with all 24 cells fits the driver's budget
    assert (2 + 14 * 24) * (MAN["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("entry", MAN["configs"] + MAN["workloads"]
                         + MAN["end_to_end"] + MAN["per_layer"],
                         ids=lambda e: e["name"])
def test_names_units_and_keys(entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
                and "\t" not in entry[key]


@pytest.mark.parametrize("metric", MAN["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
    assert 0.01 <= metric["bound"] <= 0.1
    assert metric["source"] in ("host_clock", "device_trace")
    assert reporting(metric) <= set(CELLS)


@pytest.mark.parametrize("metric", MAN["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_moves_what_its_cells_report(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer", "moves",
                           "workloads"}
    target = E2E[metric["moves"]]
    assert set(metric.get("workloads", reporting(target))) <= reporting(target)
    # the reader is a file of its own, found by the metric's name
    path = os.path.join(ROOT, "benchmark", "metrics", metric["name"] + ".py")
    assert os.path.isfile(path)
    assert "def read(ctx)" in open(path).read()
    if metric["name"].endswith("_roofline") or "_roofline." in metric["name"] \
            or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


@pytest.mark.parametrize("cell", MAN["workloads"], ids=lambda c: c["name"])
def test_cell_reports_setup_one_more_and_a_layer(cell):
    mine = [m["name"] for m in MAN["end_to_end"] if cell["name"] in reporting(m)]
    assert "setup_s" in mine and len(mine) >= 2
    assert any(cell["name"] in m.get("workloads", CELLS) for m in MAN["per_layer"])
    assert cell["chips"] in (1, 4) and cell["config"] in {c["name"] for c in MAN["configs"]}
    for rel in (f"benchmark/traffic/{cell['traffic']}.json",
                f"benchmark/limits/{cell['name']}.json"):
        assert os.path.isfile(os.path.join(ROOT, rel)), rel


@pytest.mark.parametrize("config", MAN["configs"], ids=lambda c: c["name"])
def test_configuration_file_and_its_reference(config):
    assert config["file"].startswith(tuple(p + "/" for p in MAN["paths"]))
    with open(os.path.join(ROOT, config["file"])) as f:
        body = json.load(f)
    assert body["reduced"] == config["reduced"] and len(config["reduced"]) <= 16
    assert body["source"] == config["source"]
    # the plain reference sits beside the configuration and is named in it
    ref = body["reference"]
    assert os.path.dirname(ref) == os.path.dirname(config["file"])
    src = open(os.path.join(ROOT, ref)).read()
    assert "speakingstyle_tpu" not in src.split('"""', 2)[2]
    assert not [k for k in config["reduced"] if contract.names_a_width(k)]
    # what is the configuration's own in a cell comes from that module, and
    # the file says which keys of its model block are the program's
    for name in ("hyper", "init_params", "init_batch_stats", "write_corpus",
                 "cycle_flops", "compare"):
        assert re.search(rf"^(def {name}\(|{name} = )", src, re.M), name
    assert set(body["program_model_keys"]) <= set(body["model"])


@pytest.mark.parametrize("key", [
    "hidden_size", "head_dim", "intermediate_size", "moe_intermediate_size",
    "kv_lora_rank", "q_lora_rank", "conv_filter_size", "encoder_hidden",
    "filter_size", "num_experts_per_tok", "d_model", "qk_rope_head_dim",
    "v_head_dim", "expansion_factor", "ssm_state_size", "n_embd",
    "postnet_embedding_dim", "moe_top_k", "head_size", "n_mel_channels"])
def test_reduced_refuses_a_key_that_names_a_width(key):
    assert contract.names_a_width(key)


@pytest.mark.parametrize("key", [
    "num_hidden_layers", "encoder_layer", "decoder_layer", "num_experts",
    "n_routed_experts", "vocab_size", "num_attention_heads",
    "num_key_value_heads", "conv_layer", "postnet_layers", "num_layers",
    "first_k_dense_replace"])
def test_reduced_admits_a_count_of_layers_experts_heads_or_rows(key):
    assert not contract.names_a_width(key)


def test_every_entry_has_its_reader_and_every_reader_its_entry():
    assert contract.reader_problems(MAN, ROOT) == []


@pytest.mark.parametrize("plant,said", [
    (lambda d, man: open(os.path.join(d, "orphan_ms.py"), "w").write(
        "def read(ctx):\n    return 1.0\n"), "orphan_ms.py: not in per_layer"),
    (lambda d, man: man["per_layer"].append({"name": "ghost_ms"}),
     "ghost_ms: no benchmark/metrics/ghost_ms.py"),
    (lambda d, man: open(os.path.join(d, "step_ms.py"), "w").write(
        "def measure(ctx):\n    return 1.0\n"), "step_ms.py: no read(ctx)"),
    (lambda d, man: man["per_layer"].append(dict(man["per_layer"][0])),
     "listed twice"),
], ids=["orphan_file", "entry_without_file", "file_without_read", "twice"])
def test_reader_contract_names_what_is_wrong(tmp_path, plant, said):
    import copy
    import shutil

    folder = tmp_path / "benchmark" / "metrics"
    shutil.copytree(os.path.join(ROOT, "benchmark", "metrics"), folder,
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = copy.deepcopy(MAN)
    assert contract.reader_problems(man, str(tmp_path)) == []
    plant(str(folder), man)
    wrong = contract.reader_problems(man, str(tmp_path))
    assert len(wrong) == 1 and said in wrong[0]


def test_harness_names_no_model():
    """The shared harness finds what is one configuration's own through the
    configuration: no module of it imports a reference's equations or names a
    model's keys."""
    folder = os.path.join(ROOT, "benchmark", "harness")
    for name in os.listdir(folder):
        if name.endswith(".py"):
            code = open(os.path.join(folder, name)).read()
            assert not re.search(r"reference\W+(import\W+)?fs2", code), name
    code = open(os.path.join(folder, "common.py")).read()
    for key in common.load_json("benchmark/configs/ljspeech.json")["model"]:
        assert f'"{key}"' not in code, key


def test_reference_imports_nothing_of_the_program():
    ref_dir = os.path.join(ROOT, "benchmark", "reference")
    for name in os.listdir(ref_dir):
        if name.endswith(".py"):
            code = open(os.path.join(ref_dir, name)).read()
            assert not re.search(r"^\s*(from|import)\s+speakingstyle_tpu", code, re.M), name
