"""A rehearsal of the addition a later PR makes: a second configuration, a
traffic mix, a cell with its limits and one new reader, laid by new files and
new entries alone over a *copy* of the benchmark under ``tmp_path`` (no file
of the tree is touched), then run through the benchmark's own command at toy
size on the CPU.

On the tree of PR 27 this fails, once for each of four obstacles:
1. any appended ``per_layer`` entry failed a test that pinned the list to
   twenty-four names and its last twelve; here the list's contract
   (``contract.reader_problems``) holds at any length, and did not exist;
2. every reader of a training window listed ``train_ljspeech_b200`` alone,
   so a second training cell's traced line had ``compile_s`` and
   ``cache_hit_pct`` and nothing of its loop: the set asserted below;
3. ``reduced: ["num_hidden_layers"]``, the catalog's key for depth, was
   refused for containing "hidden" (``contract.names_a_width`` goes by what
   the key counts);
4. the driver wrote ``ljspeech``'s corpus, counted its operations and ran its
   comparison whatever the configuration's file named: the second
   configuration's own ``second_gap`` was never compared and ``correct``
   came out false against a limits file that holds it.
"""

import copy
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.harness import common, contract  # noqa: E402

CELL = "train_second_toy"

# the second configuration's own bindings: ljspeech's equations (it trains
# through the same program), a corpus writer, a count and a comparison of its
# own, each of which says that it ran
REFERENCE = '''"""A throwaway configuration's bindings (tests/perfbench/test_rehearsal.py)."""
import sys

from benchmark.harness import train_compare, trafficgen
from benchmark.reference import fs2

hyper, init_params, init_batch_stats = fs2.hyper, fs2.init_params, fs2.init_batch_stats


def write_corpus(out_dir, cfg, traffic, seed):
    print("second: write_corpus", file=sys.stderr)
    spec = {**traffic["deck"], "batch_size": traffic["batch_size"],
            "pitch_range": cfg["model"]["pitch_range"],
            "energy_range": cfg["model"]["energy_range"]}
    return trafficgen.write_corpus(out_dir, spec, seed, cfg["model"]["n_mel_channels"])


def cycle_flops(cfg, traffic):
    print("second: cycle_flops", file=sys.stderr)
    return 1e9 * cfg["num_hidden_layers"]


def compare(cfg, hp, opt, params0, stats0, rec, seed, controls=(), limits=None):
    readings, notes = train_compare.first_steps(
        fs2, hp, opt, params0, stats0, rec, seed,
        block_rows=cfg["reference_block_rows"], controls=controls, limits=limits)
    readings["second_gap"] = 0.0
    return readings, notes
'''

READER = '''"""Cycles of the window that the reader was handed."""


def read(ctx):
    return float(len(ctx["events"])) if ctx["events"] else None
'''

# what a CPU has nothing to read for: no device plane in its trace, no peaks
# in the table, no memory statistics
CPU_BLIND = {"train_mfu_pct", "train_device_idle_pct", "hbm_peak_gb.train",
             "idle_in_data_wait_pct", "idle_in_loader_fetch_pct",
             "idle_unattributed_pct"}


def write_json(path, body):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(body, f, indent=1)


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """A copy of the benchmark with the addition laid over it, and the
    harness's look-ups pointed at it."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "speakingstyle_tpu"),
               os.path.join(root, "speakingstyle_tpu"))
    man = copy.deepcopy(common.manifest())
    before = json.dumps(man, sort_keys=True)

    config = common.load_json("benchmark/configs/ljspeech.json")
    config.update(name="second", reference="benchmark/configs/second_reference.py",
                  num_hidden_layers=1, reduced=["num_hidden_layers"])
    write_json(os.path.join(root, "benchmark/configs/second.json"), config)
    with open(os.path.join(root, "benchmark/configs/second_reference.py"), "w") as f:
        f.write(REFERENCE)
    traffic = common.load_json("benchmark/traffic/train_ljspeech_lengths.json")
    traffic["why_this_mix"] = "a rehearsal's deck"
    write_json(os.path.join(root, "benchmark/traffic/second_lengths.json"), traffic)
    limits = common.load_json("benchmark/limits/train_ljspeech_b200.json")["limits"]
    write_json(os.path.join(root, f"benchmark/limits/{CELL}.json"),
               {"limits": {**limits, "second_gap": 0.0}})
    with open(os.path.join(root, "benchmark/metrics/second_cycles.py"), "w") as f:
        f.write(READER)

    man["configs"].append({
        "name": "second", "source": config["source"],
        "file": "benchmark/configs/second.json", "reduced": config["reduced"],
        "why": "a rehearsal's second configuration"})
    man["workloads"].append({
        "name": CELL, "config": "second", "traffic": "second_lengths",
        "chips": 1, "why": "a rehearsal's second training cell"})
    for m in man["end_to_end"]:
        if m["name"] == "train_frames_per_s":
            m["workloads"] = m["workloads"] + [CELL]
    man["per_layer"].append({
        "name": "second_cycles", "unit": "count", "better": "higher",
        "source": "program_span", "layer": "train loop",
        "moves": "train_frames_per_s", "workloads": [CELL]})
    # new entries only: what was there is there as it was
    was = json.loads(before)
    assert [c for c in man["configs"] if c["name"] != "second"] == was["configs"]
    assert man["per_layer"][:-1] == was["per_layer"]
    write_json(os.path.join(root, "BENCHMARK.json"), man)

    monkeypatch.setattr(common, "ROOT", root)
    # the command puts the compile cache's place into the environment
    for name in ("JAX_COMPILATION_CACHE_DIR", "JAX_COMPILATION_CACHE_MAX_SIZE"):
        monkeypatch.setenv(name, os.environ.get(name, ""))
    import speakingstyle_tpu.ops.dropout as d

    monkeypatch.setattr(d, "dropout", lambda x, *a, **k: x)  # test_cells_train
    return root


def run_cell(capsys, trace):
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 28),
                   "--seconds", "2", "--trace", str(trace), "--toy", "1"])
    cap = capsys.readouterr()
    assert rc == 0, cap.err[-2000:]
    return json.loads(cap.out.strip().splitlines()[-1]), cap.err


def test_a_second_configuration_goes_in_by_files_and_entries_alone(
    checkout, capsys
):
    man = common.manifest()
    assert [c["name"] for c in man["workloads"]][-1] == CELL
    # the contract of the list holds with the addition in it (obstacles 1, 3)
    assert contract.reader_problems(man, checkout) == []
    assert not [k for c in man["configs"] for k in c["reduced"]
                if contract.names_a_width(k)]

    line, err = run_cell(capsys, 0)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert set(line["metrics"]) == {"setup_s", "train_frames_per_s"}
    assert line["metrics"]["train_frames_per_s"]["value"] > 0
    # its own corpus writer and its own comparison decided (obstacle 4)
    assert "second: write_corpus" in err
    assert line["compared"]["second_gap"] == {"value": 0.0, "limit": 0.0}
    assert line["correct"] is True and line["attempted"] > 0

    line, err = run_cell(capsys, 1)
    assert line["correct"] is True and "second: cycle_flops" in err
    # every reader that lists no cell, and its own; not ljspeech's (obstacle 2)
    due = set(contract.readers_of(man, CELL))
    assert {"second_cycles", "step_ms", "data_wait_ms", "loader_cache_hit_pct",
            "step_dispatch_ms", "setup_restore_s", "window_compiles.train"} <= due
    assert not {"loader_read_ms", "mha_roofline.train"} & due
    assert set(line["metrics"]) == due - CPU_BLIND
    assert line["metrics"]["second_cycles"]["value"] >= 1
    # and the first cell is read as it was
    assert set(contract.readers_of(man, "train_ljspeech_b200")) == {
        m["name"] for m in man["per_layer"]} - {"second_cycles"}
