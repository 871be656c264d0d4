"""CPU rehearsal of ``chip_smoke.py``: the chip's one gate must never pass
without a chip, must keep its parent off jax, and must not discover a typo
on chip time — so its input generation, event-log checks and HTTP client
run here against the tiny widths of
``tests/test_synthesis.py::test_cli_train_smoke``."""

import copy
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_real_smoke_refuses_a_cpu(monkeypatch):
    """``JAX_PLATFORMS=cpu python chip_smoke.py`` exits non-zero, names the
    device guard and prints no result line."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    existed = os.path.exists(chip_smoke.OUT_DIR)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
            capture_output=True, text=True, timeout=300,
        )
    finally:
        if not existed:
            shutil.rmtree(chip_smoke.OUT_DIR, ignore_errors=True)
    assert proc.returncode != 0
    assert "device guard" in proc.stderr
    assert "kernel leg" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_import_and_input_generation_keep_jax_out(tmp_path):
    """The parent's side of the smoke — import, corpus, lexicon, YAMLs,
    reference wav — runs without jax in the process."""
    code = (
        "import os, sys; sys.path.insert(0, sys.argv[1]); import chip_smoke; "
        "inputs = chip_smoke.generate_inputs(chip_smoke.TINY, sys.argv[2]); "
        "os.makedirs(inputs['log_path']); "
        "open(os.path.join(inputs['log_path'], 'events.jsonl'), 'w')"
        ".write('{\"event\": \"note\"}\\n'); "
        "assert chip_smoke.read_events(inputs['log_path']); "
        "assert 'jax' not in sys.modules, 'the smoke parent imported jax'"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code, ROOT, str(tmp_path / "out")],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert (tmp_path / "out" / "cfg" / "train.yaml").exists()


def test_full_size_keeps_the_preset_model_unchanged(tmp_path):
    import yaml

    # the flagship's corpus is ~100 MB; only the YAML derivation is checked
    size = chip_smoke.dataclasses.replace(
        chip_smoke.FULL, n_utts=4, val_utts=1)
    inputs = chip_smoke.generate_inputs(size, str(tmp_path / "out"))
    with open(os.path.join(chip_smoke.PRESET_DIR, "model.yaml"), "rb") as f:
        preset_model = f.read()
    with open(inputs["paths"]["model"], "rb") as f:
        assert f.read() == preset_model

    def load(path):
        with open(path) as f:
            return yaml.safe_load(f)

    trn = load(inputs["paths"]["train"])
    preset_trn = load(os.path.join(chip_smoke.PRESET_DIR, "train.yaml"))
    assert trn["serve"] == {
        "batch_buckets": [1, 4], "src_buckets": [64, 128],
        "mel_buckets": [512, 1000], "style": {"ref_buckets": [512]},
    }
    # beyond paths, the step block and the serve lattice nothing moved
    for key in set(preset_trn) - {"path", "step"}:
        assert trn[key] == preset_trn[key], key
    pre = load(inputs["paths"]["preprocess"])
    preset_pre = load(os.path.join(chip_smoke.PRESET_DIR, "preprocess.yaml"))
    assert pre["preprocessing"] == preset_pre["preprocessing"]


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One tiny run of every leg through the real child processes."""
    out = tmp_path_factory.mktemp("smoke") / "out"
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_PLATFORMS", "cpu")  # the children inherit it
        report = chip_smoke.run_legs(chip_smoke.TINY, str(out), budget_s=600)
    return report, str(out)


def test_rehearsal_passes_every_leg(rehearsal):
    report, out = rehearsal
    size = chip_smoke.TINY
    assert report["kernel"]["device"]["platform"] == "cpu"
    assert all(c["ok"] for c in report["kernel"]["fused_mha"])
    assert report["train"]["backend"] == "cpu"
    assert report["train"]["loss_last"] < report["train"]["loss_first"]
    assert report["pin"]["saved_step"] == size.max_steps + 1
    serve = report["serve"]
    kinds = [r["kind"] for r in serve["requests"]]
    assert kinds.count("sequential") == 3 and kinds.count("stream") == 1
    assert kinds.count("concurrent") == 4 * serve["coalesce_attempts"]
    # acoustic + vocoder + style programs, each with its compile seconds
    assert len(serve["programs"]) == 6
    assert all(p["compile_seconds"] > 0 for p in serve["programs"])
    # every child kept its cache where the environment pointed
    cache = os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert report["kernel"]["cache_dir"] == cache
    assert report["train"]["cache_dir"] == cache
    chip_smoke.print_report(report)  # the verdict printer runs too


@pytest.mark.parametrize("mutate, complaint", [
    (lambda ev: [e for e in ev if not e.get("final")], "final: true"),
    (lambda ev: [e for e in ev if e["event"] != "program_card"],
     "program_card"),
    (lambda ev: [dict(e, partial=True) if e["event"] == "program_card" else e
                 for e in ev], "non-partial"),
    (lambda ev: [e for e in ev if e["event"] != "train_step"][:]
     + [e for e in ev if e["event"] == "train_step"][:1], "train_step"),
    (lambda ev: [dict(e, total_loss=float("nan"))
                 if e["event"] == "train_step" else e for e in ev],
     "non-finite"),
    (lambda ev: [dict(e, backend="tpu") if e["event"] == "train_start" else e
                 for e in ev], "backend"),
])
def test_event_log_checks_name_what_is_missing(rehearsal, mutate, complaint):
    _, out = rehearsal
    events = chip_smoke.read_events(os.path.join(out, "log"))
    chip_smoke.check_train_events(events, chip_smoke.TINY, "cpu")  # intact
    with pytest.raises(chip_smoke.LegFailed) as e:
        chip_smoke.check_train_events(
            mutate(copy.deepcopy(events)), chip_smoke.TINY, "cpu")
    assert e.value.leg == "train" and complaint in e.value.reason


def test_on_a_tpu_the_train_step_must_hold_the_mosaic_kernel(rehearsal):
    """The CPU program card counts no Pallas kernel, which is right on a
    CPU (attention takes its einsum reference there) and a failure on a
    TPU: the fused kernel must be IN the compiled step."""
    _, out = rehearsal
    events = chip_smoke.read_events(os.path.join(out, "log"))
    card = next(e for e in events if e["event"] == "program_card")
    assert card["mosaic_calls"] == 0
    as_tpu = [dict(e, backend="tpu") if e["event"] == "train_start" else e
              for e in events]
    with pytest.raises(chip_smoke.LegFailed, match="einsum path"):
        chip_smoke.check_train_events(as_tpu, chip_smoke.TINY, "tpu")
    with_kernel = [dict(e, mosaic_calls=28) if e["event"] == "program_card"
                   else e for e in as_tpu]
    chip_smoke.check_train_events(with_kernel, chip_smoke.TINY, "tpu")


def test_kernel_check_fails_off_reference():
    chip_smoke.check_kernel({"fused_mha": [{"ok": True}]})
    with pytest.raises(chip_smoke.LegFailed, match="off its reference"):
        chip_smoke.check_kernel({"fused_mha": [{"ok": True}, {"ok": False}]})


def test_cluster_refuses_a_tpu_parent(monkeypatch):
    """One process per chip: ``serve --cluster`` spawns replica processes
    from a parent that already holds the host's chips."""
    import jax

    from speakingstyle_tpu.cli.serve import require_chips_for_cluster

    require_chips_for_cluster(2)  # CPU replicas share the host
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(SystemExit, match="one host per replica"):
        require_chips_for_cluster(2)
