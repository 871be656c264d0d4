"""The token corpus of the ``decoder_lm`` family: documents read once and
kept (the sample cache), packed into full rows with nothing lost, and driven
through ``run_training`` itself: events, counters, checkpoints."""

import json
import os

import numpy as np
import pytest
import yaml

from speakingstyle_tpu import obs
from speakingstyle_tpu.configs.config import PRESET_DIR, load_config
from speakingstyle_tpu.data import CacheBudget, PackedBatcher, TokenDataset

TOY_LM = dict(vocab_size=512, vocab_held=128, hidden_size=64, num_hidden_layers=4,
              num_attention_heads=4, num_key_value_heads=2, head_dim=16,
              sliding_window=8, num_experts=8, num_experts_per_tok=2,
              moe_intermediate_size=32, experts_held=2, seq_len=32)
LENGTHS = [5, 17, 40, 9, 31, 63, 12, 25] * 8


@pytest.fixture
def lm_config(tmp_path):
    """The preset's three files, cut to toy size, over a corpus of 64
    documents under ``tmp_path``."""
    corpus = tmp_path / "corpus"
    (corpus / "tokens").mkdir(parents=True)
    rng = np.random.default_rng(0)
    lines = []
    for i, n in enumerate(LENGTHS):
        np.save(corpus / "tokens" / f"d{i:04d}.npy",
                rng.integers(1, 128, n).astype(np.int32))
        lines.append(f"d{i:04d}|{n}")
    (corpus / "train.txt").write_text("\n".join(lines) + "\n")
    (corpus / "val.txt").write_text("\n".join(lines[:8]) + "\n")
    preset = os.path.join(PRESET_DIR, "Mellum2-12B-A2.5B")
    bodies = {n: yaml.safe_load(open(os.path.join(preset, n + ".yaml")))
              for n in ("preprocess", "model", "train")}
    bodies["preprocess"]["path"]["preprocessed_path"] = str(corpus)
    bodies["model"]["compute_dtype"] = "float32"
    bodies["model"]["decoder_lm"].update(TOY_LM)
    bodies["train"]["path"] = {k: str(tmp_path / k)
                               for k in ("ckpt_path", "log_path", "result_path")}
    bodies["train"]["step"].update(log_step=2, val_step=4, save_step=4, total_step=6)
    paths = {}
    for name, body in bodies.items():
        paths[name] = str(tmp_path / f"{name}.yaml")
        with open(paths[name], "w") as f:
            yaml.safe_dump(body, f)
    cfg = load_config(**paths)
    object.__setattr__(cfg, "yaml_paths", paths)  # for the CLI's -p/-m/-t
    return cfg


def test_packer_fills_rows_and_loses_no_id(lm_config):
    reg = obs.MetricsRegistry()
    ds = TokenDataset("train.txt", lm_config, cache=CacheBudget())
    batcher = PackedBatcher(ds, seq_len=32, eod_id=0, seed=3, registry=reg)
    first = list(batcher.epoch())
    assert all(b.tokens.shape == (4, 32) and b.tokens.dtype == np.int32 for b in first)
    assert all(b.frames_real == b.frames_padded == 128 and b.shape == (4, 32)
               for b in first)
    ids = sum(LENGTHS) + len(LENGTHS)             # every document and its eod
    assert len(first) == ids // 128 and batcher._stream_len == ids % 128
    # the rows are the documents in the epoch's order, end to end
    packed = np.concatenate([b.tokens.reshape(-1) for b in first])
    names = [n for b in first for n in b.ids]
    stream = np.concatenate([np.append(np.load(os.path.join(
        ds.root, "tokens", n + ".npy")), 0) for n in names])
    assert (packed == stream[: packed.size]).all()
    assert (packed == 0).sum() >= len(first)      # documents end inside rows
    # what was left is carried into the next epoch, and the second epoch
    # reads no file
    misses = ds.cache_misses
    second = list(batcher.epoch())
    assert ds.cache_misses == misses == len(LENGTHS) and ds.cache_hits == len(LENGTHS)
    assert len(first) + len(second) == 2 * ids // 128
    assert reg.value("loader_cache_hits_total") == len(LENGTHS)


def test_run_training_trains_saves_and_resumes_the_decoder_lm(lm_config):
    from speakingstyle_tpu.training.trainer import run_training

    reg = obs.MetricsRegistry()
    state = run_training(lm_config, mesh=None, registry=reg)
    assert int(state.step) == 6 and state.batch_stats == {}
    log_path = lm_config.train.path.log_path
    with open(os.path.join(log_path, "events.jsonl")) as f:
        events = [json.loads(line) for line in f]
    steps = [e for e in events if e["event"] == "train_step"]
    assert [e["step"] for e in steps] == [2, 4, 6]
    for e in steps:
        # what the benchmark's readers take from an event, for positions as
        # for frames, and the routing's counters beside them
        assert {"mel_frames_per_sec", "steps_per_sec", "frames_real", "frames_padded",
                "loader_fetch_s", "loader_read_s", "loader_collate_s",
                "loader_blocked_s", "loader_cache_hits", "loader_cache_misses",
                "total_loss", "moe_pairs_held", "moe_pairs_dropped",
                "moe_expert_tokens_max", "moe_expert_tokens_mean",
                "moe_tiles_used"} <= set(e)
        assert e["frames_real"] == e["frames_padded"] == 128
        assert e["moe_pairs_dropped"] == 0 and e["moe_pairs_held"] > 0
        assert e["moe_expert_tokens_max"] >= e["moe_expert_tokens_mean"] > 0
        assert "_moe" not in e and "_finite" not in e
    assert steps[-1]["total_loss"] < steps[0]["total_loss"] < 1.05 * np.log(128)
    assert reg.value("moe_pairs_dropped_total") == 0
    assert reg.value("train_frames_real_total") == 6 * 128
    assert [e["step"] for e in events if e["event"] == "checkpoint_save"] == [4, 6]
    assert any(e["event"] == "val" for e in events)
    ring = obs.trace.get_span_ring().spans()
    packs = [s for s in ring if s["name"] == "loader_pack"]
    assert packs and packs[-1]["fields"]["rows"] == 16
    loads = [s for s in ring if s["name"] == "moe_load"]
    assert loads and len(loads[-1]["fields"]["tokens_max"]) == 4
    assert loads[-1]["fields"]["pairs_dropped"] == [0, 0, 0, 0]
    # restored from the checkpoint (an empty batch_stats and all), it goes on
    resumed = run_training(lm_config, mesh=None, registry=obs.MetricsRegistry(),
                           restore_step=-1, max_steps=8)
    assert int(resumed.step) == 8


def test_cli_trains_the_decoder_lm_from_its_three_files(lm_config, capsys):
    from speakingstyle_tpu.__main__ import main

    paths = lm_config.yaml_paths
    main(["train", "-p", paths["preprocess"], "-m", paths["model"],
          "-t", paths["train"], "--max_steps", "2", "--data_parallel", "1"])
    assert "training finished at step 2" in capsys.readouterr().out
    assert os.path.isdir(os.path.join(lm_config.train.path.ckpt_path, "2"))


def test_train_step_events_count_the_tiles_the_plans_used(lm_config, monkeypatch):
    """``moe_tiles_used`` on a ``train_step`` event is the sum over layers and
    rows of ``plan.n_used``, per step of the window, as the plans of the
    choices those steps returned give it; the ``moe_load`` ring span holds
    the last step's beside the worst case the buffers are sized for."""
    import jax

    from speakingstyle_tpu.models import mellum
    from speakingstyle_tpu.ops import expert_dispatch
    from speakingstyle_tpu.training import trainer

    choices = []
    made = trainer.make_train_step

    def make(*args, **kwargs):
        inner = made(*args, **kwargs)

        def step(state, arrays, rng):
            state, losses = inner(state, arrays, rng)
            choices.append(losses["_choices"])
            return state, losses
        return step

    monkeypatch.setattr(trainer, "make_train_step", make)
    trainer.run_training(lm_config, mesh=None, registry=obs.MetricsRegistry(),
                         max_steps=4)
    lm = lm_config.model.decoder_lm
    tm = mellum.tile_rows(lm, TOY_LM["seq_len"])

    def tiles(step_choices):           # [layers, rows, T, k]
        return sum(int(expert_dispatch.plan(row, lm.expert_offset, lm.n_experts_held,
                                            tm).n_used[0])
                   for layer in jax.device_get(step_choices) for row in layer)

    used = [tiles(c) for c in choices]
    assert len(used) == 4
    with open(os.path.join(lm_config.train.path.log_path, "events.jsonl")) as f:
        steps = [e for e in map(json.loads, f) if e["event"] == "train_step"]
    assert [e["moe_tiles_used"] for e in steps] == [
        (used[0] + used[1]) / 2, (used[2] + used[3]) / 2]
    load = [s for s in obs.trace.get_span_ring().spans()
            if s["name"] == "moe_load"][-1]["fields"]
    worst = 4 * 4 * expert_dispatch.worst_tiles(32 * 2, 2, tm)
    assert load["moe_tiles_worst"] == worst == 160
    assert load["moe_tiles_used"] == used[-1] and 4 * 4 * 2 <= used[-1] <= worst
