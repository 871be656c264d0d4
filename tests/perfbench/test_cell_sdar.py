"""The cell ``train_sdar_4k_bd4_ep8share`` at toy size on the CPU: its command
end to end, plain and traced; its deck, its configuration file against the
published one, its count of operations; its five per-layer readings on a
trace made by hand and, as the ``per_layer`` entries a ``benchmark`` PR will
list (``ENTRIES``), through the harness's own ``read_per_layer`` on a patched
manifest; and what the comparison must catch planted in it: under the timed
path through ``fault_hook`` (every control of the cell but the rounding: half
the rows, the triangle over both streams, a noised token that sees its own
clean block, the q/k norm left out, weights of 1, the loss on the clean half
too; and a state returned unchanged), and in the reference put in the
program's place (``control``), fp8 rounding among them. Each has to read not
correct under the cell's own limits. One file, so that one worker of the test
run carries this cell's toy runs."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.harness import (blockdiff_flops, common, contract, lm_flops,  # noqa: E402
                               train_cell)

CELL = "train_sdar_4k_bd4_ep8share"
CONFIG = "benchmark/configs/sdar_30b_ep8share.json"
TRAFFIC = "benchmark/traffic/train_lm_blockdiff_4k.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CPU_BLIND = {"train_mfu_pct", "train_device_idle_pct", "hbm_peak_gb.train",
             "idle_in_data_wait_pct", "idle_in_loader_fetch_pct",
             "idle_unattributed_pct"}
COMPARED = {"loss_gap_step1", "loss_gap_step2", "grad_norm_gap", "grad_diff",
            "change_gap_step1", "change_gap_median", "route_flip_share",
            "pairs_held_gap", "frames_per_cycle_gap", "window_compiles"}
# what the next ``benchmark`` PR lists (PERF.md section 7): each with its
# reader in ``blockdiff_flops.LAYER_READINGS`` under the same name
ENTRIES = [
    {"name": "attn_blockdiff_roofline.train", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "kernels", "moves": "train_frames_per_s",
     "workloads": [CELL]},
    {"name": "attn_tiles_seen_pct", "unit": "%", "better": "higher",
     "source": "program_counter", "layer": "kernels", "moves": "train_frames_per_s",
     "workloads": [CELL]},
    {"name": "moe_gmm_roofline.train", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "kernels", "moves": "train_frames_per_s",
     "workloads": [CELL]},
    {"name": "moe_step_share_pct", "unit": "%", "better": "lower",
     "source": "device_trace", "layer": "model step", "moves": "train_frames_per_s",
     "workloads": [CELL]},
    {"name": "attn_step_share_pct", "unit": "%", "better": "lower",
     "source": "device_trace", "layer": "model step", "moves": "train_frames_per_s",
     "workloads": [CELL]},
]


def bindings():
    return common.load_module(common.load_json(CONFIG)["reference"], "bench_ref_sdar")


@pytest.mark.parametrize("trace", [0, 1], ids=["plain", "traced"])
def test_command_runs_the_cell_and_reads_correct(capsys, trace):
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 2), "--seconds", "2",
                   "--trace", str(trace), "--toy", "1"])
    cap = capsys.readouterr()
    assert rc == 0, cap.err[-2000:]
    line = json.loads(cap.out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["compared"]) == COMPARED
    assert line["compared"]["frames_per_cycle_gap"] == {"value": 0.0, "limit": 0.0}
    assert line["compared"]["window_compiles"]["value"] == 0.0
    assert line["compared"]["route_flip_share"]["value"] == 0.0   # float32 toy
    assert line["compared"]["grad_diff"]["value"] < 1e-4
    man = common.manifest()
    if not trace:
        assert set(line["metrics"]) == {"setup_s", "train_frames_per_s"}
        # a frame is a corpus token: 2 steps x 4 rows x 64 tokens a cycle
        assert "cycles of 2 steps" in cap.err
        return
    due = set(contract.readers_of(man, CELL))
    assert {"step_ms", "loader_cache_hit_pct", "loader_padding_pct", "train_mfu_pct",
            "setup_restore_s", "window_compiles.train"} <= due
    assert not {"mha_roofline.train", "loader_read_ms"} & due
    assert set(line["metrics"]) == due - CPU_BLIND
    assert line["metrics"]["loader_padding_pct"]["value"] == 0.0
    from speakingstyle_tpu.training import trainer
    assert trainer.make_train_step.__module__ == "speakingstyle_tpu.training.trainer"
    assert contract.reader_problems(man, ROOT) == []


# -- the deck, the configuration, the counts ---------------------------------

@pytest.mark.parametrize("toy", [True, False], ids=["toy", "timed"])
def test_deck_fills_its_rows_and_never_draws_the_mask_token(tmp_path, toy):
    cfg = common.sized(common.load_json(CONFIG), toy)
    traffic = common.sized(common.load_json(TRAFFIC), toy)
    ref = bindings()
    m = cfg["model"]["decoder_lm"]
    lengths = ref.document_lengths(traffic["deck"], m["seq_len"])
    assert int(lengths.sum()) + len(lengths) == traffic["deck"]["rows"] * m["seq_len"]
    assert lengths.min() >= 1 and lengths.max() <= m["seq_len"]
    out = str(tmp_path / "corpus")
    info = ref.write_corpus(out, cfg, traffic, 2 ** 31 + 9)
    names = [ln.split("|") for ln in open(os.path.join(out, "train.txt"))]
    ids = [np.load(os.path.join(out, "tokens", n + ".npy")) for n, _ in names]
    assert [len(a) for a in ids] == [int(n) for _, n in names]
    assert min(a.min() for a in ids) >= 1 and max(a.max() for a in ids) < m["mask_id"]
    assert sorted(len(a) for a in ids) == sorted(lengths)
    # corpus tokens a cycle, not the 2L positions the layers run over
    assert info["frames_per_cycle"] == traffic["log_step"] * traffic["batch_size"] \
        * m["seq_len"]
    if toy:
        return
    assert (lengths.min(), int(np.median(lengths)), lengths.max()) == (65, 501, 4025)
    assert (traffic["deck"]["rows"], traffic["batch_size"], m["seq_len"],
            m["block_length"]) == (64, 4, 4096, 4)
    assert info["frames_per_cycle"] == 4 * 16384 and m["mask_id"] == 18991
    flat = np.concatenate(ids)
    share = (flat == 1).mean()          # Zipf of exponent 1 over 18,990 ids
    assert 0.085 < share < 0.11 and 1.6 < share / (flat == 2).mean() < 2.5


def test_configuration_keeps_every_published_number_but_its_cut():
    body = common.load_json(CONFIG)
    assert body["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert body["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                 "vocab_size": 151936}
    assert (body["num_hidden_layers"], body["num_experts"], body["vocab_size"]) == (
        5, 16, 18992)
    for key in ("deployment", "assumed", "precision", "control_precision", "toy"):
        assert body[key], key
    for key in ("block_length", "noise schedule", "q/k norm", "mask_id", "weights"):
        assert key in body["assumed"], key
    lm = body["model"]["decoder_lm"]
    assert (lm["num_experts"], lm["experts_held"], lm["num_experts_per_tok"]) == (128, 16, 8)
    assert (lm["vocab_size"], lm["vocab_held"], lm["num_hidden_layers"]) == (151936, 18992, 5)
    assert (lm["objective"], lm["block_length"], lm["qk_norm"], lm["seq_len"]) == (
        "block_diffusion", 4, True, 4096)
    assert lm["rope_parameters"]["full_attention"]["rope_theta"] == body["rope_theta"]
    for key, value in body.items():
        if key in lm and key not in body["reduced"] + ["sliding_window"]:
            assert lm[key] == value, key
    # the program's preset, cut as the file says, is the file's block
    from speakingstyle_tpu.configs.config import DecoderLMConfig, _build, load_config
    preset = load_config(preset=body["preset"]).model.decoder_lm
    cut = _build(DecoderLMConfig, lm)
    for key in ("hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads",
                "moe_intermediate_size", "num_experts", "num_experts_per_tok",
                "rms_norm_eps", "rope_parameters", "objective", "block_length",
                "qk_norm", "vocab_size", "model_type", "seq_len"):
        assert getattr(preset, key) == getattr(cut, key), key
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    row = next(json.loads(ln) for ln in open(CATALOG) if "SDAR-30B-A3B-Chat" in ln)
    assert body["source"].startswith(row["source_url"]) and len(body["source"]) <= 200
    for key, value in row["config"].items():
        if key not in body["reduced"]:
            assert body[key] == value, key     # every number, widths all


def test_operation_counts_are_the_issues():
    lm = common.load_json(CONFIG)["model"]["decoder_lm"]
    assert blockdiff_flops.seen_pairs(4096, 4) == 4096 ** 2 + 4 * 4096 == 16_793_600
    parts = blockdiff_flops.forward_flops_per_row(lm, 4096)
    per_position = 2 * 2048 * 40 * 128 + 2 * 4096 * 2048
    assert parts["projections"] == 8192 * 5 * per_position
    assert parts["experts"] == 8192 * 5 * 1.0 * 3 * 2 * 2048 * 768   # 1 pair a position
    assert parts["head"] == 4096 * 2 * 2048 * 18992                  # the noised half
    assert parts["attention_core"] == 5 * 4 * 128 * 32 * 16_793_600
    step = blockdiff_flops.train_step_flops(lm, 4, 4096)
    assert step == pytest.approx(3 * 4 * sum(parts.values()))
    assert 43.5e12 < step < 44.0e12                                   # the issue's 43.7
    assert bindings().cycle_flops(common.load_json(CONFIG), common.load_json(
        TRAFFIC)) == pytest.approx(4 * step)
    ops, nbytes = blockdiff_flops.attention_core_step(lm, 4, 4096)
    assert ops == 3 * 4 * parts["attention_core"]
    assert nbytes == 5 * 2 * 4 * 8192 * (64 + 8) * 128 * 2
    assert ops / 197e12 > nbytes / 819e9          # compute-bound


# -- the readings: on a trace made by hand, and as per_layer entries ----------

def hand_trace():
    top = "jit(step_fn)/jvp(DecoderLM)/layers_0/"
    back = ("jit(step_fn)/transpose(jvp(DecoderLM))/layers_0/jvp(DecoderLM)/layers_0/"
            "checkpoint/rematted_computation/")
    ev = lambda name, start, dur, op: [name, start, dur, {"tf_op": op}]
    ops = [
        ev("while.1", 0, 900_000, top + "moe/while"),
        ev("fusion.1", 0, 500_000, top + "moe/while/body/closed_call/dispatch/gather"),
        ev("call.1", 500_000, 400_000, top + "moe/while/body/closed_call/experts/pallas_call"),
        ev("call.2", 1_000_000, 500_000, top + "self_attn/core/pallas_call"),
        ev("fusion.4", 1_500_000, 100_000, top + "self_attn/q_norm/mul"),
        ev("call.3", 1_600_000, 1_000_000, back + "self_attn/core/pallas_call"),
        ev("fusion.5", 2_600_000, 400_000, "jit(step_fn)/lm_head/while/body/dot_general"),
    ]
    return {"devices": [{"XLA Ops": ops}], "host": {"python3": [
        ["train_dispatch", 0, 9_000], ["train_dispatch", 1_400_000, 9_000]]}}


def hand_ctx():
    return {"workload": CELL, "trace": hand_trace(),
            "events": [{"moe_pairs_held": 163840.0}, {"moe_pairs_held": 163840.0}],
            "device": {"busy_s": 3.0e-3, "window_s": 3.2e-3},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def test_readings_on_a_trace_made_by_hand():
    got = {name: read(hand_ctx()) for name, read in blockdiff_flops.LAYER_READINGS.items()}
    lm = common.load_json(CONFIG)["model"]["decoder_lm"]
    ops, _ = blockdiff_flops.attention_core_step(lm, 4, 4096)
    assert got["attn_blockdiff_roofline.train"] == pytest.approx(
        100 * 2 * ops / 197e12 / 1.5e-3)
    # 80 tiles of 512 x 512 visited for the 16,793,600 pairs seen
    assert got["attn_tiles_seen_pct"] == pytest.approx(100 * 16_793_600 / (80 * 512 * 512))
    assert 80.0 < got["attn_tiles_seen_pct"] < 80.2
    assert got["attn_step_share_pct"] == pytest.approx(100 * 1.6 / 3.0)
    assert got["moe_step_share_pct"] == pytest.approx(100 * 0.9 / 3.0)
    ops, nbytes = lm_flops.grouped_products_step(lm, 163840.0)
    assert got["moe_gmm_roofline.train"] == pytest.approx(
        100 * 2 * max(ops / 197e12, nbytes / 819e9) / 0.4e-3)


def test_readings_read_nothing_where_the_program_wrote_nothing():
    bare = {"workload": CELL, "trace": {"devices": [], "host": {}},
            "events": [{"step": 8}], "device": {"busy_s": 1.0}, "peaks": None}
    assert [read(bare) for read in blockdiff_flops.LAYER_READINGS.values()] == [None] * 5
    man = common.manifest()     # not listed yet: the manifest's contract holds as it is
    assert not set(blockdiff_flops.LAYER_READINGS) & {m["name"] for m in man["per_layer"]}
    assert contract.reader_problems(man, ROOT) == []


@pytest.mark.parametrize("side", ["change", "parent"])
def test_entries_go_through_the_harness_on_a_patched_manifest(monkeypatch, side):
    """The five entries are the manifest's kind (names, units, a layer
    ``PERF.md`` has, ``moves`` what the cell reports, the cell alone listed),
    and ``read_per_layer`` reports them from readers found by their names; on
    a trace in which nothing ran under the module paths (the parent's side of
    a new cell) the line leaves them out and nothing is raised."""
    import re

    man = common.manifest()
    assert [e["name"] for e in ENTRIES] == list(blockdiff_flops.LAYER_READINGS)
    layers = {m["layer"] for m in man["per_layer"]}
    e2e = {m["name"]: m for m in man["end_to_end"]}
    for e in ENTRIES:
        assert set(e) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert re.match(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$", e["name"])
        assert e["layer"] in layers and CELL in e2e[e["moves"]]["workloads"]
        assert e["unit"] == "%" and e["workloads"] == [CELL]
    patched = {**man, "per_layer": man["per_layer"] + ENTRIES}
    monkeypatch.setattr(common, "manifest", lambda: patched)
    real = common.load_module

    def load(rel, name):
        metric = os.path.basename(rel)[:-3]
        if metric in blockdiff_flops.LAYER_READINGS:
            return type("M", (), {"read": staticmethod(
                blockdiff_flops.LAYER_READINGS[metric])})
        return real(rel, name)

    monkeypatch.setattr(common, "load_module", load)
    assert set(blockdiff_flops.LAYER_READINGS) <= set(contract.readers_of(patched, CELL))
    assert not set(blockdiff_flops.LAYER_READINGS) & set(
        contract.readers_of(patched, "train_ljspeech_b200"))
    ctx = {**hand_ctx(), "window_s": 4.0, "log_step": 4, "cycles_s": [2.0, 2.0]}
    if side == "parent":
        ctx["trace"] = {"devices": [{"XLA Ops": [["fusion.1", 0, 10, {"tf_op": "jit(f)/x"}]]}],
                        "host": {}}
    out = train_cell.read_per_layer(CELL, ctx)
    mine = {k: v for k, v in out.items() if k in blockdiff_flops.LAYER_READINGS}
    if side == "parent":
        assert mine == {}
    else:
        assert set(mine) == set(blockdiff_flops.LAYER_READINGS)
        assert all(v["unit"] == "%" and 0 < v["value"] for v in mine.values())


def test_builders_tool_prints_the_readings_beside_the_commands_line(capsys):
    tool = common.load_module("benchmark/tools/blockdiff_layers.py",
                              "bench_tool_blockdiff_layers")
    rc = tool.main(["--workload", CELL, "--seed", "31", "--seconds", "1",
                    "--trace", "1", "--toy", "1"])
    cap = capsys.readouterr()
    assert rc == 0, cap.err[-2000:]
    said = dict(ln[len("lm_layer "):].split(": ") for ln in cap.err.splitlines()
                if ln.startswith("lm_layer "))
    assert set(said) == set(blockdiff_flops.LAYER_READINGS)
    assert set(said.values()) == {"None"}     # a CPU's trace has no device plane
    assert train_cell.read_per_layer.__module__ == "benchmark.harness.train_cell"
    line = json.loads(cap.out.strip().splitlines()[-1])
    assert line["correct"] is True and not set(said) & set(line["metrics"])


# -- faults planted under the timed path -------------------------------------

def unchanged_state(monkeypatch):
    def hook(step):
        import jax
        import jax.numpy as jnp

        def broken(state, arrays, rng):
            kept = jax.tree_util.tree_map(jnp.copy, state)
            new, losses = step(state, arrays, rng)
            return kept.replace(step=new.step), losses
        return broken
    return hook


def half_rows(monkeypatch):
    def hook(step):
        return lambda state, arrays, rng: step(
            state, {k: v[: len(v) // 2] for k, v in arrays.items()}, rng)
    return hook


def unweighted(monkeypatch):
    def hook(step):
        def broken(state, arrays, rng):
            weight = (np.asarray(arrays["weight"]) > 0).astype(np.float32)
            return step(state, {**arrays, "weight": weight}, rng)
        return broken
    return hook


def causal_mask(monkeypatch):
    """The triangle over both streams: a noised token sees its clean self."""
    from speakingstyle_tpu.models import mellum

    real = mellum.blocked_attention

    def hook(step):
        monkeypatch.setattr(mellum, "blocked_attention",
                            lambda q, k, v, mask=None, **kw: real(q, k, v, **kw))
        return step
    return hook


def own_clean_block(monkeypatch):
    """A noised block also sees its own clean block."""
    from speakingstyle_tpu.ops import blocked_attention as ba

    def seen(self, T):
        import jax.numpy as jnp

        L, c = T // 2, self.block_length
        i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
        qn, kn, qb, kb = i < L, j < L, (i % L) // c, (j % L) // c
        return ((qn == kn) & (qb == kb)) | (~kn & (qb >= kb))

    def hook(step):
        monkeypatch.setattr(ba.BlockDiffusion, "seen", seen)
        return step
    return hook


def no_qk_norm(monkeypatch):
    from speakingstyle_tpu.models import mellum

    real = mellum.rms_norm

    def hook(step):   # the heads' norms are the ones over [B, T, H, D]
        monkeypatch.setattr(mellum, "rms_norm", lambda x, scale, eps:
                            x if x.ndim == 4 else real(x, scale, eps))
        return step
    return hook


def clean_head(monkeypatch):
    """The loss also on the clean half, each token under its weight."""
    from speakingstyle_tpu.models import mellum

    def both_halves(hidden, head, tokens, weight, chunk=mellum.LOSS_CHUNK):
        import jax.numpy as jnp

        twice = lambda a: jnp.concatenate([a, a], axis=1)
        B, L = tokens.shape
        return mellum.weighted_cross_entropy(
            hidden, head, twice(tokens).reshape(-1),
            twice(weight).astype(jnp.float32).reshape(-1), chunk) / (B * L)

    def hook(step):
        monkeypatch.setattr(mellum, "noised_half", lambda x: x)
        monkeypatch.setattr(mellum, "block_diffusion_loss", both_halves)
        return step
    return hook


@pytest.mark.parametrize("fault", [unchanged_state, half_rows, causal_mask,
                                   own_clean_block, no_qk_norm, unweighted, clean_head],
                         ids=lambda f: f.__name__)
def test_planted_fault_reads_not_correct(monkeypatch, capsys, fault):
    rc = train_cell.run(CELL, 16, 2.0, False, toy=True, fault_hook=fault(monkeypatch))
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    over = [k for k, v in line["compared"].items()
            if v["value"] is None or v["value"] > v["limit"]]
    print(fault.__name__, {k: v["value"] for k, v in line["compared"].items()})
    assert line["correct"] is False and over
    assert set(over) <= COMPARED - {"window_compiles"}
    if fault is unchanged_state:
        assert {"change_gap_step1", "change_gap_median", "grad_diff"} <= set(over)


def test_controls_in_the_references_place_are_not_correct():
    """One run, every control of the cell: the program's own readings pass
    the cell's limits and each control's do not."""
    controls = ["float8_e4m3fn", "half_batch", *bindings().FAULTS]
    assert set(bindings().FAULTS) == {"causal_mask", "own_clean_block", "no_qk_norm",
                                      "unweighted", "clean_head"}
    readings, notes = train_cell.run(CELL, 11, 1.0, False, toy=True,
                                     limits_only=True, control=",".join(controls))
    limits = common.load_json(f"benchmark/limits/{CELL}.json")["limits"]
    mine = {k: v for k, v in limits.items() if k in readings}
    assert common.judge(readings, mine)[0]
    for name in controls:
        got = notes["control"][name]
        assert not common.judge(got, {k: v for k, v in mine.items() if k in got})[0], name
