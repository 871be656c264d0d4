"""The cell ``train_mellum2_8k_ep4share`` at toy size on the CPU: its command
end to end, its deck against its own arithmetic, its configuration file
against the published one, its readers on a trace made by hand; and what the
comparison must catch planted in it: under the timed path through
``fault_hook`` (a state returned unchanged, half the rows left out, a
capacity that drops pairs, the window ignored, YaRN left out), and in the
reference put in the program's place (``control``), fp8 rounding among them.
Each has to read not correct under the cell's own limits. One file, so that
one worker of the test run carries this cell's toy runs."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.harness import common, contract, lm_flops, train_cell  # noqa: E402

CELL = "train_mellum2_8k_ep4share"
CONFIG = "benchmark/configs/mellum2_12b_ep4share.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# what a CPU has nothing to read for: no device plane in its trace, no peaks
# in the table, no memory statistics
CPU_BLIND = {"train_mfu_pct", "train_device_idle_pct", "hbm_peak_gb.train",
             "idle_in_data_wait_pct", "idle_in_loader_fetch_pct",
             "idle_unattributed_pct"}


def bindings():
    return common.load_module(common.load_json(CONFIG)["reference"], "bench_ref_mellum2")


def test_command_prints_every_reader_the_contract_names(capsys):
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 29), "--seconds", "2",
                   "--trace", "1", "--toy", "1"])
    cap = capsys.readouterr()
    assert rc == 0, cap.err[-2000:]
    line = json.loads(cap.out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    man = common.manifest()
    due = set(contract.readers_of(man, CELL))
    assert {"step_ms", "loader_cache_hit_pct", "loader_padding_pct",
            "setup_restore_s", "window_compiles.train"} <= due
    assert not {"mha_roofline.train", "loader_read_ms"} & due
    assert set(line["metrics"]) == due - CPU_BLIND
    assert line["metrics"]["loader_padding_pct"]["value"] == 0.0
    assert line["compared"]["frames_per_cycle_gap"] == {"value": 0.0, "limit": 0.0}
    assert line["compared"]["route_flip_share"]["value"] == 0.0   # float32 toy
    assert line["compared"]["pairs_held_gap"]["value"] <= 0.05
    # the choices came from the timed step, and the trainer has its own
    # ``make_train_step`` back
    from speakingstyle_tpu.training import trainer
    assert trainer.make_train_step.__module__ == "speakingstyle_tpu.training.trainer"
    assert line["compared"]["grad_diff"]["value"] < 1e-4
    assert contract.reader_problems(man, ROOT) == []


# -- the deck, the configuration, the counts ---------------------------------

@pytest.mark.parametrize("toy", [True, False], ids=["toy", "timed"])
def test_deck_fills_its_rows_exactly_whatever_the_seed(tmp_path, toy):
    cfg = common.sized(common.load_json(CONFIG), toy)
    traffic = common.sized(common.load_json(
        "benchmark/traffic/train_lm_packed_8k.json"), toy)
    ref = bindings()
    m = cfg["model"]["decoder_lm"]
    lengths = ref.document_lengths(traffic["deck"], m["seq_len"])
    assert int(lengths.sum()) + len(lengths) == traffic["deck"]["rows"] * m["seq_len"]
    assert lengths.min() >= 1 and lengths.max() <= m["seq_len"]
    if toy:
        return
    assert (lengths.min(), int(np.median(lengths))) == (66, 1000)
    assert traffic["deck"]["rows"] == 64 and traffic["batch_size"] == 4
    decks = []
    for seed in (5, 2 ** 31 + 9):
        out = str(tmp_path / str(seed))
        info = ref.write_corpus(out, cfg, traffic, seed)
        assert info["frames_per_cycle"] == 4 * 4 * 8192
        names = [ln.split("|") for ln in open(os.path.join(out, "train.txt"))]
        ids = [np.load(os.path.join(out, "tokens", n + ".npy")) for n, _ in names]
        assert [len(a) for a in ids] == [int(n) for _, n in names]
        assert min(a.min() for a in ids) >= 1 and max(a.max() for a in ids) < 24576
        decks.append(sorted(len(a) for a in ids))
        flat = np.concatenate(ids)
        # Zipf of exponent 1: id 1 about twice id 2, and a tenth of all ids
        share = (flat == 1).mean()
        assert 0.08 < share < 0.11 and 1.6 < share / (flat == 2).mean() < 2.5
    assert decks[0] == decks[1] == sorted(lengths)


def test_flip_share_counts_what_the_other_side_chose_and_rows_never_routed():
    ref = bindings()
    theirs = [np.array([[[0, 1], [2, 3]], [[4, 5], [6, 7]]])]       # [rows, T, k]
    assert ref.flip_share(theirs, theirs) == 0.0
    swapped = [theirs[0][..., ::-1]]                                # the order is free
    assert ref.flip_share(swapped, theirs) == 0.0
    one_off = [np.array([[[0, 1], [2, 9]], [[4, 5], [6, 7]]])]
    assert ref.flip_share(one_off, theirs) == pytest.approx(1 / 8)
    assert ref.flip_share([theirs[0][:1]], theirs) == pytest.approx(0.5)
    hp = {"lo": 0, "held": 4, "experts": 8}
    assert ref.held_counts(hp, theirs).tolist() == [[1, 1, 1, 1]]


def test_configuration_keeps_every_published_number_but_its_cut():
    body = common.load_json(CONFIG)
    assert body["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert body["published"] == {"num_hidden_layers": 28, "num_experts": 64,
                                 "vocab_size": 98304}
    assert (body["num_hidden_layers"], body["num_experts"], body["vocab_size"]) == (
        4, 16, 24576)
    for key in ("deployment", "assumed", "precision", "control_precision", "toy"):
        assert body[key], key
    lm = body["model"]["decoder_lm"]
    # the program's block says the same: the router keeps its 64 outputs and
    # its 8 experts a token; what is held is the share
    assert (lm["num_experts"], lm["experts_held"], lm["num_experts_per_tok"]) == (64, 16, 8)
    assert (lm["vocab_size"], lm["vocab_held"], lm["num_hidden_layers"]) == (98304, 24576, 4)
    for key, value in body.items():
        if key in lm and key not in body["reduced"]:
            assert lm[key] == value, key
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    row = next(json.loads(ln) for ln in open(CATALOG) if "Mellum2-12B-A2.5B" in ln)
    assert body["source"].startswith(row["source_url"]) and len(body["source"]) <= 200
    for key, value in row["config"].items():
        if key not in body["reduced"]:
            assert body[key] == value, key     # nested groups whole, widths all


def test_operation_counts_are_the_issues():
    lm = common.load_json(CONFIG)["model"]["decoder_lm"]
    parts = lm_flops.forward_flops_per_token(lm, 8192)
    assert parts["projections"] == pytest.approx(170e6, rel=0.01)
    assert parts["experts"] == pytest.approx(99.1e6, rel=0.01)     # 2 pairs a token
    assert parts["head"] == pytest.approx(113.2e6, rel=0.01)
    # the unmasked scores only: the triangle of the full layer, three bands
    full = 4 * 128 * 32 * (8192 + 1) / 2
    band = 4 * 128 * 32 * (1024 * 1025 / 2 + (8192 - 1024) * 1024) / 8192
    assert parts["attention_core"] == pytest.approx(full + 3 * band)
    assert lm_flops.unmasked_keys(8, 8) == lm_flops.unmasked_keys(8) == 4.5
    assert lm_flops.unmasked_keys(6, 2) == pytest.approx((1 + 2 * 5) / 6)
    step = lm_flops.train_step_flops(lm, 4, 8192)
    assert step == pytest.approx(3 * 32768 * sum(parts.values()))
    assert 47e12 < step < 50e12
    assert bindings().cycle_flops(common.load_json(CONFIG), common.load_json(
        "benchmark/traffic/train_lm_packed_8k.json")) == pytest.approx(4 * step)


# -- the readers on a trace made by hand -------------------------------------

def hand_trace():
    """One device, two executions of the step program; microsecond events
    under the module paths the program's names give them."""
    top = "jit(step_fn)/jvp(DecoderLM)/layers_0/"
    back = ("jit(step_fn)/transpose(jvp(DecoderLM))/layers_0/jvp(DecoderLM)/layers_0/"
            "checkpoint/rematted_computation/")
    ev = lambda name, start, dur, op: [name, start, dur, {"tf_op": op}]
    ops = [
        ev("while.1", 0, 900_000, top + "moe/while"),                      # spans the next four
        ev("fusion.1", 0, 100_000, top + "moe/while/body/closed_call/router/dot_general"),
        ev("fusion.2", 100_000, 200_000, top + "moe/while/body/closed_call/dispatch/gather"),
        ev("call.1", 300_000, 400_000, top + "moe/while/body/closed_call/experts/pallas_call"),
        ev("fusion.3", 700_000, 200_000, top + "moe/while/body/closed_call/combine/gather"),
        ev("call.2", 1_000_000, 500_000, top + "self_attn/core/pallas_call"),
        ev("fusion.4", 1_500_000, 100_000, top + "self_attn/q_proj/dot_general"),
        ev("call.3", 1_600_000, 1_000_000, back + "self_attn/core/pallas_call"),
        ev("fusion.5", 2_600_000, 400_000, "jit(step_fn)/lm_head/while/body/dot_general"),
        ["copy.7", 3_000_000, 100_000],                                    # the compiler's own
    ]
    return {"devices": [{"XLA Ops": ops}], "host": {"python3": [
        ["train_dispatch", 0, 9_000], ["train_sync", 10_000, 900_000],
        ["train_dispatch", 1_400_000, 9_000]]}}


def test_module_paths_are_read_by_their_components():
    op = "jit(step_fn)/transpose(jvp(DecoderLM))/layers_1/moe/while/body/experts/mul"
    assert lm_flops.under(op, "moe") and lm_flops.under(op, "moe", "experts")
    assert lm_flops.under(op, "DecoderLM", "layers_1")
    assert not lm_flops.under(op, "experts", "moe")        # the order is the path's
    assert not lm_flops.under(op, "layers_10") and not lm_flops.under(op, "oe")
    assert not lm_flops.under("jit(step_fn)/smoe/experts_held/x", "moe", "experts")


def test_readers_on_a_trace_made_by_hand():
    trace = hand_trace()
    assert lm_flops.traced_steps(trace) == 2
    events = [{"moe_pairs_held": 262144.0, "moe_expert_tokens_max": 9000.0,
               "moe_expert_tokens_mean": 4500.0},
              {"moe_pairs_held": 262144.0, "moe_expert_tokens_max": 8000.0,
               "moe_expert_tokens_mean": 4000.0}]
    ctx = {"workload": CELL, "trace": trace, "events": events,
           "device": {"busy_s": 3.1e-3, "window_s": 3.2e-3},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    got = {name: read(ctx) for name, read in lm_flops.LAYER_READINGS.items()}
    # the loop's own event spans its body and is no work of its own
    assert [e[0] for e in lm_flops.leaf_events(trace)][:2] == ["fusion.1", "fusion.2"]
    assert got["moe_step_share_pct"] == pytest.approx(100 * 0.9 / 3.1)
    assert got["moe_route_share_pct"] == pytest.approx(100 * 0.5 / 3.1)
    assert got["moe_load_max_over_mean"] == 2.0
    lm = common.load_json(CONFIG)["model"]["decoder_lm"]
    ops, nbytes = lm_flops.grouped_products_step(lm, 262144.0)
    assert ops == 3 * 262144 * 3 * 2 * 2304 * 896 and ops / 197e12 > nbytes / 819e9
    assert got["moe_gmm_roofline.train"] == pytest.approx(
        100 * 2 * ops / 197e12 / 0.4e-3)
    ops, _ = lm_flops.attention_core_step(lm, 4, 8192)
    assert got["attn_blocked_roofline.train"] == pytest.approx(
        100 * 2 * ops / 197e12 / 1.5e-3)


def test_readings_read_nothing_where_the_program_wrote_nothing():
    bare = {"trace": {"devices": [], "host": {}}, "events": [{"step": 8}],
            "device": {"busy_s": 1.0}, "peaks": None}
    assert [read(bare) for read in lm_flops.LAYER_READINGS.values()] == [None] * 5
    # not listed yet (PERF.md section 7): the manifest's contract holds as it is
    man = common.manifest()
    assert not set(lm_flops.LAYER_READINGS) & {m["name"] for m in man["per_layer"]}
    assert contract.reader_problems(man, ROOT) == []


def test_builders_tool_prints_the_readings_beside_the_commands_line(capsys):
    tool = common.load_module("benchmark/tools/lm_layers.py", "bench_tool_lm_layers")
    rc = tool.main(["--workload", CELL, "--seed", "7", "--seconds", "1",
                    "--trace", "1", "--toy", "1"])
    cap = capsys.readouterr()
    assert rc == 0, cap.err[-2000:]
    said = dict(ln[len("lm_layer "):].split(": ") for ln in cap.err.splitlines()
                if ln.startswith("lm_layer "))
    assert set(said) == set(lm_flops.LAYER_READINGS)
    assert float(said["moe_load_max_over_mean"]) >= 1.0
    # a CPU's trace has no device plane: nothing to read for the four others
    assert [v for k, v in said.items() if k != "moe_load_max_over_mean"] == ["None"] * 4
    assert train_cell.read_per_layer.__module__ == "benchmark.harness.train_cell"
    line = json.loads(cap.out.strip().splitlines()[-1])
    assert line["correct"] is True and not set(said) & set(line["metrics"])


# -- the comparison's arithmetic, a leaf at a time ----------------------------

@pytest.mark.parametrize("case", ["sound", "a_leaf_moved_double", "no_gradient_on_a_leaf"])
def test_leafwise_comparison_reads_what_train_compare_reads(case):
    """``lm_compare`` is ``train_compare.compare_training`` taken a leaf at a
    time in pieces: the same readings to float64 rounding, the same leaves
    named worst and left out, with pieces smaller than a leaf."""
    from benchmark.harness import lm_compare, train_compare

    rng = np.random.default_rng(3)
    shapes = {"a/kernel": (37, 5), "a/bias": (5,), "b/kernel": (64, 9), "c/scale": ()}
    draw = lambda scale: {k: (scale * rng.standard_normal(s)).astype(np.float32)
                          for k, s in shapes.items()}
    flatten = lambda tree: tree
    p0, ref_grad = draw(1.0), draw(0.1)
    if case == "no_gradient_on_a_leaf":
        ref_grad["a/bias"] *= np.float32(1e-6)
    near = lambda tree, eps: {k: (v * (1 + eps * rng.standard_normal(v.shape)))
                              .astype(np.float32) for k, v in tree.items()}
    moved = lambda tree, by: {k: (p0[k] - by * np.sign(tree[k])).astype(np.float32)
                              for k in tree}
    ref_out = ([2.0, 1.9], ref_grad, [moved(ref_grad, 1e-3), moved(ref_grad, 2e-3)])
    rec = type("R", (), {})()
    rec.losses = [2.001, 1.902]
    prog_grad = near(ref_grad, 1e-2)
    rec.first_mu = {k: v * np.float32(0.1) for k, v in prog_grad.items()}
    rec.params_after = [moved(prog_grad, 1e-3), moved(prog_grad, 2e-3)]
    if case == "a_leaf_moved_double":
        rec.params_after[0]["b/kernel"] = (
            2 * rec.params_after[0]["b/kernel"] - p0["b/kernel"])
    want, want_notes = train_compare.compare_training(
        flatten, rec, ref_out, p0, 0.9, ref_grad)
    old_piece, lm_compare.PIECE = lm_compare.PIECE, 16
    try:
        got, notes = lm_compare.compare_training(flatten, rec, ref_out, p0, 0.9)
    finally:
        lm_compare.PIECE = old_piece
    assert set(got) == set(want) - {"grad_diff_excess"}
    for name, value in got.items():
        assert value == pytest.approx(want[name], rel=1e-9, abs=1e-15), name
    assert notes["left_out"] == want_notes["left_out"]
    assert (notes["left_out"] == ["a/bias"]) == (case == "no_gradient_on_a_leaf")
    assert [w[0] for w in notes["worst"]["change_gap_step1"]] == [
        w[0] for w in want_notes["worst"]["change_gap_step1"]]
    if case == "a_leaf_moved_double":
        assert notes["worst"]["change_gap_step1"][0][0] == "b/kernel"
        assert got["change_gap_step1"] > 0.5
    assert set(notes["leaf_norms"]) == set(want_notes["leaf_norms"])
    for leaf, row in notes["leaf_norms"].items():
        for part in ("grad", "step1", "last"):
            assert row[part] == pytest.approx(want_notes["leaf_norms"][leaf][part],
                                              rel=1e-9, nan_ok=True) \
                or row[part] == want_notes["leaf_norms"][leaf][part]
    assert notes["losses"] == want_notes["losses"]


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
        def broken(state, arrays, rng):
            tokens = arrays["tokens"]
            return step(state, {"tokens": tokens[: len(tokens) // 2]}, rng)
        return broken
    return hook


def capacity(monkeypatch):
    """Pairs past ``tokens * k / experts`` an expert get no row."""
    from speakingstyle_tpu.models import mellum
    from speakingstyle_tpu.ops import expert_dispatch

    real = expert_dispatch.plan

    def dropping(idx, lo, n_held, tm):
        import jax.numpy as jnp

        p = real(idx, lo, n_held, tm)
        cap = idx.shape[0] * idx.shape[1] // 8
        local = idx - lo
        first = (jnp.cumsum(p.tile_expert[:, None] == jnp.arange(n_held), 0) == 1)
        start = jnp.argmax(first, axis=0) * tm       # each expert's first row
        rank = p.pair_row - start[jnp.clip(local, 0, n_held - 1)]
        kept = (p.pair_row < p.row_pair.shape[0]) & (rank < cap)
        pair_row = jnp.where(kept, p.pair_row, p.row_pair.shape[0])
        row_pair = jnp.full_like(p.row_pair, -1).at[pair_row.reshape(-1)].set(
            jnp.arange(idx.size, dtype=jnp.int32), mode="drop")
        return p._replace(pair_row=pair_row, row_pair=row_pair)

    def hook(step):
        monkeypatch.setattr(mellum.expert_dispatch, "plan", dropping)
        return step
    return hook


def no_window(monkeypatch):
    from speakingstyle_tpu.models import mellum

    real = mellum.blocked_attention

    def hook(step):
        monkeypatch.setattr(
            mellum, "blocked_attention",
            lambda q, k, v, window=None, **kw: real(q, k, v, window=None, **kw))
        return step
    return hook


def no_yarn(monkeypatch):
    from speakingstyle_tpu.configs.config import RopeConfig
    from speakingstyle_tpu.models import mellum

    real = mellum.rope_tables

    def hook(step):
        monkeypatch.setattr(
            mellum, "rope_tables",
            lambda rope, d, t: real(RopeConfig(rope_theta=rope.rope_theta), d, t))
        return step
    return hook


@pytest.mark.parametrize("fault", [unchanged_state, half_rows, capacity, no_window,
                                   no_yarn], ids=lambda f: f.__name__)
def test_planted_fault_reads_not_correct(monkeypatch, capsys, fault):
    rc = train_cell.run(CELL, 31, 2.0, False, toy=True,
                        fault_hook=fault(monkeypatch))
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    over = [k for k, v in line["compared"].items()
            if v["value"] is None or v["value"] > v["limit"]]
    print(fault.__name__, {k: v["value"] for k, v in line["compared"].items()})
    assert line["correct"] is False and over
    assert set(over) <= {"loss_gap_step1", "loss_gap_step2", "grad_norm_gap",
                         "grad_diff", "change_gap_step1", "change_gap_median",
                         "route_flip_share", "pairs_held_gap",
                         "frames_per_cycle_gap"}
    if fault is unchanged_state:
        assert {"change_gap_step1", "change_gap_median", "grad_diff"} <= set(over)


@pytest.mark.parametrize("control", ["float8_e4m3fn", "half_batch", "capacity",
                                     "no_window", "no_yarn"])
def test_control_in_the_references_place_is_not_correct(control):
    readings, notes = train_cell.run(CELL, 5, 1.0, False, toy=True,
                                     limits_only=True, control=control)
    limits = common.load_json(f"benchmark/limits/{CELL}.json")["limits"]
    mine = {k: v for k, v in limits.items() if k in readings}
    assert common.judge(readings, mine)[0]
    assert not common.judge(notes["control"][control], mine)[0]
