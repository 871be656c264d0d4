"""The training cell's command end to end at toy size on the CPU: once sound,
once for each fault with the timed path broken underneath, and the control
that has to come out as not correct."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import common, train_cell  # noqa: E402

TRAIN = "train_ljspeech_b200"


@pytest.fixture
def no_dropout(monkeypatch):
    """The toy configurations state no dropout, but the program's postnet
    keeps its own 0.5: at four rows its noise would drown every comparison,
    so the toy runs switch the op off in both (the chip runs keep it)."""
    import speakingstyle_tpu.ops.dropout as d

    monkeypatch.setattr(d, "dropout", lambda x, *a, **k: x)


def last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def run_train(capsys, seed, **kw):
    rc = train_cell.run(TRAIN, seed, 2.0, False, toy=True, **kw)
    assert rc == 0
    return last_line(capsys)


def test_train_cell_end_to_end_sound(no_dropout, capsys):
    line = run_train(capsys, 2 ** 31 + 77)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device",
                          "compared"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", "train_frames_per_s"}
    assert line["metrics"]["train_frames_per_s"]["value"] > 0
    assert line["compared"]["frames_per_cycle_gap"]["value"] == 0.0
    assert line["compared"]["grad_norm_gap"]["value"] < 1e-3
    assert line["compared"]["grad_diff_excess"]["value"] < 0.1


def unchanged_state(step):
    import jax
    import jax.numpy as jnp

    def broken(state, arrays, rng):
        kept = jax.tree_util.tree_map(jnp.copy, state)
        new, losses = step(state, arrays, rng)
        return kept.replace(step=new.step), losses
    return broken


def half_batch(step):
    def broken(state, arrays, rng):
        half = arrays["src_lens"].shape[0] // 2
        import jax.numpy as jnp

        cut = {k: jnp.asarray(arrays[k]).at[half:].set(0)
               for k in ("src_lens", "mel_lens", "durations")}
        return step(state, {**arrays, **cut}, rng)
    return broken


@pytest.mark.parametrize("fault", [unchanged_state, half_batch],
                         ids=lambda f: f.__name__)
def test_train_cell_fault_reads_not_correct(no_dropout, capsys, fault):
    line = run_train(capsys, 31, fault_hook=fault)
    assert line["correct"] is False
    over = [k for k, v in line["compared"].items()
            if v["value"] is None or v["value"] > v["limit"]]
    assert over and set(over) <= {"loss_gap_step1", "loss_gap_step2",
                                  "grad_norm_gap", "change_gap_step1",
                                  "change_gap_median", "grad_diff_excess"}
    assert "grad_diff_excess" in over
    if fault is unchanged_state:  # no leaf moved, at either step
        assert {"change_gap_step1", "change_gap_median"} <= set(over)


@pytest.mark.parametrize("control", ["float8_e4m3fn", "half_batch"])
def test_train_control_is_not_correct(no_dropout, control):
    readings, notes = train_cell.run(TRAIN, 5, 1.0, False, toy=True,
                                     limits_only=True,
                                     control=control + ",other_masks")
    limits = common.load_json(f"benchmark/limits/{TRAIN}.json")["limits"]
    mine = {k: v for k, v in limits.items() if k in readings}
    assert common.judge(readings, mine)[0]
    assert not common.judge(notes["control"][control], mine)[0]
    # the configuration's toy block states no dropout: other masks, same draw
    assert common.judge(notes["control"]["other_masks"], mine)[0]


def test_workdir_is_the_process_own_inside_the_checkout(monkeypatch):
    import atexit
    import signal

    kept = []
    monkeypatch.setattr(atexit, "register", lambda f, *a, **k: kept.append((f, a, k)))
    monkeypatch.setattr(signal, "signal", lambda *a: None)
    one, two = common.workdir(), common.workdir()
    try:
        base = os.path.join(ROOT, ".bench_work")
        assert one != two and os.path.dirname(one) == base == os.path.dirname(two)
        assert os.listdir(one) == [] and common.fs_type(one) != ""
    finally:
        for f, a, k in kept:
            f(*a, **k)
    assert not os.path.exists(one) and not os.path.exists(two)


def test_calm_cycles_leave_out_what_the_profiler_holds():
    events = [{"step": s} for s in range(12, 41, 4)]
    cycles = [1.0, 13.0, 1.0, 9.0, 1.0, 1.0, 1.0, 1.0]
    # traced steps 13..20: started in the cycle that ends at 16, stopped in
    # the one that ends at 20 or 24
    calm = train_cell.calm_cycles(events, cycles, 12, 20, 4)
    assert [e["step"] for e, _ in calm] == [12, 28, 32, 36, 40]
    assert max(c for _, c in calm) == 1.0
    # nothing else left: everything
    assert len(train_cell.calm_cycles(events[1:3], cycles[1:3], 12, 20, 4)) == 2
