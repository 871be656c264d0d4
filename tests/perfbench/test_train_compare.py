"""The arithmetic that decides ``correct`` for a training cell, on two Adam
steps made up in numpy: what each compared number of the parameters' change
sees, and what it is blind to by design (PERF.md section 4)."""

import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import common, train_compare  # noqa: E402

B1, B2, LR, EPS = 0.9, 0.98, 1e-4, 1e-9
LIMITS = common.load_json("benchmark/limits/train_ljspeech_b200.json")["limits"]
CHANGE = ("change_gap_step1", "change_gap_median")


def adam(params, grads_by_step, lr=LR, moves=lambda step, name: 1.0):
    """The parameters after each step; ``moves`` scales a leaf's update."""
    mu = {k: np.zeros_like(v) for k, v in params.items()}
    nu = {k: np.zeros_like(v) for k, v in params.items()}
    after, first_mu = [], None
    for t, grads in enumerate(grads_by_step, start=1):
        new = {}
        for k, g in grads.items():
            mu[k] = B1 * mu[k] + (1 - B1) * g
            nu[k] = B2 * nu[k] + (1 - B2) * g * g
            step = lr * (mu[k] / (1 - B1 ** t)) / (np.sqrt(nu[k] / (1 - B2 ** t)) + EPS)
            new[k] = params[k] - moves(t, k) * step
        params = new
        after.append(params)
        first_mu = first_mu or {k: v.copy() for k, v in mu.items()}
    return first_mu, after


@pytest.fixture
def two_steps():
    """Forty leaves of 256 elements; the second gradient is the first plus
    noise, as a sound run's is."""
    rng = np.random.default_rng(28)
    names = [f"layer_{i}/bias" for i in range(40)]
    params0 = {n: rng.normal(size=256) for n in names}
    g1 = {n: rng.normal(size=256) * 1e-3 for n in names}
    g2 = {n: g1[n] + rng.normal(size=256) * 3e-4 for n in names}
    return params0, g1, g2


def readings_of(params0, ref_grads, prog_grads, **prog_kw):
    _, ref_after = adam(params0, ref_grads)
    first_mu, prog_after = adam(params0, prog_grads, **prog_kw)
    rec = types.SimpleNamespace(losses=[1.0, 0.9], first_mu=first_mu,
                                params_after=prog_after)
    ref_out = ([1.0, 0.9], ref_grads[0], ref_after)
    other = {k: v * 1.01 for k, v in ref_grads[0].items()}
    return train_compare.compare_training(
        lambda tree: tree, rec, ref_out, params0, B1, other)[0]


def judged(readings):
    return common.judge(readings, {k: LIMITS[k] for k in CHANGE})


def test_a_sound_run_reads_nought(two_steps):
    params0, g1, g2 = two_steps
    readings = readings_of(params0, [g1, g2], [g1, g2])
    assert judged(readings)[0]
    assert all(readings[k] == 0.0 for k in CHANGE + ("change_norm_gap",))
    assert readings["grad_norm_gap"] < 1e-12  # mu / (1 - b1) rounds


def test_one_leaf_whose_gradient_turns_is_no_fault(two_steps):
    """The case of seed 417887968: a leaf's second gradient keeps its sign on
    one side and turns on the other (one draw of dropout decides it). The worst
    leaf's change after the two steps reads a half; neither compared number
    moves, since the leaf moved, once, at each step."""
    params0, g1, g2 = two_steps
    turned = {**g2, "layer_7/bias": -g1["layer_7/bias"]}
    kept = {**g2, "layer_7/bias": g1["layer_7/bias"]}
    readings = readings_of(params0, [g1, kept], [g1, turned])
    assert 0.45 < readings["change_norm_gap"] < 0.6
    assert judged(readings)[0]
    # the other way round the leaf's own norm is the smaller, and the median
    # leaf's stands under the gap
    readings = readings_of(params0, [g1, turned], [g1, kept])
    assert 0.45 < readings["change_norm_gap"] < 0.6
    assert judged(readings)[0]


@pytest.mark.parametrize("fault, over", [
    ("one_leaf_frozen", "change_gap_step1"),
    ("one_leaf_moved_double", "change_gap_step1"),
    ("state_unchanged", "change_gap_step1"),
    ("state_unchanged", "change_gap_median"),
    ("second_step_left_out", "change_gap_median"),
    ("learning_rate_doubled", "change_gap_median"),
])
def test_a_fault_of_the_update_reads_over_its_limit(two_steps, fault, over):
    params0, g1, g2 = two_steps
    moves = {
        "one_leaf_frozen": lambda t, n: 0.0 if n == "layer_3/bias" else 1.0,
        "one_leaf_moved_double": lambda t, n: 2.0 if n == "layer_3/bias" else 1.0,
        "state_unchanged": lambda t, n: 0.0,
        "second_step_left_out": lambda t, n: 1.0 if t == 1 else 0.0,
        "learning_rate_doubled": lambda t, n: 2.0,
    }[fault]
    readings = readings_of(params0, [g1, g2], [g1, g2], moves=moves)
    correct, compared = judged(readings)
    assert not correct
    assert compared[over]["value"] > compared[over]["limit"]


def test_leaves_with_no_gradient_are_left_out_of_the_change(two_steps):
    params0, g1, g2 = two_steps
    g1 = {**g1, "layer_0/bias": g1["layer_0/bias"] * 1e-9}
    noisy = {**g2, "layer_0/bias": -g2["layer_0/bias"]}
    readings = readings_of(params0, [g1, g2], [g1, noisy])
    assert readings["change_norm_gap"] == 0.0 and judged(readings)[0]


def test_leaf_gaps_hold_a_small_leaf_against_the_median_leaf():
    ref = {"a": np.ones(100), "b": np.ones(100), "c": np.full(1, 0.01)}
    prog = {**ref, "c": np.zeros(1)}
    gaps = train_compare.leaf_gaps(prog, ref)
    assert gaps["c"][0] == pytest.approx(0.01 / 10.0)
    assert train_compare.worst_leaf(gaps) == (gaps["c"][0], "c")
    # a leaf that is not a number stays the worst, whatever comes after it
    nan = train_compare.worst_leaf({"x": (float("nan"), 0.0, 0.0), "y": (0.5, 1.0, 2.0)})
    assert nan[1] == "x" and nan[0] != nan[0]
    assert train_compare.top_leaves({**gaps, "x": (float("nan"), 0.0, 0.0)}, 2)[0][0] == "x"
