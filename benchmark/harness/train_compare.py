"""The arithmetic that decides ``correct`` for a training cell, for any plain
reference that follows the first optimizer steps: each step's loss, the first
gradient as the optimizer got it and the parameters' change, leaf by leaf.
A configuration binds it to its own reference (``<name>_reference.py``:
``compare``); nothing here knows a model. ``ref`` is that reference: a module
with ``train_steps`` (losses, first clipped gradient, parameters after each
step) and ``flatten`` (a tree to {path: leaf})."""

import json
import time

import numpy as np

from . import common
from .common import log

# gradients closer than a hundredth of the reference's norm differ by nothing
DIFF_FLOOR = 1e-4


def leaf_gaps(prog: dict, ref: dict, skip=()):
    """Per leaf, |‖prog‖ − ‖ref‖| over max(‖ref‖ of that leaf, ‖ref‖ of the
    median leaf), and the two norms: {leaf: (gap, ‖prog‖, ‖ref‖)}."""
    names = [n for n in sorted(ref) if n not in skip]
    ref_norms = {n: float(np.linalg.norm(np.asarray(ref[n], np.float64)))
                 for n in names}
    median = float(np.median(list(ref_norms.values())))
    out = {}
    for n in names:
        p = float(np.linalg.norm(np.asarray(prog[n], np.float64)))
        out[n] = (abs(p - ref_norms[n]) / max(ref_norms[n], median, 1e-30),
                  p, ref_norms[n])
    return out


def worst_leaf(gaps: dict):
    """(gap, leaf) of the leaf that reads highest; NaN counts as highest."""
    worst, leaf = 0.0, None
    for n, (gap, _, _) in gaps.items():
        if worst == worst and not gap <= worst:
            worst, leaf = gap, n
    return worst, leaf


def top_leaves(gaps: dict, count=5):
    """The ``count`` leaves that read highest, each [leaf, gap, ‖prog‖,
    ‖ref‖]: what a run logs, so that a reading over its limit names its leaf."""
    order = sorted(gaps, key=lambda n: gaps[n][0] if gaps[n][0] == gaps[n][0]
                   else float("inf"), reverse=True)
    return [[n, *(round(x, 9) for x in gaps[n])] for n in order[:count]]


def compare_training(flatten, rec, ref_out, params0, b1: float, other_grad):
    """The numbers compared, from the recorder's captures and the reference's
    (losses, first clipped gradient, parameters after each step).
    ``other_grad`` is the reference's first gradient again under other
    dropout masks: what two sound draws differ by."""
    ref_losses, ref_grad, ref_after = ref_out
    readings, notes = {}, {}
    for i, (lp, lr) in enumerate(zip(rec.losses, ref_losses)):
        readings[f"loss_gap_step{i + 1}"] = abs(lp - lr) / abs(lr)
    ref_grad = {k: np.asarray(v) for k, v in flatten(ref_grad).items()}
    prog_grad = {k: np.asarray(v) / (1.0 - b1)
                 for k, v in flatten(rec.first_mu).items()}
    grad = leaf_gaps(prog_grad, ref_grad)
    readings["grad_norm_gap"] = worst_leaf(grad)[0]
    # the norm of the difference over all leaves together, against the same
    # between two draws of the reference: which rows went in shows here, where
    # a gap of norms is blind to it (a mean over half the rows has the norms)
    sq = lambda t: sum(float(np.sum(np.square(np.asarray(v, np.float64))))
                       for v in t.values())
    other = {k: np.asarray(v) for k, v in flatten(other_grad).items()}
    mine = sq({k: prog_grad[k] - ref_grad[k] for k in ref_grad}) / sq(ref_grad)
    draws = sq({k: other[k] - ref_grad[k] for k in ref_grad}) / sq(ref_grad)
    readings["grad_diff"], notes["grad_diff_draws"] = mine ** 0.5, draws ** 0.5
    readings["grad_diff_excess"] = abs(mine - draws) / max(draws, DIFF_FLOOR)
    # leaves whose gradient is nought to rounding in the reference move under
    # Adam by round-off alone: out of the change, by a rule on the gradient
    norms = {k: float(np.linalg.norm(v)) for k, v in ref_grad.items()}
    floor = 1e-3 * float(np.median(list(norms.values())))
    skip = sorted(k for k, v in norms.items() if v < floor)
    p0 = flatten(params0)
    change = lambda tree: {k: np.asarray(v, np.float64) - p0[k]
                           for k, v in flatten(tree).items()}
    # after the first step every element has moved by the learning rate,
    # whatever its gradient's size (Adam's first update is g / |g|): the worst
    # leaf says whether each leaf moved, once. After the last step a leaf's
    # change also says how far the steps' gradients agree in sign, which for
    # a leaf whose elements share one factor (a bias behind dropout 0.5) is
    # one draw: so the median leaf is compared there, and the worst is a note
    first = leaf_gaps(change(rec.params_after[0]), change(ref_after[0]), skip)
    last = leaf_gaps(change(rec.params_after[-1]), change(ref_after[-1]), skip)
    readings["change_gap_step1"] = worst_leaf(first)[0]
    readings["change_gap_median"] = float(np.median([g for g, _, _ in last.values()]))
    readings["change_norm_gap"] = worst_leaf(last)[0]  # logged, not compared
    notes["worst"] = {"grad_norm_gap": top_leaves(grad),
                      "change_gap_step1": top_leaves(first),
                      "change_norm_gap": top_leaves(last)}
    # every leaf's norms on both sides (gradient, change after the first and
    # the last step): what a limit is set from (``limits_only`` prints it)
    notes["leaf_norms"] = {
        n: {"size": int(np.size(p0[n])), "grad": grad[n][1:],
            "step1": first.get(n, (None,) * 3)[1:], "last": last.get(n, (None,) * 3)[1:]}
        for n in sorted(grad)}
    notes["left_out"] = skip
    notes["losses"] = {"program": rec.losses, "reference": list(ref_losses)}
    return readings, notes


def first_steps(ref, hp, opt, params0, stats0, rec, seed, block_rows=8,
                controls=(), limits=None):
    """(readings, notes): the reference follows the steps the recorder
    captured, on the same rows, and takes its first gradient once more under
    other masks. ``controls`` names readings to take beside the program's
    with the reference put in its place: a rounding (``float8_e4m3fn``),
    ``half_batch`` (the fault) or ``other_masks``; each is judged against
    ``limits`` and logged, and kept under ``notes["control"]``."""
    import jax

    b1 = opt["betas"][0]
    t_ref = time.time()
    ticks = []
    ref_out = ref.train_steps(hp, opt, params0, stats0, rec.batches, seed,
                              block_rows=block_rows,
                              clock=lambda name: ticks.append((name, time.time())))
    log("reference phases (s): " + ", ".join(
        f"{b[0]}={b[1] - a[1]:.1f}" for a, b in zip(ticks, ticks[1:])
        if b[0] != "start"))
    other_grad = ref.train_steps(hp, opt, params0, stats0, rec.batches[:1],
                                 seed + 1, block_rows=block_rows)[1]
    readings, notes = compare_training(ref.flatten, rec, ref_out, params0, b1,
                                       other_grad)
    log(f"reference: {time.time() - t_ref:.1f} s; notes "
        f"{json.dumps({k: notes[k] for k in ('worst', 'grad_diff_draws')})}; "
        f"losses {notes['losses']}; readings {json.dumps(readings)}")
    for name in controls:
        # the reference put in the program's place, with masks of its own
        # as the program has
        if name == "half_batch":  # the fault: half the rows left out
            rows = [{k: v[: len(v) // 2] for k, v in b.items()}
                    for b in rec.batches]
            ctl = ref.train_steps(hp, opt, params0, stats0, rows, seed + 1,
                                  block_rows=block_rows)
        elif name == "other_masks":
            ctl = ref.train_steps(hp, opt, params0, stats0, rec.batches,
                                  seed + 1, block_rows=block_rows)
        else:
            ctl = ref.train_steps(hp, opt, params0, stats0, rec.batches, seed,
                                  block_rows=block_rows, quant=quantizer(name))
        fake = type("R", (), {})()
        fake.losses = ctl[0]
        fake.first_mu = jax.tree_util.tree_map(
            lambda g: np.asarray(g) * (1.0 - b1), ctl[1])
        fake.params_after = ctl[2]
        got, where = compare_training(ref.flatten, fake, ref_out, params0, b1,
                                      other_grad)
        mine = {k: v for k, v in (limits or {}).items() if k in got}
        notes.setdefault("control", {})[name] = got
        notes.setdefault("control_leaf_norms", {})[name] = where["leaf_norms"]
        log(f"control {name}: correct {common.judge(got, mine)[0]} "
            f"{json.dumps(got)} worst {json.dumps(where['worst'])}")
    return readings, notes


def quantizer(name: str):
    """The control's rounding: both operands of every product, to ``name``
    and back."""
    import jax.numpy as jnp

    dtype = jnp.dtype(name)
    if jnp.issubdtype(dtype, jnp.integer):
        def q(x):
            scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
            return jnp.round(x / scale).astype(dtype).astype(jnp.float32) * scale
        return q
    return lambda x: x.astype(dtype).astype(jnp.float32)
