"""``train_compare.compare_training``'s arithmetic for a state of gigabytes.

The same numbers from the same captures (each step's loss, the first gradient
as Adam got it, the parameters' change after the first step by the worst leaf
and after the last by the median leaf; ``train_compare`` says why each), for
a model without dropout: ``grad_diff`` is held itself, so no second draw of
the reference comes in and ``grad_diff_excess`` is not formed. What differs
is how the sums are taken: a leaf at a time on a few threads, each leaf in
pieces that are widened to float64 one after another, where
``compare_training`` makes float64 copies of whole trees one after another
on one thread. On 595M parameters that is 109 s of a run against a few (my
chip run, PR 29); the readings agree to float64 rounding (a test holds them
to each other at toy size)."""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .train_compare import top_leaves, worst_leaf

PIECE = 1 << 20  # elements widened at a time: 8 MB of float64, twice a thread


def leaf_sums(b1, mu, ref_grad, p0, prog_first, ref_first, prog_last, ref_last):
    """One leaf's sums of squares: the program's first gradient (Adam's
    ``mu`` after one step over ``1 - b1``), the reference's, their
    difference (taken in float32, as the trees were subtracted), and the
    change from ``p0`` on each side after the first and the last step.
    Every piece is widened into the same two float64 buffers: fresh ones for
    each piece made the threads wait on one another's page faults (35 s on
    the chip's host where this takes a few)."""
    flat = [np.asarray(a).reshape(-1) for a in (mu, ref_grad, p0, prog_first,
                                                ref_first, prog_last, ref_last)]
    wide, base = np.empty(PIECE), np.empty(PIECE)
    mine, apart = np.empty(PIECE, np.float32), np.empty(PIECE, np.float32)
    out = np.zeros(7)

    def sq(x, less=None):
        w = wide[:x.size]
        np.copyto(w, x)
        if less is not None:
            np.subtract(w, less, out=w)
        return float(w.dot(w))

    for at in range(0, flat[0].size, PIECE):
        mu_, rg, p, a, b, c, d = (f[at:at + PIECE] for f in flat)
        pg = np.divide(mu_, 1.0 - b1, out=mine[:mu_.size])
        p64 = base[:p.size]
        np.copyto(p64, p)
        out += [sq(pg), sq(rg), sq(np.subtract(pg, rg, out=apart[:rg.size])),
                sq(a, p64), sq(b, p64), sq(c, p64), sq(d, p64)]
    return out


def gaps(prog: dict, ref: dict, skip=()):
    """``train_compare.leaf_gaps`` from the leaves' norms: {leaf: (gap,
    the program's norm, the reference's norm)}."""
    names = [n for n in sorted(ref) if n not in skip]
    median = float(np.median([ref[n] for n in names]))
    return {n: (abs(prog[n] - ref[n]) / max(ref[n], median, 1e-30),
                prog[n], ref[n]) for n in names}


def compare_training(flatten, rec, ref_out, params0, b1: float):
    """(readings, notes) as ``train_compare.compare_training`` gives them,
    less ``grad_diff_excess`` and ``grad_diff_draws``."""
    ref_losses, ref_grad, ref_after = ref_out
    trees = [flatten(t) for t in (rec.first_mu, ref_grad, params0,
                                  rec.params_after[0], ref_after[0],
                                  rec.params_after[-1], ref_after[-1])]
    names = sorted(trees[1])
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        sums = dict(zip(names, pool.map(
            lambda n: leaf_sums(b1, *(t[n] for t in trees)), names)))
    norm = {n: np.sqrt(sums[n]) for n in names}
    column = lambda i: {n: float(norm[n][i]) for n in names}
    readings, notes = {}, {}
    for i, (lp, lr) in enumerate(zip(rec.losses, ref_losses)):
        readings[f"loss_gap_step{i + 1}"] = abs(lp - lr) / abs(lr)
    grad = gaps(column(0), column(1))
    readings["grad_norm_gap"] = worst_leaf(grad)[0]
    readings["grad_diff"] = float(sum(sums[n][2] for n in names)
                                  / sum(sums[n][1] for n in names)) ** 0.5
    # leaves whose gradient is nought to rounding in the reference move under
    # Adam by round-off alone: out of the change, by a rule on the gradient
    floor = 1e-3 * float(np.median([norm[n][1] for n in names]))
    skip = sorted(n for n in names if norm[n][1] < floor)
    first = gaps(column(3), column(4), skip)
    last = gaps(column(5), column(6), skip)
    readings["change_gap_step1"] = worst_leaf(first)[0]
    readings["change_gap_median"] = float(np.median([g for g, _, _ in last.values()]))
    readings["change_norm_gap"] = worst_leaf(last)[0]  # logged, not compared
    notes["worst"] = {"grad_norm_gap": top_leaves(grad),
                      "change_gap_step1": top_leaves(first),
                      "change_norm_gap": top_leaves(last)}
    notes["leaf_norms"] = {
        n: {"size": int(np.size(trees[2][n])), "grad": grad[n][1:],
            "step1": first.get(n, (None,) * 3)[1:],
            "last": last.get(n, (None,) * 3)[1:]} for n in names}
    notes["left_out"] = skip
    notes["losses"] = {"program": rec.losses, "reference": list(ref_losses)}
    return readings, notes
