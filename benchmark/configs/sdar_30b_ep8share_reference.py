"""The plain reference of the ``sdar_30b_ep8share`` configuration, and what
else is this configuration's own in a training cell. The harness finds this
file by the ``reference`` key of ``sdar_30b_ep8share.json`` and takes from it,
by name: ``hyper``, ``init_params``, ``init_batch_stats`` (the seeded weights,
from the reference's own generator), ``write_corpus`` (the seeded token corpus
the program's loader packs and noises), ``cycle_flops`` (the operations one
cycle needs: ``blockdiff_flops.train_step_flops``, the attention core over the
pairs the block mask lets see, the head over the noised half, the held
experts at the expected one pair a position) and ``compare`` (the numbers
that decide ``correct``).

The equations live in ``benchmark/reference/sdar.py``. What is the second
configuration's unchanged comes from its bindings (the deck's document
lengths, the routing's two counts, the device cleared for the reference).

**What is compared, and why each tolerance is what it is** (the limits stand
in ``benchmark/limits/<cell>.json`` with their readings; PERF.md section 4).
As for ``mellum2_12b_ep4share``, through ``lm_compare``: each step's loss,
the first gradient leaf by leaf (``grad_norm_gap``) and all leaves together
(``grad_diff``: no dropout, so two draws of the reference are one), the
parameters' change after the first step by the worst leaf and after two by
the median leaf, the router's choices of the first timed step against the
reference's on the same rows (``route_flip_share``: a top-k choice is
discrete and a bfloat16 ``u`` flips it where the k-th and (k+1)-th lie close)
and the pairs that step held against the expected one a position a layer
(``pairs_held_gap``). The reference follows the rows the program trained on:
the recorder keeps ``tokens``, ``noised`` and ``weight`` as the loader handed
them to the step, so the noise is compared too (``unweighted`` and a mask
put elsewhere move the loss and the gradient).

The loss here is a weighted sum whose weights reach 1,000 (a block that drew
``t`` near 0.001): a few positions carry much of it, so its bfloat16 rounding
is larger than a mean cross-entropy's, and the loss limits are this cell's
own readings', not the second cell's.
"""

import os
import time

import numpy as np

from benchmark.harness import (blockdiff_flops, common, lm_compare, lm_program,
                               train_compare, trafficgen)
from benchmark.harness.common import log
from benchmark.reference import sdar

_second = common.load_module("benchmark/configs/mellum2_12b_ep4share_reference.py",
                             "bench_reference_mellum2_12b_ep4share")
document_lengths = _second.document_lengths
flip_share, held_counts = _second.flip_share, _second.held_counts

hyper = sdar.hyper
init_batch_stats = sdar.init_batch_stats
# the faults this model adds, planted in the reference put in the program's
# place (``control`` of ``train_cell.run``); the others are train_compare's
FAULTS = sdar.FAULTS


def init_params(hp: dict, seed: int) -> dict:
    """The seeded weights, one draw from ``seed``; and from here on the
    timed path's first step leaves its router's choices for ``compare``."""
    lm_program.keep_choices()
    return sdar.init_params(hp, seed)


def write_corpus(out_dir: str, cfg: dict, traffic: dict, seed: int) -> dict:
    """The deck as the token corpus the trainer reads: the seed permutes the
    documents and draws their ids by a Zipf law over ``1 .. mask_id - 1`` (0
    closes a document, the mask token never stands in the corpus). Returns at
    least ``frames_per_cycle``: the corpus tokens of one cycle (``log_step``
    steps), ``seq_len`` a row, not the ``2 x seq_len`` positions the layers
    run over."""
    m = cfg["model"]["decoder_lm"]
    deck, seq_len = traffic["deck"], m["seq_len"]
    lengths = document_lengths(deck, seq_len)
    order = trafficgen.permutation(len(lengths), seed, 1)
    rng = np.random.default_rng([int(seed), 2])
    ranks = np.arange(1, m["mask_id"], dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(deck["zipf_exponent"]))
    cdf /= cdf[-1]
    os.makedirs(os.path.join(out_dir, "tokens"), exist_ok=True)
    lines = []
    for slot, j in enumerate(order):
        n = int(lengths[j])
        ids = 1 + np.searchsorted(cdf, rng.random(n))   # 1 .. mask_id - 1
        np.save(os.path.join(out_dir, "tokens", f"d{slot:05d}.npy"),
                ids.astype(np.int32))
        lines.append(f"d{slot:05d}|{n}")
    with open(os.path.join(out_dir, "train.txt"), "w") as f:
        f.write("\n".join(lines) + "\n")
    with open(os.path.join(out_dir, "val.txt"), "w") as f:
        f.write("\n".join(lines[: deck.get("val_documents", 8)]) + "\n")
    per_step = traffic["batch_size"] * seq_len
    return {"frames_per_cycle": traffic["log_step"] * per_step,
            "documents": len(lengths), "rows": deck["rows"]}


def cycle_flops(cfg: dict, traffic: dict) -> float:
    """Forward and backward of one cycle's rows (module docstring)."""
    m = cfg["model"]["decoder_lm"]
    return traffic["log_step"] * blockdiff_flops.train_step_flops(
        m, traffic["batch_size"], m["seq_len"])


def compare(cfg, hp, opt, params0, stats0, rec, seed, controls=(), limits=None):
    """(readings, notes) of the recorder's first steps against ``sdar``."""
    import jax

    mine = lm_program.choices()
    _second.free_device(rec)
    rows = cfg.get("reference_block_rows", 1)
    b1 = opt["betas"][0]
    ticks, ref_choices = [("compare", time.time())], []
    ref_out = sdar.train_steps(
        hp, opt, params0, stats0, rec.batches, seed, block_rows=rows,
        choices=ref_choices, clock=lambda name: ticks.append((name, time.time())))
    readings, notes = lm_compare.compare_training(
        sdar.flatten, rec, ref_out, params0, b1)
    ticks.append(("compared", time.time()))
    log("reference phases (s): " + ", ".join(
        f"{b[0]}={b[1] - a[1]:.1f}" for a, b in zip(ticks, ticks[1:])))
    first = rec.batches[0]
    readings["route_flip_share"] = flip_share(mine, ref_choices)
    counts = held_counts(hp, mine)
    expected = 2 * first["tokens"].size * hp["layers"] * hp["top_k"] \
        * hp["held"] / hp["experts"]
    readings["pairs_held_gap"] = abs(float(counts.sum()) / expected - 1.0)
    log(f"first step's noise: {int((first['weight'] > 0).sum())} of "
        f"{first['tokens'].size} tokens masked, weights sum "
        f"{float(first['weight'].sum()):.1f}")
    log(f"first step's routing: {counts.sum()} pairs held of {expected:.0f} expected; "
        f"by layer {counts.sum(1).tolist()}; fullest over mean held expert "
        f"{np.round(counts.max(1) / counts.mean(1), 2).tolist()}")
    log(f"reference: losses {notes['losses']}; readings {readings}; "
        f"worst {notes['worst']}")
    if controls:
        # the program's captures are read; three trees of 2.2 GB go, so that
        # a control's own three fit the host beside the reference's
        rec.params_after = rec.first_mu = None
    for name in controls:
        kw, batches = {}, rec.batches
        if name in FAULTS:
            kw["fault"] = name
        elif name == "half_batch":
            batches = [{k: v[: len(v) // 2] for k, v in b.items()} for b in batches]
        else:
            kw["quant"] = train_compare.quantizer(name)
        fake, routed = type("R", (), {})(), []
        fake.losses, grad, fake.params_after = sdar.train_steps(
            hp, opt, params0, stats0, batches, seed, block_rows=rows,
            choices=routed, **kw)
        # as Adam's mu holds it after one step
        fake.first_mu = jax.tree_util.tree_map(lambda g: g * (1.0 - b1), grad)
        del grad
        got, where = lm_compare.compare_training(
            sdar.flatten, fake, ref_out, params0, b1)
        del fake
        # the choices too: a rounding reaches the router's operands, a mask
        # or a norm changes what the later layers' routers see
        got["route_flip_share"] = flip_share(routed, ref_choices)
        got["pairs_held_gap"] = abs(float(held_counts(hp, routed).sum()) / expected - 1.0)
        held = {k: v for k, v in (limits or {}).items() if k in got}
        notes.setdefault("control", {})[name] = got
        notes.setdefault("control_leaf_norms", {})[name] = where["leaf_norms"]
        log(f"control {name}: correct {common.judge(got, held)[0]} {got} "
            f"worst {where['worst']}")
    return readings, notes
