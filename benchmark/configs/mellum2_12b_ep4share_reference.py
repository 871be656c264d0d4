"""The plain reference of the ``mellum2_12b_ep4share`` configuration, and
what else is this configuration's own in a training cell. The harness finds
this file by the ``reference`` key of ``mellum2_12b_ep4share.json`` and takes
from it, by name:

- ``hyper``, ``init_params``, ``init_batch_stats``: the seeded weights, from
  the reference's own generator (the program restores them as a checkpoint);
- ``write_corpus``: the seeded token corpus the program's loader packs;
- ``cycle_flops``: the operations one cycle needs, for the whole step's share
  of the peak: projections, head, the attention core over the unmasked scores
  only, the router, and **the held experts at the expected number of pairs a
  token** (8 choices x 16 of 64 experts held = 2). ``compare`` holds the run
  to that count: ``pairs_held_gap`` is how far the pairs the first timed step
  held stand from the expected, as a share of them
  (``moe_gmm_roofline.train`` counts the pairs a step really computed);
- ``compare``: the numbers that decide ``correct``.

The equations live in ``benchmark/reference/mellum2.py``.

**What is compared, and why each tolerance is what it is** (the limits stand
in ``benchmark/limits/<cell>.json`` with their readings; PERF.md section 4).
As for ``ljspeech`` (``train_compare.compare_training``; here through
``lm_compare``, the same arithmetic a leaf at a time on a few threads, which
595M parameters need: seconds where whole-tree float64 copies took 109):
each step's loss, the first gradient leaf by leaf (``grad_norm_gap``), the
parameters' change after the first step by the worst leaf and after two by
the median leaf.
This model has no dropout, so two draws of the reference are one and the
same: ``grad_diff`` itself (the norm of program's minus reference's first
gradient over the reference's norm, all leaves together) is held where
``ljspeech`` holds its excess over two draws. A top-k choice is discrete: a
bfloat16 ``u`` moves a router probability by a few thousandths and flips the
choice where the k-th and (k+1)-th lie closer than that, so a share of the
(token, choice) pairs goes to another expert with nothing at fault:
``route_flip_share`` is that share at the layer where it is largest, from
the choices the first step of the timed path returned beside its loss
(``lm_program``) against the reference's on the same rows (the forward pass
its first gradient is of), and is held under
a limit that a sound bfloat16 run stays under and a rounding of the router's
operands to fp8 does not.
"""

import os
import time

import numpy as np

from benchmark.harness import (lm_compare, lm_flops, lm_program, train_compare,
                               trafficgen)
from benchmark.harness.common import log
from benchmark.reference import mellum2

hyper = mellum2.hyper
init_batch_stats = mellum2.init_batch_stats


def init_params(hp: dict, seed: int) -> dict:
    """The seeded weights, one draw from ``seed``; and from here on the
    timed path's first step leaves its router's choices for ``compare``."""
    lm_program.keep_choices()
    return mellum2.init_params(hp, seed)


# the faults this model adds, planted in the reference put in the program's
# place (``control`` of ``train_cell.run``); the others are train_compare's
FAULTS = ("capacity", "no_window", "no_yarn")


def document_lengths(deck: dict, seq_len: int) -> np.ndarray:
    """The deck's document lengths: a quantile table (no draw), the longest
    shortened so that the documents, each with its end-of-document id, fill
    ``rows`` rows exactly."""
    lengths = trafficgen.quantile_deck(deck["length_quantiles"], deck["documents"])
    excess = int(lengths.sum()) + len(lengths) - deck["rows"] * seq_len
    longest = int(np.argmax(lengths))
    if not 0 <= excess < lengths[longest] - lengths.min():
        raise ValueError(f"the deck's documents miss {deck['rows']} rows of "
                         f"{seq_len} by {excess} ids: change `documents`")
    lengths[longest] -= excess
    return lengths


def write_corpus(out_dir: str, cfg: dict, traffic: dict, seed: int) -> dict:
    """The deck as the token corpus the trainer reads (``train.txt``,
    ``val.txt``, ``tokens/<name>.npy``): the seed permutes the documents and
    draws their ids, by a Zipf law over the held vocabulary slice less the
    end-of-document id. Returns at least ``frames_per_cycle``: the positions
    of one cycle (``log_step`` steps)."""
    m = cfg["model"]["decoder_lm"]
    deck, seq_len = traffic["deck"], m["seq_len"]
    lengths = document_lengths(deck, seq_len)
    order = trafficgen.permutation(len(lengths), seed, 1)
    rng = np.random.default_rng([int(seed), 2])
    vocab = m.get("vocab_held") or m["vocab_size"]
    ranks = np.arange(1, vocab, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(deck["zipf_exponent"]))
    cdf /= cdf[-1]
    os.makedirs(os.path.join(out_dir, "tokens"), exist_ok=True)
    lines = []
    for slot, j in enumerate(order):
        n = int(lengths[j])
        ids = 1 + np.searchsorted(cdf, rng.random(n))   # 1..vocab-1; 0 is eod
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
    return traffic["log_step"] * lm_flops.train_step_flops(
        m, traffic["batch_size"], m["seq_len"])


def flip_share(mine: list, theirs: list) -> float:
    """The largest, over the layers, of the share of the other side's
    (token, choice) pairs whose expert this side did not choose for that
    token (a row this side never routed counts as all its pairs)."""
    worst = 0.0
    for a, b in zip(mine, theirs):
        rows = min(len(a), len(b))
        same = (a[:rows, :, :, None] == b[:rows, :, None, :]).any(-1)
        worst = max(worst, 1.0 - float(same.sum()) / b.size)
    return worst


def held_counts(hp: dict, choices: list) -> np.ndarray:
    """``[layers, held]``: the (token, choice) pairs each held expert drew."""
    return np.stack([np.bincount(c.reshape(-1), minlength=hp["experts"])
                     [hp["lo"]: hp["lo"] + hp["held"]] for c in choices])


def free_device(rec):
    """Everything the timed run left on the device goes before the reference
    starts: the reference's one-row gradient needs 9.3 GB of the chip (its
    compile for a described v5e: 2.4 arguments, 4.6 temporaries, 2.4 result)
    beside its optimizer's 7.1, and the run's state, its step executables
    (whose scratch stays reserved while they are loaded) and whatever still
    refers to either hold most of 16 GB. The recorder keeps numpy copies of
    all the comparison reads, so no device array of the run is needed again."""
    import gc

    import jax

    def in_use():
        stats = jax.devices()[0].memory_stats() or {}
        return {k: stats.get(k) for k in ("bytes_in_use", "bytes_reserved")}

    before, live = in_use(), jax.live_arrays()
    rec.inner = None                      # the jitted step and its executables
    for array in live:
        array.delete()
    del live
    jax.clear_caches()
    gc.collect()
    log(f"device freed for the reference: {before} -> {in_use()}")


def compare(cfg, hp, opt, params0, stats0, rec, seed, controls=(), limits=None):
    """(readings, notes) of the recorder's first steps against ``mellum2``."""
    import jax

    from benchmark.harness import common

    mine = lm_program.choices()
    free_device(rec)
    rows = cfg.get("reference_block_rows", 1)
    b1 = opt["betas"][0]
    ticks, ref_choices = [("compare", time.time())], []
    ref_out = mellum2.train_steps(
        hp, opt, params0, stats0, rec.batches, seed, block_rows=rows,
        choices=ref_choices, clock=lambda name: ticks.append((name, time.time())))
    readings, notes = lm_compare.compare_training(
        mellum2.flatten, rec, ref_out, params0, b1)
    ticks.append(("compared", time.time()))
    log("reference phases (s): " + ", ".join(
        f"{b[0]}={b[1] - a[1]:.1f}" for a, b in zip(ticks, ticks[1:])))
    tokens = rec.batches[0]["tokens"]
    readings["route_flip_share"] = flip_share(mine, ref_choices)
    counts = held_counts(hp, mine)
    expected = tokens.size * hp["layers"] * hp["top_k"] * hp["held"] / hp["experts"]
    readings["pairs_held_gap"] = abs(float(counts.sum()) / expected - 1.0)
    log(f"first step's routing: {counts.sum()} pairs held of {expected:.0f} expected; "
        f"by layer {counts.sum(1).tolist()}; fullest over mean held expert "
        f"{np.round(counts.max(1) / counts.mean(1), 2).tolist()}")
    log(f"reference: losses {notes['losses']}; readings {readings}; "
        f"worst {notes['worst']}")
    if controls:
        # the program's captures are read; three trees of 2.4 GB go, so that
        # a control's own three fit the host beside the reference's
        rec.params_after = rec.first_mu = None
    for name in controls:
        kw, batches = {}, rec.batches
        if name in FAULTS:
            kw["fault"] = name
        elif name == "half_batch":
            batches = [{k: v[: len(v) // 2] for k, v in b.items()} for b in batches]
        elif name != "other_masks":
            kw["quant"] = train_compare.quantizer(name)
        fake, routed = type("R", (), {})(), []
        fake.losses, grad, fake.params_after = mellum2.train_steps(
            hp, opt, params0, stats0, batches, seed, block_rows=rows,
            choices=routed, **kw)
        # as Adam's mu holds it after one step
        fake.first_mu = jax.tree_util.tree_map(lambda g: g * (1.0 - b1), grad)
        del grad
        got, where = lm_compare.compare_training(
            mellum2.flatten, fake, ref_out, params0, b1)
        del fake
        if "quant" in kw:  # the rounding reaches the router's operands too
            got["route_flip_share"] = flip_share(routed, ref_choices)
        held = {k: v for k, v in (limits or {}).items() if k in got}
        notes.setdefault("control", {})[name] = got
        notes.setdefault("control_leaf_norms", {})[name] = where["leaf_norms"]
        log(f"control {name}: correct {common.judge(got, held)[0]} {got} "
            f"worst {where['worst']}")
    return readings, notes
