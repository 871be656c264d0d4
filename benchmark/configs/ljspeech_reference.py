"""The plain reference of the ``ljspeech`` configuration, and what else is
this configuration's own in a training cell. The harness finds this file by
the ``reference`` key of ``ljspeech.json`` and takes from it, by name:

- ``hyper``, ``init_params``, ``init_batch_stats``: the seeded weights, from
  the reference's own generator (the program restores them as a checkpoint);
- ``write_corpus``: the seeded corpus the program's loader reads;
- ``cycle_flops``: the operations one cycle of the deck needs, for the whole
  step's share of the peak;
- ``compare``: the numbers that decide ``correct``.

The equations live in ``benchmark/reference/`` (straightforward float32
``jax.numpy`` at ``highest`` precision) and are shared by the configurations
that share the model's code; this file binds them to this configuration.
"""

from benchmark.harness import flops, train_compare, trafficgen
from benchmark.reference import fs2

hyper = fs2.hyper
init_params = fs2.init_params
init_batch_stats = fs2.init_batch_stats


def deck_spec(cfg: dict, traffic: dict) -> dict:
    return {**traffic["deck"], "batch_size": traffic["batch_size"],
            "pitch_range": cfg["model"]["pitch_range"],
            "energy_range": cfg["model"]["energy_range"]}


def write_corpus(out_dir: str, cfg: dict, traffic: dict, seed: int) -> dict:
    """The deck as the preprocessed corpus the trainer reads. Returns at
    least ``frames_per_cycle``: the real frames of one cycle."""
    return trafficgen.write_corpus(out_dir, deck_spec(cfg, traffic), seed,
                                   cfg["model"]["n_mel_channels"])


def cycle_flops(cfg: dict, traffic: dict) -> float:
    """Forward and backward of one cycle's utterances at their real lengths
    (as the model cuts them: ``max_seq_len``)."""
    cap = cfg["model"]["max_seq_len"]
    lengths = [(min(n, cap), min(int(d.sum()), cap))
               for n, d in trafficgen.train_deck(deck_spec(cfg, traffic))]
    return flops.train_step_flops(cfg["model"], lengths)


def compare(cfg, hp, opt, params0, stats0, rec, seed, controls=(), limits=None):
    """(readings, notes) of the recorder's first steps against ``fs2``."""
    return train_compare.first_steps(
        fs2, hp, opt, params0, stats0, rec, seed,
        block_rows=cfg.get("reference_block_rows", 8), controls=controls,
        limits=limits)
