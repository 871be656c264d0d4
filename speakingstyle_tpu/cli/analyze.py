"""``analyze`` command: corpus / model distribution analyses.

Productizes the reference's analysis notebooks (SURVEY.md §1 "Research
notebooks"):

* ``--what features`` — de-normalized pitch/energy/duration distributions
  over a split, with the notebook's IQR outlier rule for durations
  (reference: notebooks/variance_control_distbn.ipynb, corpus half).
* ``--what predictions`` — free-running forward over the split, predicted
  pitch/energy/duration distributions side-by-side with the corpus truth
  plus a histogram-overlap score (reference:
  notebooks/variance_control_distbn.ipynb, prediction half).
* ``--what style`` — reference-encoder γ/β statistics per utterance and
  the learned FiLM gate values s_gamma/s_beta by site (reference:
  notebooks/ref_encoder.ipynb).

Text tables + ASCII histograms by default; ``--json PATH`` dumps the raw
numbers for external plotting.
"""

import argparse
import json
import os

import numpy as np

from speakingstyle_tpu.cli import add_config_args, config_from_args


def build_parser(parser=None):
    parser = parser or argparse.ArgumentParser(description=__doc__)
    add_config_args(parser, required=True)
    parser.add_argument("--what", choices=("features", "predictions", "style"),
                        default="features")
    parser.add_argument("--split", default="val.txt",
                        help="metadata file inside the preprocessed dir")
    parser.add_argument("--restore_step", type=int, default=-1,
                        help="checkpoint for predictions/style (-1 latest; "
                        "if none found, random init with a warning)")
    parser.add_argument("--max_batches", type=int, default=50)
    parser.add_argument("--json", default=None,
                        help="also dump raw stats to this path")
    return parser


def _ascii_hist(values, bins=24, width=46, label=""):
    lines = []
    hist, edges = np.histogram(values, bins=bins)
    top = hist.max() or 1
    for h, lo, hi in zip(hist, edges[:-1], edges[1:]):
        bar = "#" * int(round(width * h / top))
        lines.append(f"  {lo:9.3f}..{hi:9.3f} |{bar}")
    return "\n".join([f"  [{label}]"] + lines)


def _summary(values):
    values = np.asarray(values, np.float64)
    if values.size == 0:
        return {"count": 0}
    return {
        "count": int(values.size),
        "mean": float(values.mean()),
        "std": float(values.std()),
        "p5": float(np.percentile(values, 5)),
        "p50": float(np.percentile(values, 50)),
        "p95": float(np.percentile(values, 95)),
        "min": float(values.min()),
        "max": float(values.max()),
    }


def _remove_outlier(values, k=3.0):
    """The notebook's IQR rule (variance_control_distbn.ipynb), with a
    guard for degenerate (zero-IQR) distributions the strict <> would
    empty out."""
    values = np.asarray(values)
    if values.size == 0:
        return values
    p25, p75 = np.percentile(values, 25), np.percentile(values, 75)
    if p75 == p25:
        return values
    keep = (values > p25 - k * (p75 - p25)) & (values < p75 + k * (p75 - p25))
    return values[keep]


def _split_entries(cfg, split):
    """[(basename, speaker)] from the metadata file — the one canonical
    source of feature-file names (data/dataset.py's
    ``{speaker}-{kind}-{basename}.npy`` convention)."""
    root = cfg.preprocess.path.preprocessed_path
    entries = []
    with open(os.path.join(root, split)) as f:
        for ln in f:
            if not ln.strip():
                continue
            parts = ln.split("|")
            entries.append((parts[0], parts[1]))
    return entries, root


def _corpus_features(cfg, split, denormalize=True):
    """``denormalize=False`` keeps pitch/energy in the on-disk z-normalized
    space — required when comparing against model predictions, which live
    there too."""
    entries, root = _split_entries(cfg, split)
    with open(os.path.join(root, "stats.json")) as f:
        stats = json.load(f)
    out = {"pitch": [], "energy": [], "duration": []}
    for kind in out:
        for base, spk in entries:
            path = os.path.join(root, kind, f"{spk}-{kind}-{base}.npy")
            if not os.path.exists(path):
                continue
            v = np.load(path).astype(np.float64)
            if (
                denormalize
                and kind in ("pitch", "energy")
                and len(stats.get(kind, [])) >= 4
            ):
                # de-normalize: stats.json rows are [min max mean std]
                v = v * stats[kind][3] + stats[kind][2]
            out[kind].extend(v.tolist())
    out["duration"] = _remove_outlier(out["duration"]).tolist()
    return out, stats


def _histogram_overlap(a, b, bins=50):
    lo = min(np.min(a), np.min(b))
    hi = max(np.max(a), np.max(b))
    ha, _ = np.histogram(a, bins=bins, range=(lo, hi), density=True)
    hb, _ = np.histogram(b, bins=bins, range=(lo, hi), density=True)
    ha, hb = ha / (ha.sum() or 1), hb / (hb.sum() or 1)
    return float(np.minimum(ha, hb).sum())


def _restored_state(cfg, model, restore_step):
    import jax

    from speakingstyle_tpu.models.factory import init_variables
    from speakingstyle_tpu.training.checkpoint import CheckpointManager
    from speakingstyle_tpu.training.optim import make_optimizer
    from speakingstyle_tpu.training.state import TrainState

    variables = init_variables(model, cfg, jax.random.PRNGKey(0))
    state = TrainState.create(variables, make_optimizer(cfg.train))
    try:
        ckpt = CheckpointManager(cfg.train.path.ckpt_path)
        state = ckpt.restore(
            state, step=restore_step if restore_step > 0 else None
        )
        ckpt.close()
        print(f"restored checkpoint @ step {int(state.step)}")
    except FileNotFoundError:
        print("warning: no checkpoint found — analyzing a random init")
    return state


def _predictions(cfg, split, restore_step, max_batches):
    from speakingstyle_tpu.data import BucketedBatcher, CacheBudget, SpeechDataset
    from speakingstyle_tpu.models.factory import build_model
    from speakingstyle_tpu.parallel.registry import jit_program

    model = build_model(cfg)
    state = _restored_state(cfg, model, restore_step)

    ds = SpeechDataset(split, cfg, sort=False, drop_last=False,
                       cache=CacheBudget(0))  # one pass: nothing to keep
    batcher = BucketedBatcher(
        ds, max_src=cfg.model.max_seq_len, max_mel=cfg.model.max_seq_len
    )

    @jit_program
    def fwd(params, batch_stats, arrays):
        return model.apply(
            {"params": params, "batch_stats": batch_stats},
            speakers=arrays["speakers"],
            texts=arrays["texts"],
            src_lens=arrays["src_lens"],
            mels=arrays["mels"],       # style reference (mandatory)
            mel_lens=arrays["mel_lens"],
            max_mel_len=arrays["mels"].shape[1],
            deterministic=True,
        )

    # pitch/energy predictions are phoneme- or frame-shaped depending on
    # the corpus config (configs/config.py feature levels) — pick the
    # matching pad mask for each
    p_level = cfg.preprocess.preprocessing.pitch.feature
    e_level = cfg.preprocess.preprocessing.energy.feature

    pitch, energy, durations = [], [], []
    for n, batch in enumerate(batcher.epoch(shuffle=False)):
        if n >= max_batches:
            break
        out = fwd(state.params, state.batch_stats, batch.arrays())
        keep_src = ~np.asarray(out["src_pad_mask"])
        keep_mel = ~np.asarray(out["mel_pad_mask"])
        keep_p = keep_src if p_level == "phoneme_level" else keep_mel
        keep_e = keep_src if e_level == "phoneme_level" else keep_mel
        pitch.extend(np.asarray(out["pitch_prediction"])[keep_p].tolist())
        energy.extend(np.asarray(out["energy_prediction"])[keep_e].tolist())
        durations.extend(np.asarray(out["durations"])[keep_src].tolist())
    return pitch, energy, durations


def _style(cfg, split, restore_step, max_batches):
    from flax.traverse_util import flatten_dict

    from speakingstyle_tpu.data import BucketedBatcher, CacheBudget, SpeechDataset
    from speakingstyle_tpu.models.factory import build_model

    model = build_model(cfg)
    state = _restored_state(cfg, model, restore_step)

    gates = {
        "/".join(k): float(np.asarray(v).reshape(-1)[0])
        for k, v in flatten_dict(state.params).items()
        if k[-1] in ("s_gamma", "s_beta")
    }

    ds = SpeechDataset(split, cfg, sort=False, drop_last=False,
                       cache=CacheBudget(0))  # one pass: nothing to keep
    batcher = BucketedBatcher(
        ds, max_src=cfg.model.max_seq_len, max_mel=cfg.model.max_seq_len
    )

    # only the style branch is needed — apply the ReferenceEncoder
    # submodule directly on its params subtree (same construction as
    # models/fastspeech2.py), jitted, instead of the whole acoustic model
    from speakingstyle_tpu.models.factory import reference_encoder_from_config
    from speakingstyle_tpu.ops.masking import length_to_mask
    from speakingstyle_tpu.parallel.registry import jit_program

    enc = reference_encoder_from_config(cfg)

    @jit_program
    def style_fwd(ref_params, mels, mel_lens):
        pad = length_to_mask(mel_lens, mels.shape[1])
        return enc.apply({"params": ref_params}, mels, pad, deterministic=True)

    ref_params = state.params["reference_encoder"]
    gammas_all, betas_all = [], []
    for n, batch in enumerate(batcher.epoch(shuffle=False)):
        if n >= max_batches:
            break
        arrays = batch.arrays()
        g, b = style_fwd(ref_params, arrays["mels"], arrays["mel_lens"])
        gammas_all.append(np.asarray(g)[:, 0, :])
        betas_all.append(np.asarray(b)[:, 0, :])
    gammas = np.concatenate(gammas_all) if gammas_all else np.zeros((0, 1))
    betas = np.concatenate(betas_all) if betas_all else np.zeros((0, 1))
    return gammas, betas, gates


def main(args):
    cfg = config_from_args(args)
    report = {"what": args.what, "split": args.split}

    if args.what == "features":
        feats, stats = _corpus_features(cfg, args.split)
        for kind, vals in feats.items():
            report[kind] = _summary(vals)
            print(f"== {kind} (de-normalized, {len(vals)} values)")
            for k, v in report[kind].items():
                print(f"  {k:>6}: {v:.4f}" if isinstance(v, float) else f"  {k:>6}: {v}")
            if len(vals):
                print(_ascii_hist(np.asarray(vals), label=kind))

    elif args.what == "predictions":
        # predictions live in the on-disk NORMALIZED space for pitch/energy
        # (and raw hop counts for durations) — load the truth in that same
        # space so the summaries and the overlap are comparable.
        feats, _ = _corpus_features(cfg, args.split, denormalize=False)
        pitch, energy, durations = _predictions(
            cfg, args.split, args.restore_step, args.max_batches
        )
        durations = _remove_outlier(durations).tolist()
        for kind, pred in (("pitch", pitch), ("energy", energy),
                           ("duration", durations)):
            true = feats[kind]
            report[kind] = {
                "true": _summary(true),
                "pred": _summary(pred),
            }
            if len(pred) and len(true):
                report[kind]["hist_overlap"] = _histogram_overlap(true, pred)
            print(f"== {kind}: true vs predicted")
            print(f"  true: {report[kind]['true']}")
            print(f"  pred: {report[kind]['pred']}")
            if "hist_overlap" in report[kind]:
                print(f"  histogram overlap: {report[kind]['hist_overlap']:.3f}")

    else:  # style
        gammas, betas, gates = _style(
            cfg, args.split, args.restore_step, args.max_batches
        )
        report["n_utts"] = int(gammas.shape[0])
        report["gamma"] = {
            "per_utt_norm": _summary(np.linalg.norm(gammas, axis=1)),
            "per_dim_std_mean": float(gammas.std(axis=0).mean()),
        }
        report["beta"] = {
            "per_utt_norm": _summary(np.linalg.norm(betas, axis=1)),
            "per_dim_std_mean": float(betas.std(axis=0).mean()),
        }
        report["film_gates"] = gates
        print(f"== style vectors over {report['n_utts']} utterances")
        print(f"  |gamma| {report['gamma']['per_utt_norm']}")
        print(f"  |beta|  {report['beta']['per_utt_norm']}")
        print(f"  per-dim std (gamma): {report['gamma']['per_dim_std_mean']:.4f}")
        print("  FiLM gates (s_gamma/s_beta by site):")
        for site, val in sorted(gates.items()):
            print(f"    {site}: {val:+.4f}")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
        print(f"raw stats -> {args.json}")
    return report


if __name__ == "__main__":
    main(build_parser().parse_args())
