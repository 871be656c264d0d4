"""Training-descent demonstration at paper geometry (the committed artifact).

Generates a learnable synthetic corpus (data/synthetic.py), then runs the
REAL training loop (training/trainer.run_training — reference semantics:
train.py:79-173) for ~300 steps at the paper config's batch geometry
(batch 48, ~600 mel frames/utterance), with a mid-run checkpoint and a
restore+resume leg, writing ``log.txt`` with per-step losses and
mel-frames/s throughput.

    python scripts/train_descent.py --out artifacts/train_descent_r4 \
        [--steps 300] [--resume_at 150] [--device cpu|default]

The committed artifact under artifacts/train_descent_r4/ is the output of
exactly this command (CPU host; the loop and bucketing are
device-agnostic — on TPU only the step time changes).
"""

import argparse
import dataclasses
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="artifacts/train_descent_r4")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--resume_at", type=int, default=150)
    ap.add_argument("--device", default="cpu", choices=("cpu", "default"))
    ap.add_argument("--n_utts", type=int, default=640)
    ap.add_argument("--conv_impl", default="xla",
                    help="conv lowering for this run; the CPU demonstration "
                    "defaults to 'xla' — the unfold/pallas lowerings are "
                    "MXU-oriented and memory-hungry on a CPU host, and this "
                    "artifact is about training dynamics, not conv speed")
    args = ap.parse_args()

    if args.device == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"

    from speakingstyle_tpu.configs.config import (
        Config,
        OptimizerConfig,
        StepConfig,
        TrainConfig,
        TrainPathConfig,
    )
    from speakingstyle_tpu.data.synthetic import generate_corpus
    from speakingstyle_tpu.obs.jaxmon import enable_compilation_cache
    from speakingstyle_tpu.training.trainer import run_training

    enable_compilation_cache()

    corpus = tempfile.mkdtemp(prefix="synth_corpus_")
    print(f"generating {args.n_utts}-utterance synthetic corpus in {corpus}",
          flush=True)
    # Narrow length ranges so every batch lands in ONE (src=128, mel=640)
    # bucket: exactly one train-step compile (paper geometry, ~600
    # frames/utt), which keeps the CPU demonstration tractable and the
    # throughput line comparable across steps.
    generate_corpus(
        corpus,
        n_utts=args.n_utts,
        n_phones_per_utt=(97, 104),
        duration_range=(5, 7),
    )

    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    from speakingstyle_tpu.configs.config import ModelConfig

    cfg = Config(model=ModelConfig(conv_impl=args.conv_impl),
                 train=TrainConfig(
        path=TrainPathConfig(
            ckpt_path=os.path.join(out, "ckpt"),
            log_path=out,
            result_path=os.path.join(out, "result"),
        ),
        optimizer=OptimizerConfig(batch_size=48),
        step=StepConfig(
            total_step=args.steps,
            log_step=10,
            val_step=100,
            save_step=args.resume_at,
            synth_step=10**9,  # no sample synthesis: this artifact is loss-only
        ),
    ))
    cfg = dataclasses.replace(
        cfg,
        preprocess=dataclasses.replace(
            cfg.preprocess,
            path=dataclasses.replace(
                cfg.preprocess.path, preprocessed_path=corpus
            ),
        ),
    )

    print(f"leg 1: steps 0 -> {args.resume_at}", flush=True)
    run_training(cfg, max_steps=args.resume_at)
    print(f"leg 2 (restored from the step-{args.resume_at} checkpoint): "
          f"-> {args.steps}", flush=True)
    run_training(cfg, restore_step=-1, max_steps=args.steps)

    shutil.rmtree(corpus, ignore_errors=True)
    log = os.path.join(out, "log.txt")
    print(f"done; artifact log: {log}", flush=True)
    with open(log) as f:
        lines = f.read().splitlines()
    print("\n".join(lines[:3] + ["..."] + lines[-4:]))


if __name__ == "__main__":
    main()
