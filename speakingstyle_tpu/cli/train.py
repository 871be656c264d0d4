"""``train`` command (reference: train.py:176-202)."""

import argparse

from speakingstyle_tpu.cli import add_config_args, config_from_args


def build_parser(parser=None):
    parser = parser or argparse.ArgumentParser(description=__doc__)
    add_config_args(parser, required=True)
    parser.add_argument(
        "--restore_step", type=int, default=0,
        help="checkpoint step to resume from (0 = fresh start; -1 = latest)",
    )
    parser.add_argument(
        "--max_steps", type=int, default=None,
        help="override total_step (smoke tests)",
    )
    parser.add_argument(
        "--data_parallel", type=int, default=None,
        help="data-axis size for the device mesh; overrides "
        "train.parallel.mesh (default: the train.parallel.* config block, "
        "falling back to the legacy train.sharding derivation)",
    )
    parser.add_argument(
        "--model_parallel", type=int, default=None,
        help="tensor-parallel degree over the mesh's model axis; overrides "
        "train.parallel.mesh (default: the train.parallel.* config block, "
        "falling back to train.sharding.model_axis)",
    )
    parser.add_argument(
        "--synth", action="store_true",
        help="render a GT-vs-predicted validation sample every synth_step",
    )
    parser.add_argument(
        "--vocoder_ckpt", type=str, default=None,
        help="HiFi-GAN checkpoint for --synth audio (Griffin-Lim otherwise)",
    )
    parser.add_argument(
        "--profile_dir", type=str, default=None,
        help="write a jax.profiler trace of steps 10-20 here",
    )
    parser.add_argument(
        "--profile_at", type=int, default=None,
        help="capture a jax.profiler trace over steps [N, N+10) of this "
        "run (relative to the resume point); the trace lands in "
        "--profile_dir, defaulting to <train.path.log_path>/profile",
    )
    parser.add_argument(
        "--faults", type=str, default=None,
        help="deterministic fault-injection spec for resilience drills, "
        "e.g. 'nan_grads@120;sigterm@500' (sets SPEAKINGSTYLE_FAULTS; "
        "see training/faults.py for the grammar)",
    )
    return parser


def main(args):
    import os

    if args.faults:
        from speakingstyle_tpu.training.faults import ENV_VAR, FaultPlan

        FaultPlan.parse(args.faults)  # validate the spec before training
        os.environ[ENV_VAR] = args.faults

    if os.environ.get("SPEAKINGSTYLE_MULTIHOST"):
        # Pod-slice training: every host runs this process; initialize()
        # must precede any other JAX call so the hosts form one global
        # mesh (coordinator discovery is automatic on TPU VMs). See
        # scripts/train_multihost.sh.
        import jax

        jax.distributed.initialize()
    import jax

    from speakingstyle_tpu.parallel.mesh import make_mesh, resolve_mesh
    from speakingstyle_tpu.training.trainer import run_training

    cfg = config_from_args(args)
    par = cfg.train.parallel
    flags_given = args.data_parallel is not None or args.model_parallel is not None
    if not par.is_single() and not flags_given:
        # train.parallel.* is the multichip contract: mesh != [1,1]
        # engages the mesh path; [1,1] leaves mesh=None (the single-chip
        # path, byte-for-byte the old behavior). Batch divisibility and
        # device-count fit are validated at startup (BatchShardingError /
        # ValueError name the fix).
        mesh = resolve_mesh(par)
    else:
        # legacy resolution, unchanged: CLI flags win, then the
        # train.sharding block, then all-device DP
        model_axis = (
            args.model_parallel
            if args.model_parallel is not None
            else cfg.train.sharding.model_axis
        )
        n_total = len(jax.devices())
        if args.data_parallel:
            data_axis = args.data_parallel
        elif cfg.train.sharding.data_axis > 0:
            data_axis = cfg.train.sharding.data_axis
        else:
            data_axis = n_total // model_axis
        n_dev = data_axis * model_axis
        mesh = (
            make_mesh(
                data=data_axis,
                model=model_axis,
                devices=jax.devices()[:n_dev],
            )
            if n_dev > 1
            else None
        )
    vocoder = None
    if args.synth and args.vocoder_ckpt:
        from speakingstyle_tpu.synthesis import get_vocoder

        vocoder = get_vocoder(cfg, args.vocoder_ckpt)
    profile_dir, profile_steps = args.profile_dir, (10, 20)
    if args.profile_at is not None:
        # --profile_at N: pull a trace from steps [N, N+10) without
        # needing to pick a directory (the serve-side twin is
        # POST /debug/profile)
        profile_steps = (args.profile_at, args.profile_at + 10)
        if profile_dir is None:
            profile_dir = os.path.join(cfg.train.path.log_path, "profile")
    state = run_training(
        cfg,
        mesh=mesh,
        restore_step=args.restore_step if args.restore_step != 0 else None,
        max_steps=args.max_steps,
        synth_callback="default" if args.synth else None,
        vocoder=vocoder,
        profile_dir=profile_dir,
        profile_steps=profile_steps,
    )
    print(f"training finished at step {int(state.step)}")


if __name__ == "__main__":
    main(build_parser().parse_args())
