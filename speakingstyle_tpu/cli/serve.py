"""``serve`` command: the continuous-batching text->wav HTTP server.

Starts the AOT-precompiled synthesis engine (serving/engine.py) over the
checkpoint named by ``--restore_step``, precompiles the full shape-bucket
lattice (``serve.*`` config block), then serves:

  POST /synthesize  {"text": ..., "speaker_id"?, "pitch_control"?,
                     "energy_control"?, "duration_control"?, "style_id"?,
                     "ref_audio"? (serve.style.ref_dir-confined path),
                     "priority"? (SLO class)}
                    -> audio/wav (429 + Retry-After under backpressure)
  POST /styles      upload a reference wav -> {"style_id": sha256, ...};
                    content-addressed and cached, so a repeat style skips
                    the reference encoder entirely (serving/style.py)
  GET  /styles      -> resident embedding-cache entries
  POST /synthesize/stream -> chunked audio/wav: overlap-trimmed windows
                       emitted as they are vocoded (serving/streaming.py)
                       — time-to-first-audio is the first-window bound
  POST /synthesize/longform -> chapter-length chunked audio/wav
                       (serving/longform.py): sentence-boundary chunking
                       + crossfade stitching through the batcher, or one
                       seq-sharded ring-attention program per chapter
                       when serve.longform.mesh_seq > 1
  GET  /healthz     -> engine/batcher stats (compile counter must stay at
                       its post-startup value: steady state never
                       compiles); 503 with per-replica lifecycle states
                       until at least one replica finished precompile
  GET  /metrics     -> Prometheus text: the same registry snapshot
                       (compile counters, queue depth, per-bucket dispatch
                       latency histograms, program FLOPs/peak-bytes gauges,
                       achieved-FLOP/s histograms, TTFA + replica-state
                       gauges, process RSS/uptime)
  GET  /debug/programs -> one ProgramCard JSON per compiled XLA program
                       (per-lattice-point FLOPs + memory accounting)
  POST /debug/profile?seconds=N -> pull a jax.profiler trace from the
                       live process (serve.debug_profile gates it)

``--replicas N`` (or ``serve.fleet.replicas``) > 1 serves through the
fleet router (serving/fleet.py): N replica engines warm up on background
threads (cheap under the persistent compile cache), requests carry
priority classes dispatched earliest-deadline-first, and queue-depth
watermarks shed load with 429s before latency collapses. SIGTERM drains
in-flight streams before the process exits.

No reference counterpart: the reference's synthesize.py is one-shot and
pays a fresh CUDA/compile warmup per invocation.
"""

import argparse
import signal
import threading

from speakingstyle_tpu.cli import add_config_args, config_from_args


def build_parser(parser=None):
    parser = parser or argparse.ArgumentParser(description=__doc__)
    add_config_args(parser, required=True)
    parser.add_argument("--restore_step", type=int, required=True)
    parser.add_argument(
        "--ref_audio", type=str, default=None,
        help="default style-reference wav used when a request carries none",
    )
    parser.add_argument(
        "--vocoder_ckpt", type=str, default=None,
        help="HiFi-GAN generator checkpoint (.pth.tar or .msgpack)",
    )
    parser.add_argument(
        "--griffin_lim", action="store_true",
        help="no neural vocoder: /synthesize returns the mel as JSON",
    )
    parser.add_argument("--host", type=str, default=None,
                        help="override serve.host")
    parser.add_argument("--port", type=int, default=None,
                        help="override serve.port")
    parser.add_argument(
        "--replicas", type=int, default=None,
        help="override serve.fleet.replicas: >1 serves through the fleet "
             "router (per-replica engines, EDF dispatch, load shedding)",
    )
    parser.add_argument(
        "--ref_dir", type=str, default=None,
        help="override serve.style.ref_dir: the allowlist directory for "
             'request "ref_audio" paths (unset = uploads via POST /styles '
             "only)",
    )
    parser.add_argument(
        "--cluster", action="store_true",
        help="serve through the distributed control plane (overrides "
             "serve.cluster.enabled): each replica is a separate PROCESS "
             "spawned via `speakingstyle-tpu replica`, registered over "
             "HTTP with heartbeat leases, dispatched with hedged retries "
             "(fleet mode only — needs --replicas > 1)",
    )
    parser.add_argument(
        "--enable_rollout", action="store_true",
        help="enable POST /admin/rollout (canary-gated rolling model "
             "upgrade; fleet mode only — overrides serve.rollout.enabled)",
    )
    return parser


def load_engine_parts(cfg, restore_step: int, vocoder_ckpt=None,
                      griffin_lim=False, strict=False, fault_plan=None,
                      events=None, registry=None):
    """Restore the acoustic checkpoint + vocoder ONCE; returns the
    (variables, vocoder, lattice, model, info) quintuple every replica
    engine shares — fleet replicas differ only in their compiled
    programs, so the host-side weights are loaded a single time.
    ``info`` pins the model identity ({step, weights_digest}) for the
    /healthz model block and X-Model-Version. ``strict=True`` refuses
    manifest-less checkpoints (the rollout verify gate)."""
    import jax

    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.serving.lattice import BucketLattice
    from speakingstyle_tpu.synthesis import get_vocoder
    from speakingstyle_tpu.training.checkpoint import CheckpointManager
    from speakingstyle_tpu.training.optim import make_optimizer
    from speakingstyle_tpu.training.state import TrainState

    lattice = BucketLattice.from_config(cfg.serve)
    n_position = max(lattice.max_mel, lattice.max_src, cfg.model.max_seq_len) + 1
    model = build_model(cfg, n_position=n_position)
    variables = init_variables(model, cfg, jax.random.PRNGKey(cfg.train.seed))
    state = TrainState.create(variables, make_optimizer(cfg.train))
    ckpt = CheckpointManager(
        cfg.train.path.ckpt_path, fault_plan=fault_plan, events=events,
        registry=registry,
    )
    try:
        state = ckpt.restore(
            state,
            step=restore_step if restore_step > 0 else None,
            ignore_layers=cfg.train.ignore_layers,
            strict=strict,
        )
        info = {
            "step": ckpt.last_restored_step,
            "weights_digest": ckpt.last_weights_digest,
        }
    finally:
        ckpt.close()
    vocoder = None if griffin_lim else get_vocoder(cfg, vocoder_ckpt)
    variables = {"params": state.params, "batch_stats": state.batch_stats}
    return variables, vocoder, lattice, model, info


def model_version_string(info) -> str:
    """``<step>:<digest prefix>`` — the X-Model-Version wire format."""
    digest = info.get("weights_digest") or "unverified"
    return f"{info.get('step')}:{digest[:12]}"


def load_engine(cfg, restore_step: int, vocoder_ckpt=None, griffin_lim=False,
                registry=None, fault_plan=None):
    """Restore the acoustic checkpoint + vocoder and build one engine.

    Shared by ``serve`` and ``synthesize`` so the CLI one-shot path and
    the server execute the identical padded-dispatch code.
    """
    from speakingstyle_tpu.serving.engine import SynthesisEngine

    variables, vocoder, lattice, model, _ = load_engine_parts(
        cfg, restore_step, vocoder_ckpt=vocoder_ckpt, griffin_lim=griffin_lim,
        fault_plan=fault_plan,
    )
    return SynthesisEngine(
        cfg, variables, vocoder=vocoder, lattice=lattice, model=model,
        registry=registry, fault_plan=fault_plan,
    )


def require_chips_for_cluster(replicas: int) -> None:
    """``--cluster`` spawns each replica as a process that needs its own
    chip, from a parent that restores the checkpoint and runs the style
    service — and so already holds every chip of this host. A chip belongs
    to one process, so on a TPU backend the spawn can only fail or hang:
    refuse at start-up instead. (CPU replicas share the host freely; TPU
    replicas belong on one host each.)"""
    import jax

    if jax.default_backend() != "tpu":
        return
    held = jax.local_device_count()
    raise SystemExit(
        f"serve --cluster: this process holds all {held} TPU chip(s) of "
        f"this host, so 0 are free for the {replicas} replica process(es) "
        "it would spawn (a chip belongs to one process). On TPU, --cluster "
        "needs one host per replica; on one host use --replicas N without "
        "--cluster (in-process engines)."
    )


def main(args):
    from speakingstyle_tpu.serving.server import (
        SynthesisServer,
        TextFrontend,
        load_ref_mel,
    )

    cfg = config_from_args(args)
    # fleet observability plane: size the span ring from config and arm
    # (or disarm) span recording before any serving component starts
    from speakingstyle_tpu.obs.trace import (
        configure_span_ring,
        get_span_ring,
        set_tracing_enabled,
    )

    tcfg = cfg.serve.trace
    configure_span_ring(tcfg.ring_capacity, keep_traces=tcfg.keep_traces)
    set_tracing_enabled(tcfg.enabled)
    # ONE deterministic fault plan from SPEAKINGSTYLE_FAULTS, threaded to
    # every serving component — a single shared plan keeps the @N counters
    # exact (building a plan per component would double-fire each entry)
    from speakingstyle_tpu.faults import FaultPlan

    fault_plan = FaultPlan.from_env() or None
    if fault_plan:
        print(f"fault injection armed: {fault_plan.pending()}", flush=True)
    if getattr(args, "ref_dir", None):
        import dataclasses

        cfg = dataclasses.replace(cfg, serve=dataclasses.replace(
            cfg.serve, style=dataclasses.replace(
                cfg.serve.style, ref_dir=args.ref_dir
            )
        ))
    replicas = (
        args.replicas if args.replicas is not None
        else cfg.serve.fleet.replicas
    )
    default_ref = (
        load_ref_mel(cfg, args.ref_audio) if args.ref_audio else None
    )
    events = None
    if cfg.serve.log_events:
        from speakingstyle_tpu.obs import JsonlEventLog

        events = JsonlEventLog(
            cfg.train.path.log_path,
            max_bytes=cfg.train.obs.events_max_bytes,
            keep=cfg.train.obs.events_keep,
        )
    autoscaler = None
    if replicas > 1:
        # fleet mode: load the checkpoint once, warm replicas on
        # background threads (persistent compile cache makes scale-up
        # cheap) — the server binds immediately and /healthz reports 503
        # until the first replica finishes its precompile
        from speakingstyle_tpu.obs import MetricsRegistry
        from speakingstyle_tpu.serving.engine import SynthesisEngine
        from speakingstyle_tpu.serving.fleet import FleetRouter
        from speakingstyle_tpu.serving.style import StyleService

        cluster_mode = args.cluster or cfg.serve.cluster.enabled
        if cluster_mode:
            require_chips_for_cluster(replicas)
        registry = MetricsRegistry()
        variables, vocoder, lattice, model, info = load_engine_parts(
            cfg, args.restore_step,
            vocoder_ckpt=args.vocoder_ckpt, griffin_lim=args.griffin_lim,
            fault_plan=fault_plan, events=events, registry=registry,
        )
        # ONE style service across all replicas: one embedding cache,
        # one AOT encoder lattice (the first replica's warm-up compiles
        # it; the rest find it ready)
        style = (
            StyleService(cfg, variables, registry=registry,
                         fault_plan=fault_plan)
            if cfg.model.use_reference_encoder else None
        )

        def factory(reg: "MetricsRegistry") -> "SynthesisEngine":
            return SynthesisEngine(
                cfg, variables, vocoder=vocoder, lattice=lattice,
                model=model, registry=reg, style=style,
                fault_plan=fault_plan,
            )

        if cluster_mode:
            # distributed control plane: replicas are separate processes
            # spawned as `speakingstyle-tpu replica`, each restoring the
            # same checkpoint and precompiling its own lattice.  The
            # parent keeps the checkpoint load above only for the model
            # identity + the shared style service (style vectors resolve
            # router-side and ship over the wire as gamma/beta)
            import subprocess
            import sys

            from speakingstyle_tpu.serving.cluster import ClusterRouter

            def spawn(replica_id, router_addr, extra):
                cmd = [
                    sys.executable, "-m", "speakingstyle_tpu", "replica",
                    "--replica_id", replica_id, "--router", router_addr,
                    "--restore_step",
                    str((extra or {}).get("restore_step",
                                          args.restore_step)),
                ]
                if args.preset:
                    cmd += ["--preset", args.preset]
                for flag, val in (("-p", args.preprocess_config),
                                  ("-m", args.model_config),
                                  ("-t", args.train_config)):
                    if val:
                        cmd += [flag, val]
                if args.vocoder_ckpt:
                    cmd += ["--vocoder_ckpt", args.vocoder_ckpt]
                if args.griffin_lim:
                    cmd += ["--griffin_lim"]
                return subprocess.Popen(cmd)

            router = ClusterRouter(
                spawn, cfg, replicas=replicas,
                registry=registry, events=events, style=style,
                fault_plan=fault_plan,
            )
            print(
                f"cluster control plane on "
                f"http://{router.control_addr} (lease ttl "
                f"{cfg.serve.cluster.lease_ttl_s:g}s, quorum "
                f"{cfg.serve.cluster.quorum})", flush=True,
            )
        else:
            router = FleetRouter(
                factory, cfg, replicas=replicas,
                registry=registry, events=events, style=style,
                fault_plan=fault_plan,
            )
        router.set_model_version(
            model_version_string(info), info.get("step"),
            info.get("weights_digest"),
        )
        print(
            f"warming {replicas} replicas x {len(router.lattice)} lattice "
            "points in the background (healthz: 503 until ready) ...",
            flush=True,
        )
        if cfg.serve.autoscale.enabled:
            from speakingstyle_tpu.serving.autoscale import Autoscaler

            acfg = cfg.serve.autoscale
            autoscaler = Autoscaler(router, acfg)
            print(
                f"autoscaler armed: [{acfg.min_replicas}, "
                f"{acfg.max_replicas}] replicas, tick {acfg.interval_s}s "
                f"(serve_autoscale_target tracks decisions)", flush=True,
            )
        lifecycle = None
        if args.enable_rollout or cfg.serve.rollout.enabled:
            from speakingstyle_tpu.serving.lifecycle import RolloutManager

            def verify_and_build(step: int):
                # the rollout verify gate: strict manifest-checked
                # restore — corrupt/manifest-less candidates abort here,
                # before any replica is touched
                v2, voc2, lat2, mdl2, info2 = load_engine_parts(
                    cfg, step, vocoder_ckpt=args.vocoder_ckpt,
                    griffin_lim=args.griffin_lim, strict=True,
                    fault_plan=fault_plan, events=events, registry=registry,
                )
                if cluster_mode:
                    # canary = a remote replica process restoring the
                    # candidate step; the strict load above stays the
                    # verify gate (corrupt candidates abort here)
                    return (
                        router.remote_factory({"restore_step": step}),
                        model_version_string(info2), info2,
                    )

                def factory2(reg):
                    return SynthesisEngine(
                        cfg, v2, vocoder=voc2, lattice=lat2, model=mdl2,
                        registry=reg, style=style, fault_plan=fault_plan,
                    )

                return factory2, model_version_string(info2), info2

            lifecycle = RolloutManager(router, verify_and_build,
                                       autoscaler=autoscaler, events=events)
            print("rollout enabled: POST /admin/rollout {\"step\": N}",
                  flush=True)
        server = SynthesisServer(
            frontend=TextFrontend(cfg, default_ref),
            host=args.host,
            port=args.port,
            events=events,
            router=router,
            lifecycle=lifecycle,
        )
    else:
        if args.enable_rollout:
            print("warning: --enable_rollout needs fleet mode "
                  "(--replicas > 1); ignoring", flush=True)
        if args.cluster:
            print("warning: --cluster needs fleet mode "
                  "(--replicas > 1); ignoring", flush=True)
        from speakingstyle_tpu.serving.engine import SynthesisEngine

        variables, vocoder, lattice, model, info = load_engine_parts(
            cfg, args.restore_step,
            vocoder_ckpt=args.vocoder_ckpt, griffin_lim=args.griffin_lim,
            fault_plan=fault_plan, events=events,
        )
        engine = SynthesisEngine(
            cfg, variables, vocoder=vocoder, lattice=lattice, model=model,
            fault_plan=fault_plan,
        )
        has_style = engine.style is not None
        style_points = len(engine.style.lattice) if has_style else 0
        print(
            f"precompiling {len(engine.lattice)} lattice points "
            f"+ {style_points} style-encoder points ...", flush=True,
        )
        secs = engine.precompile()
        style_n = engine.style.compile_count if has_style else 0
        print(
            f"precompiled {engine.compile_count} synthesis + {style_n} "
            f"style programs in {secs:.1f}s; steady-state serving "
            "performs zero compiles", flush=True,
        )
        server = SynthesisServer(
            engine,
            TextFrontend(cfg, default_ref),
            host=args.host,
            port=args.port,
            events=events,
            model_info=dict(info, version=model_version_string(info)),
        )
        if cfg.serve.longform.mesh_seq > 1:
            # ring tier: the chapter-length free-run as ONE seq-sharded
            # program set, compiled now (startup, not request path) and
            # attached to the server's auto-built LongformService so both
            # tiers share the one batcher/engine. Fleet mode serves the
            # chunked tier only — a ring tier would need its own
            # per-replica seq mesh, and the chunked tier already rides
            # the replicas.
            from speakingstyle_tpu.serving.longform import RingTier

            ring = RingTier(cfg, variables, engine)
            print(
                f"precompiling {len(ring.lattice)} ring-attention "
                f"long-form points (seq mesh of "
                f"{cfg.serve.longform.mesh_seq}) ...", flush=True,
            )
            ring_secs = ring.precompile()
            print(f"ring tier ready in {ring_secs:.1f}s", flush=True)
            server.longform.ring = ring

    # SLO burn-rate engine (obs/slo.py): multi-window burn rates per
    # traffic class against serve.slo.objectives, published as
    # serve_slo_burn_rate gauges + slo_alert events + /healthz slo block
    slo = None
    if cfg.serve.slo.enabled:
        from speakingstyle_tpu.obs.slo import SloEngine

        slo = SloEngine(server.registry, cfg.serve.slo, events=events,
                        trace_ring=get_span_ring())
        server.slo = slo
        print(
            f"SLO engine armed: objectives "
            f"{dict(cfg.serve.slo.objectives)}, windows "
            f"{cfg.serve.slo.fast_window_s:g}s/"
            f"{cfg.serve.slo.slow_window_s:g}s", flush=True,
        )

    # SIGTERM contract: stop accepting, drain in-flight streams (up to
    # serve.fleet.drain_timeout_s), flush admitted requests, exit.
    # shutdown() must run off the serve_forever thread.
    def _sigterm(signum, frame):
        print("SIGTERM: draining in-flight streams ...", flush=True)
        threading.Thread(
            target=server.shutdown, name="server-shutdown", daemon=True
        ).start()

    signal.signal(signal.SIGTERM, _sigterm)

    host, port = server.address[:2]
    print(
        f"latency pipeline: frontend_workers={cfg.serve.frontend_workers} "
        f"(0 = inline G2P), stream_depth={cfg.serve.fleet.stream_depth} "
        "(1 = sequential vocode)", flush=True,
    )
    print(f"serving on http://{host}:{port} "
          "(POST /synthesize, POST /synthesize/stream, "
          "POST /synthesize/longform, POST /styles, GET /styles, "
          "GET /healthz, GET /metrics, GET /debug/programs, "
          "POST /debug/profile?seconds=N)", flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        print("shutting down (flushing admitted requests) ...", flush=True)
    finally:
        # stop the policy loop before the drain: a scale decision
        # landing mid-shutdown would race the router's own teardown
        if autoscaler is not None:
            autoscaler.close()
        if slo is not None:
            slo.close()
        server.shutdown()
        if events is not None:
            events.close()


if __name__ == "__main__":
    main(build_parser().parse_args())
