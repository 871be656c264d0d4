"""``replica`` command: one cluster replica process.

The worker half of the distributed control plane (serving/cluster.py):
restores the checkpoint, AOT-precompiles the full shape-bucket lattice
(exactly the engine ``serve`` builds — replicas differ from the
single-process tier only in who routes to them), then registers with a
``ClusterRouter``'s control server and serves

  POST /dispatch   one coalesced batch over the wire (idempotency-keyed:
                   a hedge or retry of an executed batch answers from a
                   bounded cache instead of re-running the lattice)
  GET  /healthz    ready flag + compile/dispatch counters (the router's
                   adoption probe, and the zero-steady-state-compile
                   check of tests/test_cluster.py)
  POST /drain      stop admitting, finish in-flight, report not-ready

Liveness is a heartbeat lease: the process beats every
``serve.cluster.heartbeat_interval_s``; missing the miss budget expires
the lease router-side, requeueing any in-flight work there.  A beat
answered 409/410 (stale epoch / lost lease — e.g. after a healed
partition) re-registers with a bumped epoch.

When one replica spans hosts (``serve.parallel`` gives the engine a
multi-host mesh slice), pass ``--coordinator_address`` (+
``--num_processes``/``--process_id``) and the process joins the jax
distributed runtime before any device work — each *replica* is then a
whole jax process group, and the control plane above it is unchanged.

Usually spawned by ``serve --cluster`` rather than by hand.
"""

import argparse
import os
import signal
import threading

from speakingstyle_tpu.cli import add_config_args, config_from_args


def build_parser(parser=None):
    parser = parser or argparse.ArgumentParser(description=__doc__)
    add_config_args(parser, required=True)
    parser.add_argument("--restore_step", type=int, required=True)
    parser.add_argument(
        "--replica_id", type=str, required=True,
        help="lease identity assigned by the router (e.g. r3)",
    )
    parser.add_argument(
        "--router", type=str, required=True,
        help="the ClusterRouter control server, host:port",
    )
    parser.add_argument(
        "--vocoder_ckpt", type=str, default=None,
        help="HiFi-GAN generator checkpoint (.pth.tar or .msgpack)",
    )
    parser.add_argument(
        "--griffin_lim", action="store_true",
        help="no neural vocoder: results carry the mel only",
    )
    parser.add_argument("--host", type=str, default="127.0.0.1",
                        help="bind address for the replica's HTTP server")
    parser.add_argument("--port", type=int, default=0,
                        help="bind port (0 = ephemeral)")
    parser.add_argument(
        "--coordinator_address", type=str, default=None,
        help="jax.distributed coordinator (host:port) when this replica "
             "spans hosts; omitted = single-process replica",
    )
    parser.add_argument("--num_processes", type=int, default=None,
                        help="jax.distributed process count (with "
                             "--coordinator_address)")
    parser.add_argument("--process_id", type=int, default=None,
                        help="this process's jax.distributed index (with "
                             "--coordinator_address)")
    return parser


def main(args):
    cfg = config_from_args(args)
    # replica half of the fleet observability plane: size this process's
    # span ring and arm recording from the SAME serve.trace block the
    # router uses, so a fleet-wide trace has every hop recorded
    from speakingstyle_tpu.obs.trace import (
        configure_span_ring,
        set_tracing_enabled,
    )

    configure_span_ring(cfg.serve.trace.ring_capacity,
                        keep_traces=cfg.serve.trace.keep_traces)
    set_tracing_enabled(cfg.serve.trace.enabled)
    if args.coordinator_address:
        # multi-host replica: join the distributed runtime BEFORE any
        # device work so the engine's serve.parallel mesh sees every
        # host's devices
        import jax

        jax.distributed.initialize(
            coordinator_address=args.coordinator_address,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
    from speakingstyle_tpu.cli.serve import load_engine, model_version_string
    from speakingstyle_tpu.faults import FaultPlan
    from speakingstyle_tpu.obs import MetricsRegistry
    from speakingstyle_tpu.serving.cluster import ReplicaServer

    fault_plan = FaultPlan.from_env() or None
    if fault_plan:
        print(f"fault injection armed: {fault_plan.pending()}", flush=True)
    registry = MetricsRegistry()
    engine = load_engine(
        cfg, args.restore_step, vocoder_ckpt=args.vocoder_ckpt,
        griffin_lim=args.griffin_lim, registry=registry,
        fault_plan=fault_plan,
    )
    print(
        f"[{args.replica_id}] precompiling {len(engine.lattice)} lattice "
        "points before registering ...", flush=True,
    )
    secs = engine.precompile()
    print(
        f"[{args.replica_id}] {engine.compile_count} programs in "
        f"{secs:.1f}s; registering with {args.router}", flush=True,
    )
    server = ReplicaServer(
        engine, args.replica_id, args.router, cfg.serve.cluster,
        registry=registry, host=args.host, port=args.port, pid=os.getpid(),
    )
    server.start()
    print(
        f"[{args.replica_id}] serving on http://{server.host}:{server.port} "
        f"(lease ttl {cfg.serve.cluster.lease_ttl_s:g}s)", flush=True,
    )

    # SIGTERM contract mirrors serve: stop admitting (heartbeats report
    # not-ready, dispatches answer 503), let in-flight finish, exit.
    def _sigterm(signum, frame):
        print(f"[{args.replica_id}] SIGTERM: draining ...", flush=True)
        server._draining = True

        def _stop():
            threading.Event().wait(cfg.serve.fleet.drain_timeout_s)
            server.close()

        threading.Thread(target=_stop, name="replica-shutdown",
                         daemon=True).start()

    signal.signal(signal.SIGTERM, _sigterm)
    try:
        server.wait_closed()
    except KeyboardInterrupt:
        server.close()
    return 0


if __name__ == "__main__":
    main(build_parser().parse_args())
