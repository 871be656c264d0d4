"""Fleet router: N replica engines behind one SLO-aware admission queue.

The production shape of the serving stack (ROADMAP item 2): instead of
one engine on one device, a ``FleetRouter`` owns N replicas — each a
full ``SynthesisEngine`` with its own AOT-precompiled lattice — behind a
single admission queue that knows about service-level objectives:

  * **Priority classes.** Every request carries a class name
    (``serve.fleet.class_deadline_ms`` keys, e.g. ``interactive`` /
    ``batch``); its SLO deadline is ``arrival + class budget``.
  * **Earliest-deadline-first dispatch.** The pending structure is a
    bounded heap ordered by SLO deadline: whichever replica frees next
    pops the most urgent work, so an interactive request admitted after
    a burst of batch work still dispatches first. Coalescing within one
    replica dispatch follows the single-engine batcher's rule (greedy
    drain, then wait until the oldest *dispatch-by* instant,
    ``arrival + serve.max_wait_ms``).
  * **Explicit backpressure.** Queue-depth watermarks
    (``shed_high_watermark``/``shed_low_watermark`` fractions of
    ``fleet.queue_depth``, with hysteresis) shed load by raising
    ``Overloaded`` — surfaced as HTTP 429 + Retry-After and counted in
    ``serve_shed_total``, deliberately distinct from the shutdown path's
    ``ShutdownError``/``serve_rejected_total``.
  * **Elastic warm-up.** ``scale_to(n)`` adds replicas that move through
    an explicit lifecycle — cold → warming (building + precompiling on a
    background thread; cheap when the persistent compile cache is warm)
    → ready → draining → stopped — published per replica as the
    ``serve_replica_state`` gauge, and `/healthz` reports 503 until at
    least one replica is ready so load balancers never route into a
    compile storm.

Every replica preserves the engine's zero-steady-state-compiles
invariant independently: the router never creates programs, it only
routes into each replica's precompiled lattice (streaming windows
included — serving/streaming.py rides the same vocoder buckets).

**Supervision** (serving/resilience.py, ARCHITECTURE.md "Serving
resilience"): a replica whose dispatch raises — or exceeds the
``fleet.hang_watchdog_s`` watchdog — transitions to a sixth lifecycle
state, ``failed``; its in-flight requests are requeued onto healthy
replicas (exactly-once: the hung worker's late results are discarded via
a claim handshake on ``Replica.inflight``), each burning one unit of its
class's ``fleet.retry_budget`` before resolving as ``ReplicaError``.
The failed replica is circuit-broken with exponential-backoff re-warm
through the same cold → warming → ready lifecycle (cheap under the
persistent compile cache).  EDF is also an enforced guarantee now: a
request popped past its class deadline budget resolves as
``DeadlineExceeded`` (504) instead of dispatching late.
"""

import heapq
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Optional

import numpy as np

from speakingstyle_tpu.faults import FaultPlan
from speakingstyle_tpu.obs import JsonlEventLog, MetricsRegistry
from speakingstyle_tpu.obs.trace import Span, TailSampler, get_span_ring
from speakingstyle_tpu.serving import streaming
from speakingstyle_tpu.serving.batcher import (
    DrainRateEstimator,
    Overloaded,
    ShutdownError,
)
from speakingstyle_tpu.serving.engine import (
    SynthesisEngine,
    SynthesisRequest,
    SynthesisResult,
    bucket_label,
)
from speakingstyle_tpu.serving.lattice import BucketLattice, StyleLattice
from speakingstyle_tpu.obs.locks import make_lock
from speakingstyle_tpu.serving.resilience import (
    CircuitBreaker,
    DeadlineExceeded,
    DispatchError,
    InjectedFault,
    ReplicaError,
)

# replica lifecycle states (serve_replica_state gauge values in parens)
COLD = "cold"          # (0) constructed, nothing compiled
WARMING = "warming"    # (1) building the engine / precompiling the lattice
READY = "ready"        # (2) dispatching
DRAINING = "draining"  # (3) finishing in-flight work, admitting nothing
STOPPED = "stopped"    # (4) worker exited
FAILED = "failed"      # (5) dispatch raised/hung; circuit-broken, awaiting
#                            its breaker backoff before a re-warm trial
STATE_CODE = {COLD: 0, WARMING: 1, READY: 2, DRAINING: 3, STOPPED: 4,
              FAILED: 5}


@dataclass(order=True)
class _Pending:
    """One admitted request in the EDF heap (orders by SLO deadline)."""

    slo_deadline: float
    seq: int
    request: SynthesisRequest = field(compare=False)
    future: Future = field(compare=False)
    dispatch_by: float = field(compare=False)  # coalescing deadline
    klass: str = field(compare=False)
    # replica-failure requeues survived so far (bounded by the class's
    # fleet.retry_budget)
    retries: int = field(compare=False, default=0)
    # wall-clock submit stamp: the queue-wait span's start_ts (span
    # timestamps must be wall clock — they cross processes); the
    # monotonic twin measures the span's DURATION (JL009: wall deltas
    # jump under NTP)
    submit_wall: float = field(compare=False, default=0.0)
    submit_mono: float = field(compare=False, default=0.0)


class Replica:
    """One engine plus its lifecycle state and dispatch thread."""

    def __init__(self, index: int, breaker: CircuitBreaker):
        self.index = index
        self.engine: Optional[SynthesisEngine] = None
        self.state = COLD
        self.error: Optional[BaseException] = None
        self.worker: Optional[threading.Thread] = None
        self.breaker = breaker
        # exactly-once handshake with the hang watchdog: the batch this
        # replica is dispatching right now.  The worker claims results
        # back under the router lock; if the supervisor stole the batch
        # first (hang), the worker finds ``inflight is not batch`` and
        # discards.  ``generation`` orphans a hung worker across a
        # re-warm: state transitions from a stale generation are ignored.
        self.inflight: Optional[List["_Pending"]] = None
        self.dispatch_started: Optional[float] = None
        self.dispatch_n = 0
        self.generation = 0
        # model-lifecycle pin (serving/lifecycle.py): a replica started
        # with an explicit factory re-warms with THAT factory forever —
        # a mid-rollout breaker re-warm of an old replica must rebuild
        # the OLD weights, never silently pick up the candidate's
        self.factory: Optional[Callable] = None
        self.version: Optional[str] = None


class FleetRouter:
    """SLO-aware admission + EDF dispatch over N replica engines.

    ``engine_factory(registry)`` builds one (un-precompiled) replica
    engine sharing the fleet's metrics registry; the router precompiles
    it during warm-up. The router exposes the same ``submit -> Future``
    surface as ``ContinuousBatcher`` so the HTTP server treats either as
    its dispatch backend.
    """

    def __init__(
        self,
        engine_factory: Callable[[MetricsRegistry], SynthesisEngine],
        cfg,
        replicas: Optional[int] = None,
        registry: Optional[MetricsRegistry] = None,
        events: Optional[JsonlEventLog] = None,
        style=None,  # StyleService shared by every replica (cli/serve.py
        # builds one and closes the factory over it): one embedding
        # cache, one encoder lattice — a style uploaded once is warm
        # fleet-wide. None = replicas own private services (tests).
        fault_plan: Optional[FaultPlan] = None,  # SPEAKINGSTYLE_FAULTS
        # plan threaded in by cli/serve.py or a chaos test; consumes the
        # replica_raise@N / replica_hang@N kinds (N = router-global
        # dispatch counter, 1-based). None = no injection.
        tier: Optional[str] = None,  # quality-tier name when this router
        # serves one tier of a TierRouter ("teacher-f32", "student-int8",
        # ...); stamped onto every result as SynthesisResult.tier.
        # None = untiered (the historical single-router deployment).
    ):
        serve = cfg.serve
        fleet = serve.fleet
        self.cfg = cfg
        self.fleet = fleet
        self.tier = tier
        self.engine_factory = engine_factory
        self.registry = registry if registry is not None else MetricsRegistry()
        self.events = events
        self.style = style
        self.lattice = BucketLattice.from_config(serve)
        # admission geometry for raw-reference requests (engine-free,
        # like self.lattice: admission must work while replicas warm)
        self.style_lattice = StyleLattice.from_config(serve)
        self.max_batch = self.lattice.max_batch
        self.max_wait = serve.max_wait_ms / 1e3
        self._frames_per_phoneme = serve.frames_per_phoneme

        self._cond = make_lock("FleetRouter._cond", kind="condition")
        self._heap: List[_Pending] = []
        self._seq = 0
        self._closing = False
        self._shedding = False
        self._replicas: List[Replica] = []
        self._stream_overlap: Optional[int] = None
        self.fault_plan = fault_plan
        self._dispatch_total = 0  # router-global, under self._cond; the
        # counter the replica_raise@N / replica_hang@N fault kinds index
        self._watchdog = fleet.hang_watchdog_s
        # model-lifecycle surface (serving/lifecycle.py): the running
        # version string + a scale-down hold the autoscaler honors while
        # a rollout's canary surge is live
        self.rollout_active = False
        self.model_version: Optional[str] = None
        self.model_step: Optional[int] = None
        self.model_digest: Optional[str] = None
        # tail-sampling surface: interesting traces (shed / 504 / miss /
        # hedge-won) are pinned into the process span ring the moment
        # this router detects them; the trace id of the most recent such
        # pressure signal also rides the autoscale event (the operator
        # jumps from a scale decision to the trace that triggered it)
        self._trace_ring = get_span_ring()
        trace_cfg = getattr(serve, "trace", None)
        self._tail_sampler = TailSampler(
            trace_cfg.sample_rate if trace_cfg is not None else 0.1
        )
        self.last_pressure_trace_id: Optional[str] = None
        # golden-probe traffic class (obs/quality.py plane): admitted
        # with its own deadline budget but EXCLUDED from shed/pressure
        # accounting, the latency SLO stream, and the autoscaler's
        # queue/occupancy signals — synthetic replays must never page
        # latency or distort scaling (serving/probes.py)
        qcfg = getattr(serve, "quality", None)
        self._probe_class = (
            qcfg.probe_class if qcfg is not None else "probe"
        )
        self._probe_deadline_ms = (
            qcfg.probe_deadline_ms if qcfg is not None else 30_000.0
        )

        self._shed_ctr = self.registry.counter(
            "serve_shed_total",
            help="submits shed by backpressure (429, NOT shutdown)",
        )
        self._rejected_ctr = self.registry.counter(
            "serve_rejected_total", help="submits refused at/after shutdown"
        )
        self._pending_gauge = self.registry.gauge(
            "serve_queue_depth", help="router pending-heap occupancy"
        )
        self._latency_hist = self.registry.histogram(
            "serve_request_latency_seconds",
            help="request arrival -> result latency through the router",
        )
        self._queue_wait_hist = self.registry.histogram(
            "serve_queue_wait_seconds",
            help="submit -> dispatch-start wait (the coalescing window "
                 "the frontend pool overlaps with)",
        )
        self._ttfa_hist = self.registry.histogram(
            "serve_ttfa_seconds",
            help="request arrival -> first streamed wav chunk ready",
        )
        self._requeued_ctr = self.registry.counter(
            "serve_requeued_total",
            help="in-flight requests requeued off a failed replica",
        )
        # measured queue drain throughput: Retry-After on a 429 is
        # derived from this (hysteresis gap / rate), not a constant
        self.drain_rate = DrainRateEstimator()
        # measured warm-up cost (engine build + lattice precompile wall
        # time, sampled per warm-up): the autoscaler's scale-up cost
        # model and the capacity artifact both read this histogram
        self._warmup_hist = self.registry.histogram(
            "serve_replica_warmup_seconds",
            help="wall seconds from scale-up to READY (engine build + "
                 "lattice precompile; cheap when the persistent compile "
                 "cache is warm)",
        )
        self.scale_to(replicas if replicas is not None else fleet.replicas)
        # the supervisor owns the hang watchdog and the breaker re-warm
        # schedule; it wakes on the cond (close notifies it) or every
        # interval, whichever is sooner
        self._supervise_interval = max(0.005, min(
            0.25,
            fleet.rewarm_backoff_s / 2.0,
            self._watchdog / 4.0 if self._watchdog > 0 else 0.25,
        ))
        self._supervisor = threading.Thread(
            target=self._supervise, name="fleet-supervisor", daemon=True
        )
        self._supervisor.start()

    # -- replica lifecycle --------------------------------------------------

    def _set_state(self, rep: Replica, state: str) -> None:
        """Caller must hold ``self._cond``."""
        rep.state = state
        self.registry.gauge(
            "serve_replica_state",
            labels={"replica": str(rep.index)},
            help="replica lifecycle: 0=cold 1=warming 2=ready 3=draining "
                 "4=stopped 5=failed",
        ).set(STATE_CODE[state])
        if self.events is not None:
            self.events.emit(
                "replica_state", replica=rep.index, state=state
            )
        self._cond.notify_all()

    def _set_breaker_gauge(self, rep: Replica) -> None:
        self.registry.gauge(
            "serve_replica_breaker_state",
            labels={"replica": str(rep.index)},
            help="replica circuit breaker: 0=closed 1=open 2=half_open",
        ).set(rep.breaker.code)

    def scale_to(self, n: int) -> None:
        """Elastically grow or shrink the ready+warming replica set.

        Growth spawns warm-up threads (engine build + lattice precompile
        off the caller's thread — the persistent compile cache makes this
        a ~seconds operation when warm); shrink marks the newest replicas
        DRAINING: they finish their in-flight dispatch, stop pulling
        work, and stop.
        """
        if n < 0:
            raise ValueError(f"scale_to requires n >= 0, got {n}")
        with self._cond:
            if self._closing:
                raise ShutdownError("router is closed")
            live = [r for r in self._replicas
                    if r.state in (COLD, WARMING, READY, FAILED)]
            for rep in live[n:]:          # shrink newest-first
                if rep.state == READY:
                    self._set_state(rep, DRAINING)
                else:   # cold/warming/failed: nothing in flight to drain
                    self._set_state(rep, STOPPED)
            grow = n - len(live)
            new = []
            for _ in range(max(0, grow)):
                rep = Replica(len(self._replicas), CircuitBreaker(
                    self.fleet.rewarm_backoff_s,
                    self.fleet.rewarm_backoff_max_s,
                ))
                self._replicas.append(rep)
                self._set_state(rep, COLD)
                self._set_breaker_gauge(rep)
                new.append(rep)
        for rep in new:
            t = threading.Thread(
                target=self._warm, args=(rep,),
                name=f"replica-{rep.index}-warmup", daemon=True,
            )
            t.start()

    def _warm(self, rep: Replica) -> None:
        """Background warm-up: build the engine, precompile the lattice,
        go READY, and start the dispatch worker."""
        with self._cond:
            if rep.state != COLD:   # shrunk away before warm-up began
                return
            self._set_state(rep, WARMING)
            # capture the per-replica factory while still holding the
            # lock: a concurrent rollout may stamp rep.factory from the
            # control thread, and this read must see a settled value
            factory = rep.factory if rep.factory is not None \
                else self.engine_factory
        t0 = time.monotonic()
        try:
            engine = factory(self.registry)
            # bind the engine's quality choke point (obs/quality.py) to
            # this fleet's tier name and trace plumbing so a failing wav
            # pins its trace exactly like a latency incident does
            gate = getattr(engine, "quality", None)
            if gate is not None:
                gate.bind(
                    tier=self.tier, trace_ring=self._trace_ring,
                    tail_sampler=self._tail_sampler, events=self.events,
                )
            secs = engine.precompile()
            self.registry.gauge(
                "serve_replica_precompile_seconds",
                labels={"replica": str(rep.index)},
                help="wall seconds the replica's lattice precompile took",
            ).set(secs)
            self._warmup_hist.observe(time.monotonic() - t0)
        except BaseException as e:
            with self._cond:
                rep.error = e
                if rep.breaker.state == "half_open":
                    # a re-warm trial failed: re-open the breaker with a
                    # doubled backoff and try again later, instead of
                    # giving the replica up for good
                    rep.breaker.record_failure(time.monotonic())
                    self._set_breaker_gauge(rep)
                    self._set_state(rep, FAILED)
                else:       # initial warm-up never worked: stop for good
                    self._set_state(rep, STOPPED)
            if self.events is not None:
                self.events.emit(
                    "replica_warm_failed", replica=rep.index,
                    error=type(e).__name__,
                )
            return
        with self._cond:
            if rep.state != WARMING:  # shrunk away mid-warm-up
                return
            rep.engine = engine
            rep.generation += 1       # orphan any worker from a past life
            gen = rep.generation
            self._set_state(rep, READY)
            # publish AND start the worker under the lock: close() joins
            # every non-None rep.worker, and join() on a never-started
            # thread raises, so the handle must not be visible before
            # start().  The worker's first acquire of _cond just blocks
            # until this block releases.
            worker = threading.Thread(
                target=self._worker, args=(rep, gen),
                name=f"replica-{rep.index}-dispatch", daemon=True,
            )
            worker.start()
            rep.worker = worker

    def states(self) -> Dict[int, str]:
        with self._cond:
            return {r.index: r.state for r in self._replicas}

    def ready(self) -> bool:
        with self._cond:
            return any(r.state == READY for r in self._replicas)

    def wait_ready(self, timeout: float = 120.0,
                   n: Optional[int] = None) -> bool:
        """Block until ``n`` replicas are READY (default 1 — the
        /healthz readiness bar) or warm-up can no longer get there
        (every replica stopped, or the deadline passed)."""
        want = 1 if n is None else n
        deadline = time.monotonic() + timeout
        with self._cond:
            while True:
                if sum(r.state == READY for r in self._replicas) >= want:
                    return True
                if all(r.state == STOPPED for r in self._replicas):
                    return False
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cond.wait(timeout=remaining)

    def engines(self) -> List[SynthesisEngine]:
        with self._cond:
            return [r.engine for r in self._replicas if r.engine is not None]

    def engine_at(self, index: int) -> Optional[SynthesisEngine]:
        with self._cond:
            return self._replicas[index].engine

    # -- model lifecycle surface (serving/lifecycle.py drives these) ---------

    def start_replica(self, factory: Optional[Callable] = None,
                      version: Optional[str] = None) -> int:
        """Append ONE replica — optionally pinned to its own engine
        factory (the rollout canary builds candidate weights while
        ``self.engine_factory`` still builds the live version) — and
        warm it through the normal COLD->WARMING->READY lifecycle.
        Returns the new replica's index."""
        with self._cond:
            if self._closing:
                raise ShutdownError("router is closed")
            rep = Replica(len(self._replicas), CircuitBreaker(
                self.fleet.rewarm_backoff_s,
                self.fleet.rewarm_backoff_max_s,
            ))
            rep.factory = factory
            rep.version = version
            self._replicas.append(rep)
            self._set_state(rep, COLD)
            self._set_breaker_gauge(rep)
        threading.Thread(
            target=self._warm, args=(rep,),
            name=f"replica-{rep.index}-warmup", daemon=True,
        ).start()
        return rep.index

    def drain_replica(self, index: int) -> None:
        """Gracefully retire ONE specific replica (the rolling replace
        picks old-version replicas by index; ``scale_to`` only ever
        shrinks newest-first). READY drains — it finishes its in-flight
        dispatch and stops pulling work; cold/warming/failed stop
        immediately; draining/stopped is a no-op."""
        with self._cond:
            rep = self._replicas[index]
            if rep.state == READY:
                self._set_state(rep, DRAINING)
            elif rep.state in (COLD, WARMING, FAILED):
                self._set_state(rep, STOPPED)

    def wait_state(self, index: int, states, timeout: float = 120.0) -> bool:
        """Block until replica ``index`` reaches one of ``states``."""
        want = (states,) if isinstance(states, str) else tuple(states)
        deadline = time.monotonic() + timeout
        with self._cond:
            while self._replicas[index].state not in want:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cond.wait(timeout=remaining)
            return True

    def set_model_version(self, version: Optional[str],
                          step: Optional[int] = None,
                          digest: Optional[str] = None) -> None:
        """Publish the running model's identity: the
        ``serve_model_version`` gauge (numeric: checkpoint step), the
        ``X-Model-Version`` response header, and the /healthz model
        block all read this."""
        self.model_version = version
        self.model_step = step
        self.model_digest = digest
        if step is not None:
            self.registry.gauge(
                "serve_model_version",
                help="checkpoint step of the model version the fleet is "
                     "serving (see the /healthz model block for the digest)",
            ).set(step)

    # -- autoscaler signal surface (serving/autoscale.py reads these) -------

    def pending_depth(self) -> int:
        """Current EDF pending-heap occupancy, EXCLUDING probe-class
        entries: golden probes must not feed the autoscaler's queue
        signal (a probe burst is not tenant demand)."""
        with self._cond:
            return sum(
                p.klass != self._probe_class for p in self._heap
            )

    def live_replica_count(self) -> int:
        """Replicas counted by ``scale_to`` (cold/warming/ready/failed)
        — the autoscaler's notion of current capacity, warm-ups
        included so one queue spike cannot trigger a scale-up per tick
        while the first new replica is still compiling."""
        with self._cond:
            return sum(r.state in (COLD, WARMING, READY, FAILED)
                       for r in self._replicas)

    def occupancy(self) -> float:
        """Instantaneous busy fraction of READY replicas (a replica is
        busy while it holds an in-flight dispatch claim); 0.0 when none
        are READY. A claim holding ONLY probe-class requests does not
        count as busy — golden probes must not feed the autoscaler's
        occupancy signal."""
        with self._cond:
            ready = [r for r in self._replicas if r.state == READY]
            if not ready:
                return 0.0
            busy = sum(
                r.inflight is not None and any(
                    p.klass != self._probe_class for p in r.inflight
                )
                for r in ready
            )
            return busy / len(ready)

    def warmup_cost_s(self) -> Optional[float]:
        """Measured warm-up cost (p50 of serve_replica_warmup_seconds);
        None until the first warm-up completes."""
        if self._warmup_hist.count == 0:
            return None
        return self._warmup_hist.percentile(0.50)

    # -- tail sampling -------------------------------------------------------

    def _note_pressure(self, ctx, reason: str) -> None:
        """An interesting trace (shed / deadline / retry exhaustion /
        hedge-won) was just detected: pin it into the span ring so it
        survives ring churn, and remember its id as the latest pressure
        signal — the autoscale event joins on it."""
        if ctx is None:
            return
        if self._tail_sampler.keep(ctx.trace_id, reason):
            self._trace_ring.pin(ctx.trace_id)
        self.last_pressure_trace_id = ctx.trace_id

    # -- admission ----------------------------------------------------------

    def _admit(self, req: SynthesisRequest) -> str:
        """Geometry + class validation at submit time (engine-free: only
        the lattice is consulted, so admission works while every replica
        is still warming). Returns the resolved priority class."""
        klass = req.priority or self.fleet.default_class
        if (klass not in self.fleet.class_deadline_ms
                and klass != self._probe_class):
            raise ValueError(
                f"unknown priority class {klass!r}; configured classes: "
                f"{sorted(self.fleet.class_deadline_ms)}"
            )
        if getattr(req, "pending", False):
            # a frontend handle (serving/frontend.py): class + deadline
            # math need nothing beyond the handle; geometry waits for
            # the resolved sequence and is validated at dispatch
            # (_resolve_pending), where errors resolve the future
            return klass
        if req.sequence.ndim != 1:
            raise ValueError(
                f"request {req.id!r}: sequence must be [L], "
                f"got {req.sequence.shape}"
            )
        if req.style is None and req.ref_mel is not None:
            if req.ref_mel.ndim != 2:
                raise ValueError(
                    f"request {req.id!r}: ref_mel must be [T, n_mels], "
                    f"got {req.ref_mel.shape}"
                )
            # reference length rides the style lattice, NOT T_mel — a
            # max-length reference no longer inflates the output bucket
            self.style_lattice.cover(1, req.ref_mel.shape[0])
        need_mel = len(req.sequence) * self._frames_per_phoneme
        self.lattice.cover(1, len(req.sequence), need_mel)
        return klass

    def _budget_s(self, req: SynthesisRequest, klass: str) -> float:
        """Effective SLO budget in seconds: the class deadline, unless
        the request carries a ``deadline_ms`` override (a long-form
        chapter group's budget scales with its chunk count), clamped to
        ``fleet.max_deadline_ms`` so a client cannot park an entry in
        the EDF heap forever."""
        override = getattr(req, "deadline_ms", None)
        if override is None:
            if klass == self._probe_class:
                # probes carry their own budget (serve.quality), never
                # a tenant class's deadline
                return self._probe_deadline_ms / 1e3
            return self.fleet.class_deadline_ms[klass] / 1e3
        if override <= 0:
            raise ValueError(
                f"request {getattr(req, 'id', '?')!r}: deadline_ms "
                f"override must be > 0, got {override}"
            )
        return min(float(override), self.fleet.max_deadline_ms) / 1e3

    def _check_shed(self, count: bool = True) -> None:
        """Watermark hysteresis; caller holds ``self._cond``.
        ``count=False`` (probe-class submits) sheds without bumping
        ``serve_shed_total`` — the autoscaler's pressure signal must
        not see synthetic probe traffic."""
        depth = len(self._heap)
        cap = self.fleet.queue_depth
        if self._shedding:
            if depth <= self.fleet.shed_low_watermark * cap:
                self._shedding = False
        elif depth >= self.fleet.shed_high_watermark * cap:
            self._shedding = True
        if self._shedding:
            if count:
                self._shed_ctr.inc()
            # Retry-After = hysteresis gap / measured drain rate: the
            # seconds until the heap is back under the low watermark
            # (where admission resumes) at the current service rate;
            # shed_retry_after_s is only the fallback before any
            # dispatch has completed
            raise Overloaded(
                f"fleet pending queue at {depth}/{cap} (high watermark "
                f"{self.fleet.shed_high_watermark:g}): shedding load",
                retry_after_s=self.drain_rate.retry_after(
                    max(depth - self.fleet.shed_low_watermark * cap, 1.0),
                    self.fleet.shed_retry_after_s,
                ),
            )

    def submit(self, request: SynthesisRequest) -> Future:
        """Admit one request; returns a Future resolving to its
        SynthesisResult. Raises RequestTooLarge/ValueError on geometry,
        Overloaded past the shed watermark, ShutdownError after close."""
        klass = self._admit(request)
        is_probe = klass == self._probe_class
        fut: Future = Future()
        with self._cond:
            if self._closing:
                self._rejected_ctr.inc()
                raise ShutdownError("router is closed")
            try:
                self._check_shed(count=not is_probe)
            except Overloaded:
                if is_probe:
                    # probe sheds are accounted on their own family:
                    # neither serve_shed_total (autoscaler pressure)
                    # nor serve_class_shed_total (latency SLO bad
                    # stream) may see synthetic traffic
                    self.registry.counter(
                        "serve_probe_shed_total",
                        help="probe-class submits shed by backpressure "
                             "(excluded from pressure + latency SLO)",
                    ).inc()
                    raise
                # the classless serve_shed_total already counted inside
                # _check_shed; this per-class family is what the SLO
                # burn-rate engine differentiates (obs/slo.py)
                self.registry.counter(
                    "serve_class_shed_total", labels={"class": klass},
                    help="submits shed by backpressure, per priority "
                         "class (the SLO engine's bad-event stream)",
                ).inc()
                # a shed trace is always kept (tail-sampling keep rule)
                self._note_pressure(
                    getattr(request, "trace", None), "shed")
                raise
            budget = self._budget_s(request, klass)
            self._seq += 1
            heapq.heappush(self._heap, _Pending(
                slo_deadline=request.arrival + budget,
                seq=self._seq,
                request=request,
                future=fut,
                dispatch_by=request.arrival + self.max_wait,
                klass=klass,
                submit_wall=time.time(),
                submit_mono=time.monotonic(),
            ))
            self._pending_gauge.set(len(self._heap))
            if is_probe:
                self.registry.counter(
                    "serve_probe_requests_total",
                    help="probe-class requests admitted (the quality "
                         "plane's golden replays — not tenant traffic)",
                ).inc()
            else:
                self.registry.counter(
                    "serve_class_requests_total", labels={"class": klass},
                    help="requests admitted per priority class",
                ).inc()
            self._cond.notify_all()
        return fut

    # -- dispatch -----------------------------------------------------------

    @property
    def dispatch_total(self) -> int:
        """Router-global dispatch count so far — the counter the
        ``replica_raise@N``/``replica_hang@N`` fault kinds index
        (the chaos tests read this to arm a kill that has not
        happened yet)."""
        with self._cond:
            return self._dispatch_total

    def _collect(self, rep: Replica) -> Optional[List[_Pending]]:
        """EDF pop + coalesce for one replica. None = worker should exit
        (draining or closed-and-drained).

        Deadline enforcement happens here: a pending popped past its SLO
        deadline is never dispatched — it resolves as DeadlineExceeded
        (504) once the lock is released.  The returned batch is also
        registered as the replica's in-flight claim for the hang
        watchdog before the lock is dropped, and stamped with its
        router-global dispatch number (``rep.dispatch_n`` — the counter
        the fault kinds index) while still under the lock.
        """
        expired: List[_Pending] = []
        batch: Optional[List[_Pending]] = None
        with self._cond:
            while batch is None:
                if not self._heap:
                    if rep.state != READY or self._closing:
                        break
                    self._cond.wait(timeout=0.5)
                    continue
                p = heapq.heappop(self._heap)
                if time.monotonic() > p.slo_deadline:
                    expired.append(p)
                    continue
                batch = [p]
            if batch is not None:
                while len(batch) < self.max_batch:
                    if self._heap:
                        p = heapq.heappop(self._heap)
                        if time.monotonic() > p.slo_deadline:
                            expired.append(p)
                            continue
                        batch.append(p)
                        continue
                    if self._closing or rep.state != READY:
                        break
                    wait = (min(q.dispatch_by for q in batch)
                            - time.monotonic())
                    if wait <= 0:
                        break
                    self._cond.wait(timeout=wait)
                self._dispatch_total += 1
                rep.dispatch_n = self._dispatch_total
                rep.inflight = batch
                rep.dispatch_started = time.monotonic()
            self._pending_gauge.set(len(self._heap))
        for p in expired:
            self._resolve_deadline_exceeded(p)
        return batch

    def _resolve_deadline_exceeded(self, p: _Pending) -> None:
        """Resolve one pending as DeadlineExceeded. Caller must already
        have removed it from the heap / any in-flight batch."""
        if p.future.done():
            # already resolved (a failed frontend resolution that was
            # then stolen/requeued): the verdict is out, nothing to add
            return
        ctx = getattr(p.request, "trace", None)
        if p.klass == self._probe_class:
            # probe expiry: own counter, no class label, no pressure
            # pin — the latency SLO and autoscaler never see probes
            self.registry.counter(
                "serve_probe_deadline_exceeded_total",
                help="probe-class requests resolved 504 before dispatch "
                     "(excluded from the latency SLO bad stream)",
            ).inc()
        else:
            self.registry.counter(
                "serve_deadline_exceeded_total", labels={"class": p.klass},
                help="requests resolved 504 instead of dispatched past "
                     "their class deadline budget",
            ).inc()
            self._note_pressure(ctx, "deadline_exceeded")
        if self.events is not None:
            self.events.emit(
                "deadline_exceeded", req_id=p.request.id, klass=p.klass,
                retries=p.retries,
                trace_id=ctx.trace_id if ctx is not None else None,
            )
        budget = self._budget_s(p.request, p.klass) * 1e3
        # an expiry removes the entry from the heap for good — it drains
        # the queue exactly as a dispatch does for Retry-After purposes
        self.drain_rate.note(1)
        p.future.set_exception(DeadlineExceeded(
            f"request {p.request.id!r} exceeded its {p.klass!r} deadline "
            f"budget ({budget:g} ms) before dispatch",
            klass=p.klass, budget_ms=budget,
        ))

    def _claim(self, rep: Replica, batch: List[_Pending]) -> bool:
        """Take the in-flight batch back from the watchdog.  False means
        the supervisor stole it (hang): the caller owns nothing and must
        discard whatever the engine eventually returned."""
        with self._cond:
            if rep.inflight is not batch:
                return False
            rep.inflight = None
            rep.dispatch_started = None
            return True

    def _resolve_pending(self, p: _Pending) -> bool:
        """Swap a frontend handle for its resolved SynthesisRequest in
        place. False = the frontend raised (or wedged past the resolve
        bound); the future already carries the error and the entry must
        leave the batch."""
        if not getattr(p.request, "pending", False):
            return True
        try:
            request = p.request.resolve()
            self._admit(request)   # geometry deferred from submit
        except BaseException as e:
            # the done-guard matters after a watchdog steal: a stolen
            # entry whose resolution failed may come back through a
            # requeue with its future already resolved
            if not p.future.done():
                p.future.set_exception(e)
            return False
        p.request = request
        return True

    def _dispatch(self, rep: Replica, gen: int,
                  batch: List[_Pending]) -> bool:
        """Run one coalesced batch on the replica. Returns False when the
        replica failed (or its results were stolen by the hang watchdog)
        and the worker loop must exit — supervision owns the replica's
        state from that point."""
        # resolve frontend handles before the device sees the batch.
        # ``batch`` is also the replica's in-flight claim object (the
        # watchdog handshake compares identity), so failed entries are
        # removed IN PLACE and only under the router lock — the
        # supervisor iterates this same list when it steals a hang
        drop = [p for p in batch if not self._resolve_pending(p)]
        if drop:
            with self._cond:
                if rep.inflight is not batch:
                    return False  # stolen mid-resolve; supervisor owns it
                for p in drop:
                    batch.remove(p)
        if not batch:
            self._claim(rep, batch)   # nothing left to run: release it
            return True
        req_ids = [p.request.id for p in batch]
        # jaxlint: disable=JL020 reason=stamped under _cond in _collect by this same single dispatch worker
        n = rep.dispatch_n
        t0 = time.monotonic()
        t0_wall = time.time()
        for p in batch:
            self._queue_wait_hist.observe(t0 - p.request.arrival)
            # the EDF wait is only known here, on the dispatch thread —
            # record it after the fact under the request's context
            ctx = getattr(p.request, "trace", None)
            if ctx is not None and p.submit_wall:
                Span.record(
                    "serve_queue", p.submit_wall,
                    max(0.0, t0 - p.submit_mono), parent=ctx,
                    klass=p.klass, retries=p.retries,
                )
        try:
            if self.fault_plan is not None:
                if self.fault_plan.fire("replica_raise", n):
                    raise InjectedFault(
                        f"injected replica_raise at dispatch {n}"
                    )
                if self.fault_plan.fire("replica_hang", n):
                    # stall past the watchdog, then fall through to a
                    # real dispatch: exercises the stolen-results path
                    time.sleep(
                        3.0 * self._watchdog if self._watchdog > 0 else 0.5
                    )
                if self.fault_plan.fire("replica_proc_kill", n):
                    if not self._chaos_proc_kill(rep):
                        raise InjectedFault(
                            f"injected replica_proc_kill at dispatch {n}"
                        )
                if self.fault_plan.fire("net_partition", n):
                    if not self._chaos_partition(rep):
                        raise InjectedFault(
                            f"injected net_partition at dispatch {n}"
                        )
                if self.fault_plan.fire("tier_poison", n):
                    # the quality-plane degradation drill: corrupt this
                    # replica's param tree in place (same shapes, zero
                    # compiles) and CONTINUE — the dispatch succeeds,
                    # the audio is garbage, and only the validators +
                    # golden probes can page it
                    # jaxlint: disable=JL020 reason=engine set under _cond before this generation's worker starts and never reassigned within a generation
                    poison = getattr(rep.engine, "poison_params", None)
                    if poison is not None:
                        poison()
            # jaxlint: disable=JL020 reason=engine set under _cond before this generation's worker starts and never reassigned within a generation
            results = rep.engine.run([p.request for p in batch])
        except BaseException as e:
            if not self._claim(rep, batch):
                return False   # watchdog already failed us and requeued
            if self.events is not None:
                self.events.emit(
                    "fleet_dispatch", replica=rep.index, req_ids=req_ids,
                    rows=len(batch), duration_s=time.monotonic() - t0,
                    ok=False, error=type(e).__name__,
                )
            self._replica_failed(rep, batch, e, kind="raise")
            return False
        if not self._claim(rep, batch):
            # hung past the watchdog, then finished anyway: the requests
            # were requeued elsewhere — these results are orphans
            if self.events is not None:
                self.events.emit(
                    "dispatch_discarded", replica=rep.index,
                    req_ids=req_ids, duration_s=time.monotonic() - t0,
                )
            return False
        now = time.monotonic()
        # the batch left the queue for good (every future resolves below,
        # result or DispatchError): it is drain the Retry-After sees
        self.drain_rate.note(len(batch), now=now)
        try:
            self.registry.counter(
                "serve_batch_occupancy_total",
                labels={"rows": str(len(batch))},
                help="dispatches by real-row occupancy",
            ).inc()
            self.registry.counter(
                "serve_replica_dispatches_total",
                labels={"replica": str(rep.index)},
                help="coalesced dispatches executed per replica",
            ).inc()
            self.registry.counter(
                "serve_replica_requests_total",
                labels={"replica": str(rep.index)},
                help="requests served per replica",
            ).inc(len(batch))
            # engines are duck-typed in tests (the batcher's convention)
            bucket = getattr(results[0], "bucket", None) if results else None
            if self.events is not None:
                self.events.emit(
                    "fleet_dispatch", replica=rep.index, req_ids=req_ids,
                    rows=len(batch),
                    bucket=(bucket_label(bucket) if bucket is not None
                            else None),
                    duration_s=now - t0,
                )
            if rep.breaker.state != "closed":
                # first good dispatch after a re-warm trial: close it
                rep.breaker.record_success()
                with self._cond:
                    self._set_breaker_gauge(rep)
            for p, r in zip(batch, results):
                r.replica = rep.index
                if self.tier is not None:
                    r.tier = self.tier
                self._latency_hist.observe(now - p.request.arrival)
                ctx = getattr(p.request, "trace", None)
                if now > p.slo_deadline:
                    if p.klass == self._probe_class:
                        # probe misses stay off the latency SLO bad
                        # stream and off the pressure/pin path
                        self.registry.counter(
                            "serve_probe_deadline_miss_total",
                            help="probe-class requests completed past "
                                 "their probe deadline (excluded from "
                                 "the latency SLO bad stream)",
                        ).inc()
                    else:
                        self.registry.counter(
                            "serve_deadline_miss_total",
                            labels={"class": p.klass},
                            help="requests completed past their SLO "
                                 "deadline",
                        ).inc()
                        self._note_pressure(ctx, "deadline_miss")
                elif ctx is not None and \
                        self._tail_sampler.keep(ctx.trace_id):
                    # healthy traffic: deterministic sample-rate dice
                    self._trace_ring.pin(ctx.trace_id)
                if ctx is not None:
                    Span.record(
                        "fleet_dispatch", t0_wall,
                        max(0.0, now - t0), parent=ctx,
                        replica=rep.index, rows=len(batch),
                    )
                p.future.set_result(r)
        except BaseException as e:
            # bookkeeping bug AFTER a successful engine call: resolve the
            # affected futures with a structured error and keep the loop
            # alive — a raise here used to kill the dispatch thread and
            # strand the queue
            self.registry.counter(
                "serve_dispatch_errors_total",
                help="dispatch-loop bookkeeping errors resolved as "
                     "DispatchError (500) without killing the worker",
            ).inc()
            if self.events is not None:
                self.events.emit(
                    "dispatch_error", replica=rep.index, req_ids=req_ids,
                    error=type(e).__name__,
                )
            err = DispatchError(
                f"dispatch bookkeeping failed on replica {rep.index}: "
                f"{type(e).__name__}: {e}"
            )
            for p in batch:
                if not p.future.done():
                    p.future.set_exception(err)
        return True

    def _chaos_proc_kill(self, rep: Replica) -> bool:
        """Hook for the ``replica_proc_kill`` drill.  The base router's
        replicas are in-process (there is no process to kill), so this
        returns False and the dispatch raises InjectedFault instead —
        the same failure path, one level down.  ClusterRouter overrides
        it to SIGKILL the replica's real process and returns True: the
        wire call that follows then fails organically."""
        return False

    def _chaos_partition(self, rep: Replica) -> bool:
        """Hook for the ``net_partition`` drill.  Base router: False
        (no wire to cut) -> InjectedFault.  ClusterRouter overrides it
        to drop all router<->replica packets for this replica until the
        drill heals the link; the dispatch and every heartbeat then fail
        organically."""
        return False

    def _replica_failed(self, rep: Replica, batch: List[_Pending],
                        error: BaseException, kind: str) -> None:
        """Fail one replica and requeue its in-flight batch onto healthy
        replicas. Called by the worker (dispatch raised) or by the
        supervisor (hang watchdog); the caller must already own ``batch``
        exclusively (claimed or stolen)."""
        now = time.monotonic()
        expired: List[_Pending] = []
        exhausted: List[_Pending] = []
        shutdown: List[_Pending] = []
        requeued: List[_Pending] = []
        with self._cond:
            rep.error = error
            if rep.state in (READY, DRAINING):
                # a DRAINING replica was being shrunk away: do not
                # resurrect it — requeue its batch but stop it for good
                target = FAILED if rep.state == READY else STOPPED
                backoff = rep.breaker.record_failure(now)
                self._set_breaker_gauge(rep)
                self._set_state(rep, target)
            else:
                backoff = rep.breaker.retry_at() - now
            self.registry.counter(
                "serve_replica_failures_total",
                labels={"replica": str(rep.index)},
                help="dispatch failures (raise or hang) per replica",
            ).inc()
            for p in batch:
                budget = self.fleet.retry_budget.get(p.klass, 0)
                if p.future.done():
                    continue  # already resolved (failed frontend handle)
                if self._closing:
                    shutdown.append(p)
                elif now > p.slo_deadline:
                    expired.append(p)
                elif p.retries >= budget:
                    exhausted.append(p)
                else:
                    p.retries += 1
                    requeued.append(p)
            for p in requeued:
                heapq.heappush(self._heap, p)
                self._requeued_ctr.inc()
                self.registry.counter(
                    "serve_retries_total", labels={"class": p.klass},
                    help="replica-failure retries consumed per class",
                ).inc()
            self._pending_gauge.set(len(self._heap))
            self._cond.notify_all()
        if self.events is not None:
            self.events.emit(
                "replica_failure", replica=rep.index, kind=kind,
                error=type(error).__name__, req_ids=[
                    p.request.id for p in batch
                ],
                requeued=[p.request.id for p in requeued],
                failed=[p.request.id for p in exhausted],
                expired=[p.request.id for p in expired],
                backoff_s=round(max(0.0, backoff), 6),
                trace_id=next(
                    (p.request.trace.trace_id for p in batch
                     if getattr(p.request, "trace", None) is not None),
                    None,
                ),
            )
        # every requeued request gets a point-in-time span event so the
        # assembled trace shows the failure → retry hop explicitly
        now_wall = time.time()
        for p in requeued:
            ctx = getattr(p.request, "trace", None)
            if ctx is not None:
                Span.record(
                    "fleet_requeue", now_wall, 0.0, parent=ctx,
                    events=[{"name": "requeue", "ts": now_wall,
                             "replica": rep.index, "kind": kind,
                             "retry": p.retries}],
                )
        for p in expired:
            self._resolve_deadline_exceeded(p)
        for p in shutdown:
            p.future.set_exception(ShutdownError("router closed"))
        for p in exhausted:
            self._note_pressure(getattr(p.request, "trace", None), "error")
            p.future.set_exception(ReplicaError(
                f"request {p.request.id!r} ({p.klass!r}) exhausted its "
                f"retry budget after replica {rep.index} failed: "
                f"{type(error).__name__}: {error}"
            ))

    def _supervise(self) -> None:
        """Hang watchdog + breaker re-warm scheduler (one daemon thread
        per router)."""
        while True:
            hung = []
            rewarm = []
            expired = []
            with self._cond:
                if self._closing:
                    return
                self._cond.wait(timeout=self._supervise_interval)
                if self._closing:
                    return
                now = time.monotonic()
                # the heap is EDF-ordered, so expired work is at the
                # front: sweep it here too, so deadlines resolve even
                # when no worker is popping (e.g. every replica failed)
                while self._heap and now > self._heap[0].slo_deadline:
                    expired.append(heapq.heappop(self._heap))
                if expired:
                    self._pending_gauge.set(len(self._heap))
                for rep in self._replicas:
                    if (self._watchdog > 0 and rep.state == READY
                            and rep.inflight is not None
                            and rep.dispatch_started is not None
                            and now - rep.dispatch_started > self._watchdog):
                        # steal the batch: the hung worker will find its
                        # claim gone and discard whatever it returns
                        batch = rep.inflight
                        rep.inflight = None
                        rep.dispatch_started = None
                        hung.append((rep, batch))
                    elif (rep.state == FAILED
                          and rep.breaker.ready_to_trial(now)):
                        rep.breaker.begin_trial()
                        self._set_breaker_gauge(rep)
                        self._set_state(rep, COLD)
                        rewarm.append(rep)
            for p in expired:
                self._resolve_deadline_exceeded(p)
            for rep, batch in hung:
                self._replica_failed(rep, batch, TimeoutError(
                    f"replica {rep.index} dispatch exceeded the "
                    f"{self._watchdog:g}s hang watchdog"
                ), kind="hang")
            for rep in rewarm:
                threading.Thread(
                    target=self._warm, args=(rep,),
                    name=f"replica-{rep.index}-rewarm", daemon=True,
                ).start()

    def _worker(self, rep: Replica, gen: int) -> None:
        try:
            while True:
                batch = self._collect(rep)
                if batch is None:
                    break
                if not self._dispatch(rep, gen, batch):
                    return  # replica failed/orphaned; supervision owns it
        except BaseException as e:  # engine + bookkeeping errors are
            # handled inside _dispatch; anything here is a harness bug —
            # fail waiters loudly
            self._fail_pending(e)
            raise
        finally:
            with self._cond:
                # do not stomp FAILED (supervision owns it) or a newer
                # generation's state after a re-warm
                if rep.generation == gen and rep.state in (READY, DRAINING):
                    self._set_state(rep, STOPPED)

    def _fail_pending(self, error: BaseException) -> None:
        with self._cond:
            pending, self._heap = self._heap, []
            self._pending_gauge.set(0)
        for p in pending:
            if not p.future.done():
                p.future.set_exception(
                    ShutdownError(f"fleet router closed: {error!r}")
                )

    # -- streaming ----------------------------------------------------------

    def stream(
        self, result: SynthesisResult, arrival: Optional[float] = None
    ) -> Iterator[np.ndarray]:
        """Yield int16 wav chunks for a dispatched result, vocoded window
        by window on the replica that produced it (precompiled buckets —
        zero compiles). Observes ``serve_ttfa_seconds`` at the first
        chunk when ``arrival`` (a monotonic stamp) is given."""
        with self._cond:
            reps = {r.index: r for r in self._replicas}
            rep = reps.get(result.replica)
            if rep is None or rep.engine is None:
                raise ValueError(
                    f"result {result.id!r} carries no live replica "
                    f"(replica={result.replica})"
                )
            if rep.state not in (READY, DRAINING):
                # stream continuations are non-idempotent: they are never
                # transparently retried on another replica (a re-warmed
                # replica going READY again serves them fine — vocode
                # windows are stateless)
                raise ReplicaError(
                    f"stream for result {result.id!r} lost replica "
                    f"{result.replica} (state={rep.state!r}); stream "
                    "continuations are not retried"
                )
            engine = rep.engine
        if self._stream_overlap is None:
            gen, _ = engine.vocoder
            self._stream_overlap = streaming.resolve_overlap(
                self.fleet.stream_overlap, gen
            )
        first = True
        for chunk in streaming.stream_wav(
            engine, result, self.fleet.stream_window, self._stream_overlap,
            depth=self.fleet.stream_depth,
        ):
            if first and arrival is not None:
                self._ttfa_hist.observe(time.monotonic() - arrival)
            first = False
            yield chunk

    # -- shutdown -----------------------------------------------------------

    def close(self, flush: bool = True, timeout: float = 30.0) -> None:
        """Idempotent shutdown. ``flush=True`` lets ready workers drain
        the pending heap; ``flush=False`` fails pending requests with
        ShutdownError. In-flight dispatches always complete."""
        with self._cond:
            self._closing = True
            # replicas still cold/warming will never be needed — and a
            # failed replica must not be re-warmed into a closed router:
            # stop them all now (also wakes the supervisor, which exits
            # on _closing)
            for rep in self._replicas:
                if rep.state in (COLD, WARMING, FAILED):
                    self._set_state(rep, STOPPED)
            workers = [r.worker for r in self._replicas if r.worker]
            self._cond.notify_all()
        if not flush:
            self._fail_pending(ShutdownError("router closed"))
        deadline = time.monotonic() + timeout
        for w in workers:
            w.join(timeout=max(0.0, deadline - time.monotonic()))
        # anything still pending after the drain (no replica ever came
        # ready, or the join timed out) must not strand its waiters
        self._fail_pending(ShutdownError("router closed"))

    def __enter__(self) -> "FleetRouter":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False
