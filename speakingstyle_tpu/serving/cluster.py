"""Distributed control plane: replica *processes* behind the fleet router.

Everything the in-process fleet earned — EDF admission, breakers, the
hang watchdog's exactly-once claim handshake, requeue-at-original-
deadline, canary rollout, the measured-warmup autoscaler — survives the
hop to separate processes because the router's replica surface is just
``precompile()`` + ``run(requests)``.  This module supplies that surface
over HTTP (ARCHITECTURE.md "Distributed control plane"):

  ``ClusterRouter``   a ``FleetRouter`` whose replicas are processes.
        It runs a small control server (``POST /register`` +
        ``POST /heartbeat``), grants heartbeat **leases** (a replica may
        miss ``cluster.lease_miss_budget`` consecutive beats before its
        lease expires), and sweeps expired leases into the *existing*
        ``_replica_failed`` machinery: breaker opens, in-flight work is
        stolen under the router lock (the same identity handshake the
        hang watchdog uses) and requeued at its original SLO deadline.
        ``scale_to()`` spawns/drains real processes through the caller's
        ``spawn`` callable, and the warm-up wall time (process spawn +
        the child's engine AOT precompile + registration) lands in the
        same ``serve_replica_warmup_seconds`` histogram the autoscaler's
        cost model reads — measured, not assumed.

  ``RemoteEngine``    the router-side replica proxy (the
        "RemoteReplica" interface rollout/canary and the autoscaler
        drive).  ``precompile()`` adopts a still-live orphan process
        (how a healed partition re-admits a warm replica through the
        breaker's half-open trial without recompiling anything) or
        spawns a fresh one and waits for its lease.  ``run()`` is a
        **hedged** wire dispatch: a second request goes to a different
        host once the first has been outstanding past the class's
        observed wire-latency hedge quantile, both requests carry the
        same idempotency key, the first response wins and the loser's
        connection is torn down (``serve_hedge_fired_total`` /
        ``serve_hedge_won_total``).  Every wire call carries an explicit
        timeout derived from the request class's deadline budget —
        jaxlint JL024 makes that structural for the whole serving tree.

  ``ReplicaServer``   the replica-process side: ``/dispatch`` (with a
        bounded idempotency cache so a hedge or wire retry of an
        already-executed batch returns the cached response instead of
        re-running the lattice), ``/healthz``, ``/drain``, and the
        heartbeat loop.  ``cli/replica.py`` wraps it around a full
        ``SynthesisEngine``; tests wrap duck engines.

Exactly-once, across the wire: the router's claim handshake is still
the client-facing guarantee (a stolen batch's late results are
discarded; a requeued request resolves exactly once).  Idempotency keys
add the wire-level half: the *same* dispatch sent twice (hedge, retry)
executes at most once per host, so hedging never doubles device work
for the winner's host pair beyond the one extra dispatch it deliberately
paid for.

Partition semantics (the ``net_partition`` chaos drill): a partitioned
replica's packets drop in both directions — the control server refuses
its heartbeats (lease keeps aging) and ``RemoteEngine.run`` fails fast
instead of connecting.  The replica process itself stays up; once the
drill heals the link, its heartbeat gets a lease-expired answer,
re-registers with a bumped epoch (a *stale* epoch is rejected — the
zombie-writer fence), and the next breaker trial adopts the warm
process back through half-open.
"""

import base64
import hashlib
import json
import queue
import subprocess
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass
from http.client import HTTPConnection
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from speakingstyle_tpu.faults import FaultPlan
from speakingstyle_tpu.obs import JsonlEventLog, MetricsRegistry
from speakingstyle_tpu.obs import trace as obstrace
from speakingstyle_tpu.obs.locks import make_lock
from speakingstyle_tpu.obs.registry import merge_states
from speakingstyle_tpu.obs.trace import Span, TraceContext, get_span_ring
from speakingstyle_tpu.serving.engine import (
    SynthesisRequest,
    SynthesisResult,
)
from speakingstyle_tpu.serving.fleet import (
    FleetRouter,
    READY,
    STOPPED,
    Replica,
)
from speakingstyle_tpu.serving.lattice import Bucket
from speakingstyle_tpu.serving.resilience import LeaseExpired, WireError
from speakingstyle_tpu.serving.style import StyleVectors

__all__ = [
    "ClusterRouter",
    "RemoteEngine",
    "ReplicaServer",
    "Lease",
    "LeaseTable",
    "encode_request",
    "decode_request",
    "encode_result",
    "decode_result",
    "batch_key",
]


# ---------------------------------------------------------------------------
# wire codec: JSON + base64 ndarrays
# ---------------------------------------------------------------------------


def _enc_arr(a: Optional[np.ndarray]) -> Optional[Dict]:
    if a is None:
        return None
    a = np.ascontiguousarray(a)
    return {
        "dtype": str(a.dtype),
        "shape": list(a.shape),
        "b64": base64.b64encode(a.tobytes()).decode("ascii"),
    }


def _dec_arr(d: Optional[Dict]) -> Optional[np.ndarray]:
    if d is None:
        return None
    raw = base64.b64decode(d["b64"])
    # frombuffer views read-only memory; copy so downstream slice-assign
    # (pool staging writes) keeps working
    return np.frombuffer(raw, dtype=d["dtype"]).reshape(d["shape"]).copy()


def _enc_ctl(c) -> Dict:
    if np.isscalar(c):
        return {"scalar": float(c)}
    return {"array": _enc_arr(np.asarray(c, np.float32))}


def _dec_ctl(d: Dict):
    if "scalar" in d:
        return float(d["scalar"])
    return _dec_arr(d["array"])


def encode_request(r: SynthesisRequest) -> Dict:
    """One admitted request -> its JSON-ready wire form.  ``arrival`` is
    deliberately NOT shipped: monotonic stamps do not transfer between
    processes — router-side latency math keeps the router's stamp, and
    the replica stamps its own on decode."""
    style = None
    if r.style is not None:
        style = {
            "key": r.style.key,
            "gamma": _enc_arr(r.style.gamma),
            "beta": _enc_arr(r.style.beta),
        }
    return {
        "id": r.id,
        "sequence": _enc_arr(np.asarray(r.sequence)),
        "ref_mel": _enc_arr(r.ref_mel),
        "style": style,
        "speaker": int(r.speaker),
        "raw_text": r.raw_text,
        "p_control": _enc_ctl(r.p_control),
        "e_control": _enc_ctl(r.e_control),
        "d_control": _enc_ctl(r.d_control),
        "stream": bool(r.stream),
        "style_degraded": bool(r.style_degraded),
        # the propagated trace context: three strings, riding the body
        # (per request — one coalesced dispatch can carry many traces)
        "trace": r.trace.as_dict() if r.trace is not None else None,
    }


def decode_request(d: Dict) -> SynthesisRequest:
    style = None
    if d.get("style") is not None:
        s = d["style"]
        style = StyleVectors(
            key=s["key"], gamma=_dec_arr(s["gamma"]), beta=_dec_arr(s["beta"])
        )
    return SynthesisRequest(
        id=d["id"],
        sequence=_dec_arr(d["sequence"]),
        ref_mel=_dec_arr(d.get("ref_mel")),
        style=style,
        speaker=d.get("speaker", 0),
        raw_text=d.get("raw_text", ""),
        p_control=_dec_ctl(d["p_control"]),
        e_control=_dec_ctl(d["e_control"]),
        d_control=_dec_ctl(d["d_control"]),
        stream=d.get("stream", False),
        style_degraded=d.get("style_degraded", False),
        trace=TraceContext.from_dict(d.get("trace")),
    )


def encode_result(r) -> Dict:
    """Duck-typed on purpose: test engines return plain objects
    with a subset of the SynthesisResult fields."""
    bucket = getattr(r, "bucket", None)
    return {
        "id": r.id,
        "raw_text": getattr(r, "raw_text", ""),
        "mel": _enc_arr(getattr(r, "mel", None)),
        "mel_len": int(getattr(r, "mel_len", 0)),
        "wav": _enc_arr(getattr(r, "wav", None)),
        "durations": _enc_arr(getattr(r, "durations", None)),
        "pitch_prediction": _enc_arr(getattr(r, "pitch_prediction", None)),
        "energy_prediction": _enc_arr(getattr(r, "energy_prediction", None)),
        "src_len": int(getattr(r, "src_len", 0)),
        "bucket": ([bucket.b, bucket.l_src, bucket.t_mel]
                   if bucket is not None else None),
        "batch_rows": int(getattr(r, "batch_rows", 1)),
        "style_degraded": bool(getattr(r, "style_degraded", False)),
    }


_EMPTY = np.zeros((0,), np.float32)


def decode_result(d: Dict, served_by: Optional[str] = None) -> SynthesisResult:
    def arr(key):
        a = _dec_arr(d.get(key))
        return a if a is not None else _EMPTY

    b = d.get("bucket")
    return SynthesisResult(
        id=d["id"],
        raw_text=d.get("raw_text", ""),
        mel=arr("mel"),
        mel_len=d.get("mel_len", 0),
        wav=_dec_arr(d.get("wav")),
        durations=arr("durations"),
        pitch_prediction=arr("pitch_prediction"),
        energy_prediction=arr("energy_prediction"),
        src_len=d.get("src_len", 0),
        bucket=Bucket(*b) if b else None,
        batch_rows=d.get("batch_rows", 1),
        style_degraded=d.get("style_degraded", False),
        served_by=served_by,
    )


def batch_key(requests: List[SynthesisRequest]) -> str:
    """The idempotency key for one coalesced wire dispatch: a stable
    hash of the request ids it carries.  Both hedge legs (and any wire
    retry of the same dispatch) send the same key, so the replica-side
    cache makes a duplicate arrival a lookup, not a lattice run.  A
    *requeued* batch regrouped by the router hashes differently — and
    must: different membership is genuinely different work."""
    h = hashlib.sha256()
    for r in requests:
        h.update(r.id.encode("utf-8", "replace"))
        h.update(b"\x00")
    return h.hexdigest()[:32]


# ---------------------------------------------------------------------------
# leases
# ---------------------------------------------------------------------------


@dataclass
class Lease:
    """One replica's liveness lease (all stamps ``time.monotonic``)."""

    replica_id: str
    host: str
    port: int
    epoch: int
    pid: int
    deadline: float          # expired strictly AFTER this instant
    last_beat: float
    ready: bool
    registered_at: float


class LeaseTable:
    """Epoch-fenced heartbeat leases, keyed by replica id.

    Epochs are the zombie-writer fence: a replica re-registers with a
    bumped epoch after every lease loss, and a registration or beat
    carrying an epoch *older* than the table's is rejected — a partition
    survivor that never noticed its lease lapse cannot overwrite the
    newer incarnation's lease.  Expiry is strict: a beat landing exactly
    at the deadline still renews (``now <= deadline``), one tick later
    does not.
    """

    def __init__(self, ttl_s: float):
        self.ttl_s = float(ttl_s)
        self._lock = make_lock("LeaseTable._lock")
        self._leases: Dict[str, Lease] = {}

    def register(self, replica_id: str, host: str, port: int, epoch: int,
                 pid: int, now: float) -> Tuple[bool, int]:
        """Grant (or re-grant) a lease.  Returns ``(accepted, epoch)``
        where a rejection's epoch is the table's current one — the
        caller re-registers above it."""
        with self._lock:
            cur = self._leases.get(replica_id)
            if cur is not None and epoch < cur.epoch:
                return False, cur.epoch
            self._leases[replica_id] = Lease(
                replica_id=replica_id, host=host, port=port, epoch=epoch,
                pid=pid, deadline=now + self.ttl_s, last_beat=now,
                ready=False, registered_at=now,
            )
            return True, epoch

    def heartbeat(self, replica_id: str, epoch: int, ready: bool,
                  now: float) -> str:
        """Renew one lease.  Returns ``renewed``, ``unknown`` (never
        registered / dropped), ``stale`` (older epoch than the table's),
        or ``expired`` (the beat landed after the deadline — the caller
        must re-register with a bumped epoch)."""
        with self._lock:
            lease = self._leases.get(replica_id)
            if lease is None:
                return "unknown"
            if epoch < lease.epoch:
                return "stale"
            if now > lease.deadline:
                return "expired"
            lease.epoch = epoch
            lease.deadline = now + self.ttl_s
            lease.last_beat = now
            lease.ready = bool(ready)
            return "renewed"

    def get(self, replica_id: str) -> Optional[Lease]:
        with self._lock:
            lease = self._leases.get(replica_id)
            if lease is None:
                return None
            return Lease(**vars(lease))   # snapshot, not the live object

    def alive(self, replica_id: str, now: float) -> bool:
        with self._lock:
            lease = self._leases.get(replica_id)
            return lease is not None and now <= lease.deadline

    def drop(self, replica_id: str) -> None:
        with self._lock:
            self._leases.pop(replica_id, None)

    def snapshot(self, now: float) -> List[Dict]:
        """JSON-ready lease rows for the /healthz cluster block."""
        with self._lock:
            rows = []
            for lease in sorted(self._leases.values(),
                                key=lambda l: l.replica_id):
                rows.append({
                    "replica_id": lease.replica_id,
                    "host": f"{lease.host}:{lease.port}",
                    "pid": lease.pid,
                    "epoch": lease.epoch,
                    "ready": lease.ready,
                    "lease_age_s": round(now - lease.registered_at, 3),
                    "last_heartbeat_s": round(now - lease.last_beat, 3),
                    "expired": now > lease.deadline,
                })
            return rows


# ---------------------------------------------------------------------------
# HTTP plumbing shared by both sides
# ---------------------------------------------------------------------------


def _post_json(host: str, port: int, path: str, payload: Dict,
               timeout: float,
               headers: Optional[Dict[str, str]] = None) -> Tuple[int, Dict]:
    """One bounded JSON round-trip (every wire call in this module has
    an explicit timeout — jaxlint JL024)."""
    conn = HTTPConnection(host, port, timeout=timeout)
    try:
        body = json.dumps(payload).encode("utf-8")
        hdrs = {"Content-Type": "application/json"}
        if headers:
            hdrs.update(headers)
        conn.request("POST", path, body=body, headers=hdrs)
        resp = conn.getresponse()
        data = resp.read()
        try:
            parsed = json.loads(data) if data else {}
        except ValueError:
            parsed = {}
        return resp.status, parsed
    finally:
        conn.close()


def _get_json(host: str, port: int, path: str,
              timeout: float) -> Tuple[int, Dict]:
    conn = HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        data = resp.read()
        try:
            parsed = json.loads(data) if data else {}
        except ValueError:
            parsed = {}
        return resp.status, parsed
    finally:
        conn.close()


class _JsonHandler(BaseHTTPRequestHandler):
    """Shared request plumbing: subclasses map (method, path) -> a
    callable ``(body, headers) -> (status, payload_dict)`` — headers
    carry the ``X-Trace-*`` propagation fields."""

    protocol_version = "HTTP/1.1"
    # a wedged peer must not pin a handler thread forever
    timeout = 30.0

    def log_message(self, fmt, *args):   # quiet; events go to JSONL
        pass

    def _read_body(self) -> Dict:
        length = int(self.headers.get("Content-Length", 0) or 0)
        raw = self.rfile.read(length) if length else b""
        try:
            return json.loads(raw) if raw else {}
        except ValueError:
            return {}

    def _reply(self, status: int, payload: Dict) -> None:
        body = json.dumps(payload).encode("utf-8")
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _route(self, method: str) -> None:
        handler = self.server.routes.get((method, self.path.split("?")[0]))
        if handler is None:
            self._reply(404, {"error": f"no route {method} {self.path}"})
            return
        try:
            body = self._read_body() if method == "POST" else {}
            status, payload = handler(body, self.headers)
        except BrokenPipeError:
            raise
        except Exception as e:  # a handler bug answers 500, not a hang
            status, payload = 500, {"error": f"{type(e).__name__}: {e}"}
        self._reply(status, payload)

    def do_GET(self):
        self._route("GET")

    def do_POST(self):
        self._route("POST")


class _JsonServer(ThreadingHTTPServer):
    daemon_threads = True
    allow_reuse_address = True

    def __init__(self, addr, routes: Dict):
        self.routes = routes
        super().__init__(addr, _JsonHandler)


# ---------------------------------------------------------------------------
# replica-process side
# ---------------------------------------------------------------------------


class ReplicaServer:
    """The serving half that lives inside one replica process.

    Owns the dispatch endpoint (serialized — the in-process router also
    runs one dispatch at a time per replica, and the engine lock's
    warming-state guard means a compile-on-miss never blocks this
    server's other endpoints), the bounded idempotency cache, and the
    heartbeat loop against the router's control server.  The engine is
    duck-typed exactly like the router's: ``precompile()`` +
    ``run(requests)`` (``cli/replica.py`` passes a full
    ``SynthesisEngine``; tests pass toys).
    """

    def __init__(
        self,
        engine,
        replica_id: str,
        router: str,                      # control server "host:port"
        cluster_cfg,                      # configs.ClusterConfig
        registry: Optional[MetricsRegistry] = None,
        events: Optional[JsonlEventLog] = None,
        host: str = "127.0.0.1",
        port: int = 0,
        pid: int = 0,
    ):
        self.engine = engine
        self.replica_id = replica_id
        rhost, _, rport = router.rpartition(":")
        self.router_host = rhost
        self.router_port = int(rport)
        self.ccfg = cluster_cfg
        self.registry = registry if registry is not None else MetricsRegistry()
        self.events = events
        self.pid = pid
        self._epoch = 1
        self._draining = False
        self._stop = threading.Event()
        self._dispatch_lock = make_lock("ReplicaServer._dispatch_lock")
        # bounded idempotency cache: key -> encoded response payload.
        # LRU eviction (move-to-end on hit, evict-oldest on insert) so a
        # hedge losing by seconds still hits; serve_idempotent_hits_total
        # counts the duplicate arrivals the cache absorbed.  Keys whose
        # batch is EXECUTING RIGHT NOW live in _inflight instead: the
        # duplicate leg of a hedge parks on the first leg's event and
        # then reads the cache, so the lock never spans engine.run
        # (which takes the engine's own locks — nesting them under the
        # handler lock would invert the committed lock order)
        self._idem: "OrderedDict[str, Dict]" = OrderedDict()
        self._inflight: Dict[str, threading.Event] = {}
        self._idem_cap = int(cluster_cfg.idempotency_cache)
        self._idem_hits = self.registry.counter(
            "serve_idempotent_hits_total",
            help="duplicate wire dispatches (hedges/retries) answered "
                 "from the idempotency cache without re-running the "
                 "lattice",
        )
        self._idem_evict = self.registry.counter(
            "serve_idempotent_evictions_total",
            help="idempotency-cache LRU evictions (bounded cache)",
        )
        self._dispatch_ctr = self.registry.counter(
            "serve_wire_dispatches_total",
            help="wire dispatches executed by this replica process",
        )
        # single-flight latch for the fan-out profile endpoint
        self._profiling = threading.Event()
        self._httpd = _JsonServer((host, port), {
            ("GET", "/healthz"): self._handle_healthz,
            ("POST", "/dispatch"): self._handle_dispatch,
            ("POST", "/drain"): self._handle_drain,
            ("GET", "/metrics"): self._handle_metrics,
            ("GET", "/debug/spans"): self._handle_spans,
            ("POST", "/debug/profile"): self._handle_profile,
        })
        self.host = host
        self.port = self._httpd.server_address[1]
        self._http_thread = threading.Thread(
            target=self._httpd.serve_forever,
            name=f"replica-{replica_id}-http", daemon=True,
        )
        self._beat_thread = threading.Thread(
            target=self._beat_loop,
            name=f"replica-{replica_id}-heartbeat", daemon=True,
        )

    # -- lifecycle ----------------------------------------------------------

    def start(self, register_timeout: Optional[float] = None) -> None:
        """Serve + register + start heartbeats.  Call AFTER the engine
        precompiled: the router measures warm-up as spawn-to-lease, so
        registration is the 'ready' edge of the cost model."""
        self._http_thread.start()
        deadline = time.monotonic() + (
            register_timeout if register_timeout is not None
            else self.ccfg.spawn_grace_s
        )
        if not self._register(deadline):
            raise WireError(
                f"replica {self.replica_id} could not register with "
                f"{self.router_host}:{self.router_port}"
            )
        self._beat_thread.start()

    def close(self) -> None:
        self._stop.set()
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._http_thread.is_alive():
            self._http_thread.join(timeout=5.0)
        if self._beat_thread.is_alive():
            self._beat_thread.join(timeout=5.0)

    def wait_closed(self, timeout: Optional[float] = None) -> bool:
        """Park until ``close()`` (cli/replica.py's main-thread wait)."""
        return self._stop.wait(timeout=timeout)

    # -- control-plane client ----------------------------------------------

    def _register(self, deadline: float) -> bool:
        while not self._stop.is_set():
            if time.monotonic() >= deadline:
                return False
            try:
                status, body = _post_json(
                    self.router_host, self.router_port, "/register",
                    {
                        "replica_id": self.replica_id,
                        "host": self.host, "port": self.port,
                        "epoch": self._epoch, "pid": self.pid,
                        "ready": self._ready(),
                    },
                    timeout=self.ccfg.connect_timeout_s,
                )
            except OSError:
                status, body = 0, {}
            if status == 200:
                return True
            if status == 409:   # stale epoch: jump past the table's
                self._epoch = max(self._epoch, int(
                    body.get("epoch", self._epoch))) + 1
            # 503 = partitioned, 0 = unreachable: keep trying
            if self._stop.wait(min(0.2, self.ccfg.heartbeat_interval_s)):
                return False
        return False

    def _ready(self) -> bool:
        return bool(getattr(self.engine, "is_ready", True)) \
            and not self._draining

    def _beat_loop(self) -> None:
        interval = self.ccfg.heartbeat_interval_s
        while not self._stop.wait(interval):
            try:
                status, body = _post_json(
                    self.router_host, self.router_port, "/heartbeat",
                    {
                        "replica_id": self.replica_id,
                        "epoch": self._epoch,
                        "ready": self._ready(),
                    },
                    timeout=self.ccfg.connect_timeout_s,
                )
            except OSError:
                continue   # unreachable/partitioned: the lease just ages
            if status in (409, 410):
                # stale epoch or expired/unknown lease: this incarnation
                # lost its lease (partition heal, router restart) —
                # re-register above the table's epoch and carry on
                self._epoch = max(self._epoch, int(
                    body.get("epoch", self._epoch))) + 1
                self._register(time.monotonic() + interval)

    # -- endpoints ----------------------------------------------------------

    def _handle_healthz(self, body: Dict, headers=None) -> Tuple[int, Dict]:
        ready = self._ready()
        return (200 if ready else 503), {
            "ready": ready,
            "replica_id": self.replica_id,
            "epoch": self._epoch,
            "draining": self._draining,
            "compile_count": int(getattr(self.engine, "compile_count", 0)),
            "dispatch_count": int(getattr(self.engine, "dispatch_count", 0)),
            "wire_dispatches": int(self._dispatch_ctr.value),
            "idempotent_hits": int(self._idem_hits.value),
        }

    def _handle_drain(self, body: Dict, headers=None) -> Tuple[int, Dict]:
        self._draining = True
        return 200, {"ok": True, "replica_id": self.replica_id}

    def _handle_metrics(self, body: Dict, headers=None) -> Tuple[int, Dict]:
        """Raw registry state for the router's federation scraper:
        counters/gauges plus histograms with their raw bucket counts, so
        the router merges buckets instead of averaging percentiles."""
        return 200, self.registry.export_state()

    def _handle_spans(self, body: Dict, headers=None) -> Tuple[int, Dict]:
        """This process's span ring + tail-sampled keep-store — the
        router's trace assembler stitches these with its own spans."""
        ring = get_span_ring()
        return 200, {
            "replica_id": self.replica_id,
            "spans": ring.spans(),
            "kept": {tid: ring.spans(tid)
                     for tid in ring.kept_trace_ids()},
            "stats": ring.stats(),
        }

    def _handle_profile(self, body: Dict, headers=None) -> Tuple[int, Dict]:
        """One bounded jax.profiler capture, off-thread (the handler
        answers immediately; the fan-out hits every replica at once).
        Single-flight: a capture already running answers 409."""
        secs = min(60.0, max(0.05, float(body.get("seconds", 1.0) or 1.0)))
        out_dir = str(body.get("dir")
                      or f"/tmp/jax-profile-{self.replica_id}")
        if self._profiling.is_set():
            return 409, {"error": "profile already running",
                         "replica_id": self.replica_id}
        self._profiling.set()

        def _capture() -> None:
            try:
                import jax
                jax.profiler.start_trace(out_dir)
                try:
                    self._stop.wait(secs)   # stop-aware, never a bare sleep
                finally:
                    jax.profiler.stop_trace()
            except Exception as e:
                # best-effort: profiling never takes a replica down, but
                # the failure is counted so a dead fan-out is visible
                self.registry.counter(
                    "replica_profile_errors_total",
                    labels={"error": type(e).__name__},
                    help="failed jax.profiler captures by error type",
                ).inc()
            finally:
                self._profiling.clear()

        threading.Thread(
            target=_capture, name=f"replica-{self.replica_id}-profile",
            daemon=True,
        ).start()
        return 200, {"ok": True, "replica_id": self.replica_id,
                     "dir": out_dir, "seconds": secs}

    def _handle_dispatch(self, body: Dict, headers=None) -> Tuple[int, Dict]:
        if self._draining:
            return 503, {"error": "draining"}
        key = body.get("key", "")
        reqs = body.get("requests", [])
        hedge_leg = (headers.get("X-Hedge-Leg")
                     if headers is not None else None) or "primary"
        served_by = f"{self.host}:{self.port}"
        # exactly-once via check-then-claim-then-store: the lock guards
        # only the cache + in-flight bookkeeping (never engine.run — the
        # engine takes its own locks, and nesting them under the handler
        # lock would invert the committed order).  The duplicate leg of
        # a hedge either hits the cache, or parks on the first leg's
        # in-flight event and re-checks — never a double run of a batch
        # that succeeds.  A FAILED first leg clears its claim with no
        # cache entry, so the duplicate leg re-runs: at-least-once
        # delivery, at-most-once successful execution.
        while True:
            wait_for = None
            with self._dispatch_lock:
                if key and key in self._idem:
                    self._idem.move_to_end(key)
                    self._idem_hits.inc()
                    cached = dict(self._idem[key])
                    cached["idempotent"] = True
                    return 200, cached
                if key and key in self._inflight:
                    wait_for = self._inflight[key]
                else:
                    if key:
                        self._inflight[key] = threading.Event()
                    break
            # stop-aware park: the first leg's wall time is bounded by
            # its caller's wire read timeout, ours by the same client's
            wait_for.wait(timeout=1.0)
            if self._stop.is_set():
                return 503, {"error": "stopping"}
        try:
            requests = [decode_request(d) for d in reqs]
            t0_wall = time.time()     # span start_ts: wall, cross-process
            t0 = time.monotonic()     # span duration: monotonic (JL009)
            results = self.engine.run(requests)
            dt = time.monotonic() - t0
            payload = {
                "served_by": served_by,
                "replica_id": self.replica_id,
                "results": [encode_result(r) for r in results],
                "idempotent": False,
            }
            # one replica_dispatch span per distinct trace in the batch,
            # recorded after the fact so tracing never sits on the wire
            # path; the engine's own engine_run spans land as siblings
            seen_traces = set()
            for r in requests:
                ctx = getattr(r, "trace", None)
                if ctx is None or ctx.trace_id in seen_traces:
                    continue
                seen_traces.add(ctx.trace_id)
                Span.record(
                    "replica_dispatch", t0_wall, dt, parent=ctx,
                    replica=self.replica_id, rows=len(requests),
                    hedge_leg=hedge_leg,
                )
        except BaseException:
            if key:
                with self._dispatch_lock:
                    ev = self._inflight.pop(key, None)
                if ev is not None:
                    ev.set()
            raise
        if key:
            with self._dispatch_lock:
                self._idem[key] = payload
                while len(self._idem) > self._idem_cap:
                    self._idem.popitem(last=False)
                    self._idem_evict.inc()
                ev = self._inflight.pop(key, None)
            if ev is not None:
                ev.set()
        self._dispatch_ctr.inc()
        return 200, payload


# ---------------------------------------------------------------------------
# router side: the remote replica proxy
# ---------------------------------------------------------------------------


class RemoteEngine:
    """One remote replica process, seen through the router's duck-typed
    engine surface (``precompile()`` + ``run()``) — the RemoteReplica
    interface rollout, autoscaling, and the breaker re-warm all drive.

    No vocoder handle is exposed (``vocoder = None``): streaming
    continuations are replica-affine device work and are served by the
    in-process tier; the HTTP layer already answers 400 when streaming
    is unavailable.
    """

    vocoder = None

    def __init__(self, cluster: "ClusterRouter",
                 registry: Optional[MetricsRegistry] = None,
                 spawn_extra: Optional[Dict] = None):
        self._cluster = cluster
        self._registry = registry if registry is not None \
            else cluster.registry
        self._spawn_extra = spawn_extra
        # bound by precompile() (the warm-up thread) strictly before the
        # dispatch worker starts — the same happens-before edge
        # rep.engine itself rides
        self.replica_id: str = ""
        self.host: str = ""
        self.port: int = 0

    # -- warm-up ------------------------------------------------------------

    def precompile(self) -> float:
        """Adopt-or-spawn, then wait for a live+ready lease.  The wall
        time returned feeds ``serve_replica_warmup_seconds`` via the
        router's ``_warm`` — process spawn, the child's AOT precompile,
        and registration are all inside the measured window, which keeps
        the autoscaler's warm-up cost model honest for real processes
        (adoption of a warm orphan is the cheap path, and measures
        cheap)."""
        t0 = time.monotonic()
        rid, host, port = self._cluster._acquire_replica(self._spawn_extra)
        self.replica_id = rid
        self.host = host
        self.port = port
        return time.monotonic() - t0

    @property
    def is_ready(self) -> bool:
        lease = self._cluster.leases.get(self.replica_id)
        return lease is not None and lease.ready \
            and time.monotonic() <= lease.deadline

    @property
    def compile_count(self) -> int:
        """Remote compile counter via /healthz; -1 when unreachable."""
        try:
            status, body = _get_json(
                self.host, self.port, "/healthz",
                timeout=self._cluster.ccfg.connect_timeout_s,
            )
        except OSError:
            return -1
        return int(body.get("compile_count", -1))

    # -- hedged dispatch ----------------------------------------------------

    def _hedge_delay_s(self, klass: str) -> float:
        ccfg = self._cluster.ccfg
        hist = self._registry.histogram(
            "serve_wire_latency_seconds", labels={"class": klass},
            help="winning wire dispatch round-trip per priority class "
                 "(the hedge-delay quantile source)",
        )
        q = hist.percentile(ccfg.hedge_quantile) if hist.count else None
        delay = q if q is not None else ccfg.hedge_max_ms / 1e3
        return min(max(delay, ccfg.hedge_min_ms / 1e3),
                   ccfg.hedge_max_ms / 1e3)

    def run(self, requests: List[SynthesisRequest]) -> List[SynthesisResult]:
        """One coalesced dispatch over the wire, hedged.

        Per-class discipline: the whole call is bounded by the class's
        deadline budget (+ grace); a failed first leg retries once with
        backoff; a *slow* first leg fires a hedge to a different host
        after the class's observed hedge quantile.  Both legs carry the
        same idempotency key; the first success wins and the loser's
        connection is closed.  Total failure raises ``WireError`` into
        the worker's except path — the router requeues the batch at its
        original deadline, exactly like an in-process raise.
        """
        if not requests:
            return []
        c = self._cluster
        if c.is_partitioned(self.replica_id):
            raise WireError(
                f"replica {self.replica_id} is partitioned from the router"
            )
        fleet = c.fleet
        klass = requests[0].priority or fleet.default_class
        budget_s = fleet.class_deadline_ms.get(
            klass, max(fleet.class_deadline_ms.values())
        ) / 1e3 + fleet.deadline_grace_ms / 1e3
        key = batch_key(requests)
        payload = json.dumps({
            "key": key,
            "requests": [encode_request(r) for r in requests],
        }).encode("utf-8")
        # the distinct trace contexts this dispatch carries: every leg
        # records one "remote_dispatch" span per trace, so hedge legs
        # appear as SIBLINGS under the request's router-side span, each
        # tagged with hedge_leg= and (exactly one) winner=True
        traces: List[TraceContext] = []
        seen_tids: set = set()
        for r in requests:
            t_ctx = getattr(r, "trace", None)
            if t_ctx is not None and t_ctx.trace_id not in seen_tids:
                seen_tids.add(t_ctx.trace_id)
                traces.append(t_ctx)
        wire_headers = {}
        if traces:
            # the header-level join (per ISSUE: X-Trace-* rides the
            # wire); the body carries the full per-request contexts
            wire_headers["X-Trace-Id"] = traces[0].trace_id
            wire_headers["X-Parent-Span"] = traces[0].span_id or ""

        hedge_enabled = c.ccfg.hedge_quantile > 0.0
        hedge_delay = self._hedge_delay_s(klass)
        deadline = time.monotonic() + budget_s

        # at most 3 legs ever run (primary, one retry, one hedge), so 4
        # slots can never block a producer (JL011: bounded by design)
        out_q: "queue.Queue" = queue.Queue(maxsize=4)
        conns: Dict[str, HTTPConnection] = {}
        threads: List[threading.Thread] = []
        leg_recs: Dict[str, List[Dict]] = {}

        def record_leg(tag: str, host: str, port: int, t0_wall: float,
                       dt: float, err: Optional[BaseException]) -> None:
            """One remote_dispatch span per trace this leg carried.  The
            ring stores dict references, so the winner flag can be set
            in place once the race resolves."""
            if not traces or not obstrace.tracing_enabled():
                return
            ring = get_span_ring()
            recs = []
            for ctx in traces:
                child = ctx.child()
                rec: Dict = {
                    "name": "remote_dispatch",
                    "start_ts": t0_wall,
                    "duration_s": dt,
                    **child.as_dict(),
                    "fields": {"hedge_leg": tag,
                               "target": f"{host}:{port}"},
                }
                if err is not None:
                    rec["ok"] = False
                    rec["error"] = f"{type(err).__name__}: {err}"
                ring.add(rec)
                recs.append(rec)
            leg_recs[tag] = recs

        def leg(host: str, port: int, tag: str) -> None:
            t0 = time.monotonic()
            t0_wall = time.time()
            hdrs = {"Content-Type": "application/json",
                    "X-Hedge-Leg": tag}
            hdrs.update(wire_headers)
            conn = HTTPConnection(
                host, port, timeout=max(0.05, deadline - t0)
            )
            conns[tag] = conn
            err_out: Optional[BaseException] = None
            try:
                conn.request("POST", "/dispatch", body=payload,
                             headers=hdrs)
                resp = conn.getresponse()
                data = resp.read()
                if resp.status != 200:
                    raise WireError(
                        f"dispatch to {host}:{port} answered {resp.status}"
                    )
                body = json.loads(data)
                try:
                    out_q.put((tag, time.monotonic() - t0, body, None),
                              timeout=1.0)
                except queue.Full:
                    pass
            except BaseException as e:
                err_out = e
                try:
                    out_q.put((tag, time.monotonic() - t0, None, e),
                              timeout=1.0)
                except queue.Full:
                    pass
            finally:
                record_leg(tag, host, port, t0_wall,
                           time.monotonic() - t0, err_out)
                conn.close()

        def fire(host: str, port: int, tag: str) -> None:
            t = threading.Thread(
                target=leg, args=(host, port, tag),
                name=f"wire-{self.replica_id}-{tag}", daemon=True,
            )
            threads.append(t)
            t.start()

        fire(self.host, self.port, "primary")
        outstanding = 1
        hedge_fired = False
        retried = False
        winner = None
        last_err: Optional[BaseException] = None
        hedge_due = time.monotonic() + hedge_delay

        def fire_hedge() -> bool:
            target = c.hedge_target(self.replica_id)
            if target is None:
                return False
            h_host, h_port, _h_id = target
            self._registry.counter(
                "serve_hedge_fired_total", labels={"class": klass},
                help="hedge legs fired (slow or failed first leg)",
            ).inc()
            fire(h_host, h_port, "hedge")
            return True

        while winner is None:
            now = time.monotonic()
            if now >= deadline:
                break
            if hedge_enabled and not hedge_fired and now >= hedge_due:
                hedge_fired = True   # one hedge per dispatch, target or not
                if fire_hedge():
                    outstanding += 1
                continue
            wait = deadline - now
            if hedge_enabled and not hedge_fired:
                wait = min(wait, hedge_due - now)
            try:
                tag, dt, body, err = out_q.get(timeout=max(0.01, wait))
            except queue.Empty:
                continue
            outstanding -= 1
            if err is None:
                winner = (tag, dt, body)
                break
            last_err = err
            if c.is_partitioned(self.replica_id) and outstanding == 0 \
                    and not hedge_fired:
                break   # mid-dispatch partition: fail fast, requeue
            if tag in ("primary", "retry") and hedge_enabled \
                    and not hedge_fired:
                # a FAILED (not merely slow) first leg hedges right away
                hedge_fired = True
                if fire_hedge():
                    outstanding += 1
                    continue
            if not retried and time.monotonic() < deadline \
                    and outstanding == 0:
                # per-class backoff before the single wire retry: scaled
                # to the class budget, never past the deadline
                retried = True
                backoff = min(budget_s / 20.0,
                              max(0.0, deadline - time.monotonic()))
                if backoff > 0 and self._cluster.stopped.wait(backoff):
                    break
                fire(self.host, self.port, "retry")
                outstanding += 1
                continue
            if outstanding == 0:
                break

        # first-wins cancel: closing the losers' connections unblocks
        # their threads (they error out and drop their late result)
        for tag, conn in list(conns.items()):
            if winner is not None and tag == winner[0]:
                continue
            try:
                conn.close()
            except OSError:
                pass
        for t in threads:
            t.join(timeout=1.0)

        if winner is None:
            raise WireError(
                f"dispatch to replica {self.replica_id} failed within its "
                f"{klass!r} budget ({budget_s:.3f}s): "
                f"{type(last_err).__name__ if last_err else 'timeout'}: "
                f"{last_err}"
            ) from last_err
        tag, dt, body = winner
        # all legs are joined: leg_recs is stable — flag the winner's
        # spans in place (the ring holds these same dict objects)
        for rec in leg_recs.get(tag, []):
            rec.setdefault("fields", {})["winner"] = True
        self._registry.histogram(
            "serve_wire_latency_seconds", labels={"class": klass},
            help="winning wire dispatch round-trip per priority class "
                 "(the hedge-delay quantile source)",
        ).observe(dt)
        if tag == "hedge":
            self._registry.counter(
                "serve_hedge_won_total", labels={"class": klass},
                help="dispatches won by the hedge leg",
            ).inc()
            # a hedge win is a tail event by definition: pin its traces
            for t_ctx in traces:
                c._note_pressure(t_ctx, "hedge_won")
        served_by = body.get("served_by") or f"{self.host}:{self.port}"
        return [decode_result(d, served_by=served_by)
                for d in body.get("results", [])]


# ---------------------------------------------------------------------------
# the cluster router
# ---------------------------------------------------------------------------


class ClusterRouter(FleetRouter):
    """A FleetRouter whose replicas are processes with heartbeat leases.

    ``spawn(replica_id, router_addr, extra)`` launches one replica
    process and returns a Popen-shaped handle (``poll``/``terminate``/
    ``kill``/``wait``); the process must start a ``ReplicaServer``
    pointed at ``router_addr`` under that ``replica_id``.  Everything
    else — EDF, watchdog, breakers, requeue, rollout, autoscaling — is
    inherited: a ``RemoteEngine`` is just an engine to the base class.
    """

    def __init__(
        self,
        spawn: Callable,
        cfg,
        replicas: Optional[int] = None,
        registry: Optional[MetricsRegistry] = None,
        events: Optional[JsonlEventLog] = None,
        style=None,
        fault_plan: Optional[FaultPlan] = None,
        tier: Optional[str] = None,
    ):
        ccfg = cfg.serve.cluster
        self.ccfg = ccfg
        self._spawn = spawn
        self.leases = LeaseTable(ccfg.lease_ttl_s)
        self._proc_lock = make_lock("ClusterRouter._proc_lock")
        self._procs: Dict[str, object] = {}     # replica_id -> process
        self._orphans: List[str] = []           # adoptable warm processes
        self._partitioned: set = set()
        self._id_seq = 0
        # stop signal for waits that cannot ride the router cond (the
        # warm-up thread's acquire poll, the wire retry backoff)
        self.stopped = threading.Event()
        # quorum is the autoscaler's floor too: scaling below it would
        # wedge /healthz at 503 with the fleet nominally 'healthy'
        self.scale_floor = ccfg.quorum
        # the control server must listen before the first spawn (the
        # child registers during super().__init__'s warm-up)
        self._control = _JsonServer(
            (ccfg.control_host, ccfg.control_port), {
                ("POST", "/register"): self._handle_register,
                ("POST", "/heartbeat"): self._handle_heartbeat,
                ("GET", "/cluster"): lambda body, headers=None: (200, {
                    "replicas": self.cluster_stats()
                }),
            })
        self.control_host = ccfg.control_host
        self.control_port = self._control.server_address[1]
        self._control_thread = threading.Thread(
            target=self._control.serve_forever,
            name="cluster-control-http", daemon=True,
        )
        self._control_thread.start()
        # style service stays router-side: style ids resolve to gamma/
        # beta vectors BEFORE dispatch and ship over the wire, so replica
        # processes never run the reference encoder
        super().__init__(
            self._remote_factory, cfg, replicas=replicas,
            registry=registry, events=events, style=style,
            fault_plan=fault_plan, tier=tier,
        )
        self._lease_requeue_hist = self.registry.histogram(
            "serve_lease_requeue_seconds",
            help="lease expiry instant -> in-flight work requeued "
                 "(the failover latency the lease sweeper adds)",
        )
        self._lease_expired_ctr = self.registry.counter(
            "serve_lease_expired_total",
            help="leases the sweeper expired into _replica_failed",
        )
        self._cluster_thread = threading.Thread(
            target=self._cluster_supervise,
            name="cluster-lease-sweeper", daemon=True,
        )
        self._cluster_thread.start()
        # metrics federation: scrape each live replica's /metrics on a
        # stop-aware cadence into a cache the router's own /metrics
        # handler merges (merge_states) — fleet p999 comes from merged
        # buckets, never from averaged percentiles
        self._fed_lock = make_lock("ClusterRouter._fed_lock")
        self._fed_states: Dict[str, Dict] = {}
        self._fed_scrapes = self.registry.counter(
            "serve_federation_scrapes_total",
            help="replica /metrics scrapes the federator completed",
        )
        self._fed_errors = self.registry.counter(
            "serve_federation_errors_total",
            help="replica /metrics scrapes that failed (unreachable, "
                 "partitioned, bad payload)",
        )
        self._fed_thread = threading.Thread(
            target=self._federate,
            name="cluster-metrics-federator", daemon=True,
        )
        self._fed_thread.start()

    @property
    def control_addr(self) -> str:
        return f"{self.control_host}:{self.control_port}"

    def _remote_factory(self, registry: MetricsRegistry) -> RemoteEngine:
        return RemoteEngine(self, registry)

    def remote_factory(self, spawn_extra: Optional[Dict] = None) -> Callable:
        """A replica factory for ``start_replica`` — the rollout canary
        passes ``spawn_extra`` (e.g. a candidate checkpoint path) so the
        spawned process builds the candidate weights while the default
        factory keeps building the live version."""
        def factory(registry: MetricsRegistry) -> RemoteEngine:
            return RemoteEngine(self, registry, spawn_extra=spawn_extra)
        return factory

    # -- control-plane endpoints -------------------------------------------

    def _handle_register(self, body: Dict, headers=None) -> Tuple[int, Dict]:
        rid = str(body.get("replica_id", ""))
        if not rid:
            return 400, {"error": "missing replica_id"}
        if self.is_partitioned(rid):
            return 503, {"error": "partitioned"}
        now = time.monotonic()
        ok, epoch = self.leases.register(
            rid, str(body.get("host", "127.0.0.1")),
            int(body.get("port", 0)), int(body.get("epoch", 1)),
            int(body.get("pid", 0)), now,
        )
        if not ok:
            return 409, {"error": "stale_epoch", "epoch": epoch}
        if body.get("ready"):
            self.leases.heartbeat(rid, int(body.get("epoch", 1)),
                                  True, now)
        ev = getattr(self, "events", None)
        if ev is not None:
            ev.emit("replica_register", replica_id=rid, epoch=epoch,
                    host=f"{body.get('host')}:{body.get('port')}")
        return 200, {
            "epoch": epoch,
            "lease_ttl_s": self.leases.ttl_s,
            "heartbeat_interval_s": self.ccfg.heartbeat_interval_s,
        }

    def _handle_heartbeat(self, body: Dict,
                          headers=None) -> Tuple[int, Dict]:
        rid = str(body.get("replica_id", ""))
        if self.is_partitioned(rid):
            return 503, {"error": "partitioned"}
        status = self.leases.heartbeat(
            rid, int(body.get("epoch", 0)), bool(body.get("ready")),
            time.monotonic(),
        )
        code = {"renewed": 200, "stale": 409,
                "expired": 410, "unknown": 410}[status]
        payload: Dict = {"status": status}
        if status in ("stale", "expired"):
            lease = self.leases.get(rid)
            if lease is not None:
                payload["epoch"] = lease.epoch
        return code, payload

    # -- partition drill ----------------------------------------------------

    def is_partitioned(self, replica_id: str) -> bool:
        with self._proc_lock:
            return replica_id in self._partitioned

    def partition(self, replica_id: str) -> None:
        """Deterministically drop all router<->replica packets for one
        replica: its heartbeats stop renewing (503), its dispatches fail
        fast, and adoption probes refuse — until ``heal``."""
        with self._proc_lock:
            self._partitioned.add(replica_id)
        ev = getattr(self, "events", None)
        if ev is not None:
            ev.emit("net_partition", replica_id=replica_id)

    def heal(self, replica_id: str) -> None:
        with self._proc_lock:
            self._partitioned.discard(replica_id)
        ev = getattr(self, "events", None)
        if ev is not None:
            ev.emit("net_partition_heal", replica_id=replica_id)

    # -- chaos hooks (fleet._dispatch fires these) --------------------------

    def _chaos_proc_kill(self, rep: Replica) -> bool:
        eng = rep.engine
        if not isinstance(eng, RemoteEngine):
            return False
        with self._proc_lock:
            proc = self._procs.get(eng.replica_id)
        if proc is None:
            return False
        try:
            proc.kill()
        except OSError:
            return False
        ev = getattr(self, "events", None)
        if ev is not None:
            ev.emit("chaos_proc_kill", replica_id=eng.replica_id,
                    replica=rep.index)
        return True   # the wire call that follows fails organically

    def _chaos_partition(self, rep: Replica) -> bool:
        eng = rep.engine
        if not isinstance(eng, RemoteEngine):
            return False
        self.partition(eng.replica_id)
        return True

    # -- process pool -------------------------------------------------------

    def _new_id(self) -> str:
        with self._proc_lock:
            self._id_seq += 1
            return f"r{self._id_seq}"

    def _take_orphan(self) -> Optional[str]:
        """Pop one adoptable orphan (live process); dead orphans are
        reaped on the way."""
        with self._proc_lock:
            while self._orphans:
                rid = self._orphans.pop(0)
                proc = self._procs.get(rid)
                if proc is None:
                    continue
                if proc.poll() is not None:   # process is dead: reap
                    self._procs.pop(rid, None)
                    self.leases.drop(rid)
                    continue
                return rid
        return None

    def _stash_orphan(self, replica_id: str) -> None:
        """A failed replica's still-live process becomes adoptable (the
        partition-heal path re-admits it warm); a dead one is reaped."""
        if not replica_id:
            return
        with self._proc_lock:
            proc = self._procs.get(replica_id)
            if proc is None:
                return
            if proc.poll() is not None:
                self._procs.pop(replica_id, None)
                self.leases.drop(replica_id)
                return
            if replica_id not in self._orphans:
                self._orphans.append(replica_id)

    def _acquire_replica(
        self, spawn_extra: Optional[Dict] = None
    ) -> Tuple[str, str, int]:
        """Adopt-or-spawn one replica process and wait for its live,
        ready lease.  Raises ``WireError`` on partition, process death,
        or the spawn grace deadline — the caller is ``_warm``, whose
        except path runs the breaker's half-open bookkeeping."""
        rid = self._take_orphan() if spawn_extra is None else None
        spawned = False
        if rid is None:
            rid = self._new_id()
            proc = self._spawn(rid, self.control_addr, spawn_extra)
            with self._proc_lock:
                self._procs[rid] = proc
            spawned = True
        if self.is_partitioned(rid):
            self._stash_orphan(rid)
            raise WireError(f"replica {rid} is partitioned from the router")
        deadline = time.monotonic() + self.ccfg.spawn_grace_s
        poll_s = min(0.05, self.ccfg.heartbeat_interval_s / 2.0)
        while True:
            if self.stopped.is_set():
                self._stash_orphan(rid)
                raise WireError("router is closing")
            if self.is_partitioned(rid):
                self._stash_orphan(rid)
                raise WireError(
                    f"replica {rid} partitioned during warm-up"
                )
            with self._proc_lock:
                proc = self._procs.get(rid)
            rc = proc.poll() if proc is not None else -1
            if rc is not None:
                with self._proc_lock:
                    self._procs.pop(rid, None)
                self.leases.drop(rid)
                raise WireError(
                    f"replica {rid} process exited (rc={rc}) before READY"
                )
            now = time.monotonic()
            lease = self.leases.get(rid)
            if lease is not None and lease.ready and now <= lease.deadline:
                try:
                    status, _ = _get_json(
                        lease.host, lease.port, "/healthz",
                        timeout=self.ccfg.connect_timeout_s,
                    )
                except OSError:
                    status = 0
                if status == 200:
                    return rid, lease.host, lease.port
            if now >= deadline:
                if spawned:
                    try:
                        proc.kill()
                    except OSError:
                        pass
                    with self._proc_lock:
                        self._procs.pop(rid, None)
                    self.leases.drop(rid)
                else:
                    self._stash_orphan(rid)
                raise WireError(
                    f"replica {rid} missed the {self.ccfg.spawn_grace_s:g}s "
                    "spawn grace (no live+ready lease)"
                )
            self.stopped.wait(poll_s)

    def hedge_target(self, exclude: str) -> Optional[Tuple[str, int, str]]:
        """Another host a hedge leg can go to: a live, ready,
        un-partitioned lease that is not ``exclude``."""
        now = time.monotonic()
        for row in self.leases.snapshot(now):
            rid = row["replica_id"]
            if rid == exclude or row["expired"] or not row["ready"]:
                continue
            if self.is_partitioned(rid):
                continue
            host, _, port = row["host"].rpartition(":")
            return host, int(port), rid
        return None

    # -- metrics federation + trace fan-in ----------------------------------

    def _federate(self) -> None:
        """Scrape live replicas' /metrics into the federation cache.

        Lock discipline: every wire call runs with NO lock held; the
        cache swap under ``_fed_lock`` is pure dict work (JL021).  A
        lease-expired or partitioned replica is skipped — and dropped
        from the cache, so its frozen counters stop polluting the
        merged view until it re-registers."""
        interval = max(0.05, self.ccfg.heartbeat_interval_s)
        while not self.stopped.wait(interval):
            now = time.monotonic()
            rows = self.leases.snapshot(now)
            fresh: Dict[str, Dict] = {}
            live = set()
            for row in rows:
                rid = row["replica_id"]
                if row["expired"] or self.is_partitioned(rid):
                    continue
                live.add(rid)
                host, _, port = row["host"].rpartition(":")
                try:
                    status, state = _get_json(
                        host, int(port), "/metrics",
                        timeout=self.ccfg.connect_timeout_s,
                    )
                except OSError:
                    status, state = 0, {}
                if status == 200 and isinstance(
                        state.get("metrics"), list):
                    fresh[rid] = state
                    self._fed_scrapes.inc()
                else:
                    self._fed_errors.inc()
            with self._fed_lock:
                self._fed_states.update(fresh)
                for rid in list(self._fed_states):
                    if rid not in live:
                        self._fed_states.pop(rid)

    def federated_states(self) -> List[Tuple[str, Dict]]:
        """The latest scraped ``(replica_id, export_state)`` pairs."""
        with self._fed_lock:
            return sorted(self._fed_states.items())

    def federated_registry(self) -> MetricsRegistry:
        """The fleet-merged view: counters summed, histogram buckets
        merged elementwise, gauges ``replica=``-labeled — the
        ``fleet_*`` series the router's /metrics appends."""
        return merge_states(self.federated_states())

    def fetch_remote_spans(
        self, trace_id: Optional[str] = None
    ) -> List[Dict]:
        """Pull replica-side spans for cross-process trace assembly
        (``GET /debug/trace/<req_id>``). Best-effort: unreachable or
        partitioned replicas contribute nothing; ring + keep-store
        duplicates dedup by span_id."""
        out: Dict[str, Dict] = {}
        for row in self.leases.snapshot(time.monotonic()):
            rid = row["replica_id"]
            if row["expired"] or self.is_partitioned(rid):
                continue
            host, _, port = row["host"].rpartition(":")
            try:
                status, payload = _get_json(
                    host, int(port), "/debug/spans",
                    timeout=self.ccfg.connect_timeout_s,
                )
            except OSError:
                continue
            if status != 200:
                continue
            cand = list(payload.get("spans", []))
            for kept in (payload.get("kept") or {}).values():
                cand.extend(kept)
            for s in cand:
                if trace_id is not None \
                        and s.get("trace_id") != trace_id:
                    continue
                sid = s.get("span_id")
                if sid:
                    out[sid] = s
        return list(out.values())

    def profile_fanout(self, seconds: float = 1.0) -> Dict[str, bool]:
        """POST /debug/profile to every live replica at once — one
        fleet-wide jax.profiler capture window."""
        out: Dict[str, bool] = {}
        for row in self.leases.snapshot(time.monotonic()):
            rid = row["replica_id"]
            if row["expired"] or self.is_partitioned(rid):
                continue
            host, _, port = row["host"].rpartition(":")
            try:
                status, _body = _post_json(
                    host, int(port), "/debug/profile",
                    {"seconds": seconds},
                    timeout=self.ccfg.connect_timeout_s,
                )
                out[rid] = status == 200
            except OSError:
                out[rid] = False
        return out

    # -- lease sweep + reap -------------------------------------------------

    def _cluster_supervise(self) -> None:
        """Expire leases into ``_replica_failed`` (the failover path)
        and reap the processes of replicas the router retired.

        Lock discipline: lease reads happen OUTSIDE the router cond
        (``LeaseTable._lock`` sits earlier in the committed lock order
        than ``FleetRouter._cond``, so nesting it inside would invert
        the runtime witness); the in-flight steal then re-acquires the
        cond and re-validates state, exactly like the hang watchdog's
        collect-then-act split."""
        interval = max(0.02, self.ccfg.heartbeat_interval_s / 2.0)
        while True:
            candidates = []
            reap = []
            with self._cond:
                if self._closing:
                    return
                self._cond.wait(timeout=interval)
                if self._closing:
                    return
                for rep in self._replicas:
                    eng = rep.engine
                    if not isinstance(eng, RemoteEngine):
                        continue
                    if rep.state == READY:
                        candidates.append((rep, eng))
                    elif rep.state == STOPPED and eng.replica_id:
                        reap.append(eng.replica_id)
            now = time.monotonic()
            for rep, eng in candidates:
                lease = self.leases.get(eng.replica_id)
                if lease is not None and now <= lease.deadline:
                    continue
                t_exp = lease.deadline if lease else now
                with self._cond:
                    # re-validate: the replica may have failed/drained
                    # (or re-warmed onto a new engine) since the scan
                    if rep.state != READY or rep.engine is not eng:
                        continue
                    # steal the in-flight batch exactly like the hang
                    # watchdog: the worker's late wire result fails its
                    # claim and is discarded
                    batch = rep.inflight
                    rep.inflight = None
                    rep.dispatch_started = None
                age = time.monotonic() - t_exp
                self._lease_expired_ctr.inc()
                self._replica_failed(rep, batch or [], LeaseExpired(
                    f"replica {eng.replica_id} lease expired "
                    f"{age:.3f}s ago (miss budget "
                    f"{self.ccfg.lease_miss_budget} exceeded)",
                    replica_id=eng.replica_id, age_s=age,
                ), kind="lease")
                self._lease_requeue_hist.observe(time.monotonic() - t_exp)
            for rid in reap:
                self._retire_process(rid)

    def _replica_failed(self, rep: Replica, batch, error, kind) -> None:
        eng = rep.engine
        super()._replica_failed(rep, batch, error, kind)
        # the failed replica's process (if still alive) becomes an
        # adoptable orphan: the breaker's next half-open trial re-admits
        # it warm instead of respawning — the partition-heal path
        if isinstance(eng, RemoteEngine):
            self._stash_orphan(eng.replica_id)

    def _retire_process(self, replica_id: str) -> None:
        """Drain + terminate one retired replica's process."""
        with self._proc_lock:
            proc = self._procs.pop(replica_id, None)
            if replica_id in self._orphans:
                self._orphans.remove(replica_id)
        self.leases.drop(replica_id)
        if proc is None or proc.poll() is not None:
            return
        try:
            proc.terminate()
        except OSError:
            pass
        try:
            proc.wait(timeout=self.fleet.drain_timeout_s)
        except (OSError, subprocess.TimeoutExpired):
            try:
                proc.kill()
            except OSError:
                pass

    # -- readiness + stats --------------------------------------------------

    def ready(self) -> bool:
        """Quorum readiness: /healthz stays 503 until at least
        ``cluster.quorum`` replicas are READY."""
        with self._cond:
            return sum(
                r.state == READY for r in self._replicas
            ) >= self.ccfg.quorum

    def cluster_stats(self) -> List[Dict]:
        """Per-replica lease rows (lease age, host, last heartbeat,
        partition flag) for the /healthz cluster block."""
        now = time.monotonic()
        rows = self.leases.snapshot(now)
        for row in rows:
            row["partitioned"] = self.is_partitioned(row["replica_id"])
        return rows

    # -- shutdown -----------------------------------------------------------

    def close(self, flush: bool = True, timeout: float = 30.0) -> None:
        self.stopped.set()
        super().close(flush=flush, timeout=timeout)
        if self._cluster_thread.is_alive():
            self._cluster_thread.join(timeout=5.0)
        if self._fed_thread.is_alive():
            self._fed_thread.join(timeout=5.0)
        with self._proc_lock:
            procs = dict(self._procs)
            self._procs = {}
            self._orphans = []
        for rid, proc in procs.items():
            try:
                proc.terminate()
            except OSError:
                pass
        for rid, proc in procs.items():
            try:
                proc.wait(timeout=5.0)
            except (OSError, subprocess.TimeoutExpired):
                try:
                    proc.kill()
                except OSError:
                    pass
        self._control.shutdown()
        self._control.server_close()
        if self._control_thread.is_alive():
            self._control_thread.join(timeout=5.0)
