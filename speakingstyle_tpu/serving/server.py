"""Stdlib HTTP front-end over the continuous batcher.

``ThreadingHTTPServer`` gives one thread per connection; each handler
thread does the host-side work (JSON parse, G2P, reference-mel lookup),
submits a SynthesisRequest, and blocks on its future — so concurrent
HTTP clients coalesce into shared device dispatches without any async
framework. The synthesize handler never compiles or dispatches jax work
(JL008 enforces that compiles stay out of request handlers); all device
work happens on the batcher's single dispatch thread against
AOT-precompiled executables. The one jax touch in a handler is the
/debug/profile capture hook, which only starts/stops the profiler.

API (request schema — every field but "text" optional):
  POST /synthesize     {"text": ..., "speaker_id"?/"speaker"? (numeric id
                        or speakers.json name — unknown names and
                        out-of-registry ids -> 400), "pitch_control"?,
                        "energy_control"?, "duration_control"? (a scalar,
                        or a per-WORD list like [1.0, 2.5, 1.0] — English
                        text only; expanded to per-phoneme arrays via the
                        span-preserving G2P, wrong word count -> 400),
                        "style_id"? (a POST /styles content hash),
                        "ref_audio"? (server-side wav path, confined to
                        serve.style.ref_dir — absolute paths and ".."
                        escapes -> 400; disabled entirely when ref_dir
                        is unset),
                        "priority"? (SLO class, a
                        serve.fleet.class_deadline_ms key — default
                        serve.fleet.default_class; unknown class -> 400)}
                       -> audio/wav (16-bit PCM); X-Request-Id on every
                       response (success AND error JSON), joinable with
                       the batcher's serve_dispatch span/event records.
                       429 + Retry-After under backpressure shed
                       (serve_shed_total), 503 during shutdown
                       (serve_rejected_total) — two different verdicts,
                       two different counters
  POST /synthesize/stream
                       same schema -> chunked audio/wav: a streaming
                       RIFF header, then PCM in overlap-trimmed windows
                       as they are vocoded (serving/streaming.py), each
                       window one precompiled lattice dispatch. Cuts
                       time-to-first-audio to the first-window bound;
                       serve_ttfa_seconds records it
  POST /styles         upload a reference wav (raw audio/wav body, or
                       JSON {"ref_audio": <ref_dir-relative path>}) ->
                       {"style_id": sha256-of-bytes, "ref_frames",
                       "speaker", "cached"}. Content-addressed and
                       idempotent: re-uploading the same bytes returns
                       the same style_id with "cached": true and runs
                       ZERO encoder work. "?speaker=NAME" (or a JSON
                       "speaker" field) binds the style to a registry
                       speaker; /synthesize then rejects that style_id
                       under a different explicit speaker
  GET  /styles         -> {"styles": [{style_id, ref_frames, speaker,
                       d_model}...], "capacity"} — the resident
                       embedding-cache entries, registration-ordered
  GET  /healthz        -> JSON view of the metrics-registry snapshot
                       (compile counter, batch occupancy, queue depth,
                       shed/rejected split) plus build info (git SHA,
                       jax/jaxlib versions, backend, device count) so
                       every probe identifies WHAT is running. Readiness
                       semantics: 503 with per-replica lifecycle states
                       until at least one replica finished precompile —
                       load balancers never route into a compile storm
  GET  /metrics        -> Prometheus text exposition of the same registry
                       (incl. per-bucket serve_program_flops /
                       serve_program_peak_bytes gauges, the
                       serve_achieved_flops_per_sec histograms, and
                       process_rss_bytes / process_uptime_seconds)
  GET  /debug/programs -> one ProgramCard JSON dict per compiled XLA
                       program (obs/cost.py): FLOPs, bytes accessed,
                       argument/output/temp/peak bytes per lattice point
  POST /debug/profile?seconds=N
                       -> capture a jax.profiler trace from the live
                       process (serve.debug_profile gates it)

The registry (obs/) is the single accounting path: ``stats()`` is a view
of ``registry.snapshot()`` — the request counter, occupancy histogram,
and compile counters have no server-side shadow copies (and therefore no
lock-discipline gap between the write and read sides).
"""

import concurrent.futures
import contextlib
import json
import os
import struct
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, Optional
from urllib.parse import parse_qs, urlparse

import numpy as np

from speakingstyle_tpu.configs.config import Config
from speakingstyle_tpu.obs import JsonlEventLog, build_info, process_rss_bytes
from speakingstyle_tpu.obs.quality import last_fail as quality_last_fail
from speakingstyle_tpu.obs.trace import Span, assemble_trace, get_span_ring
from speakingstyle_tpu.serving import streaming
from speakingstyle_tpu.serving.batcher import (
    ContinuousBatcher,
    Overloaded,
    ShutdownError,
)
from speakingstyle_tpu.serving.engine import SynthesisEngine, SynthesisRequest
from speakingstyle_tpu.serving.frontend import FrontendPool
from speakingstyle_tpu.serving.lattice import RequestTooLarge
from speakingstyle_tpu.obs.locks import make_lock
from speakingstyle_tpu.serving.resilience import (
    DeadlineExceeded,
    DispatchError,
    ReplicaError,
)


def wav_bytes(wav: np.ndarray, sampling_rate: int) -> bytes:
    """int16 PCM -> a complete RIFF/WAVE file in memory (stdlib only)."""
    data = np.asarray(wav, np.int16).tobytes()
    hdr = b"RIFF" + struct.pack("<I", 36 + len(data)) + b"WAVE"
    hdr += b"fmt " + struct.pack("<IHHIIHH", 16, 1, 1, sampling_rate,
                                 sampling_rate * 2, 2, 16)
    hdr += b"data" + struct.pack("<I", len(data))
    return hdr + data


def wav_stream_header(sampling_rate: int) -> bytes:
    """A RIFF/WAVE header with unknown-length size fields (0xFFFFFFFF,
    the streaming-wav convention players accept) — sent before the first
    PCM chunk of a chunked /synthesize/stream response."""
    hdr = b"RIFF" + struct.pack("<I", 0xFFFFFFFF) + b"WAVE"
    hdr += b"fmt " + struct.pack("<IHHIIHH", 16, 1, 1, sampling_rate,
                                 sampling_rate * 2, 2, 16)
    hdr += b"data" + struct.pack("<I", 0xFFFFFFFF)
    return hdr


class TextFrontend:
    """Host-side request preparation: G2P, speaker registry, style
    resolution.

    Style resolution order: ``style_id`` (embedding-cache lookup) ->
    ``ref_audio`` (a ``serve.style.ref_dir``-confined server-side path,
    content-addressed through the StyleService so repeats never re-run
    the encoder) -> the server's default reference. The pre-style-service
    per-path mel dict this class used to keep is gone — the bounded
    content-addressed cache in StyleService is the one caching layer
    (jaxlint JL012 bans unbounded caches under serving/).
    """

    def __init__(
        self,
        cfg: Config,
        default_ref_mel: Optional[np.ndarray],
        style=None,  # StyleService; the server wires its backend's in
    ):
        self.cfg = cfg
        self.default_ref_mel = default_ref_mel
        self.style = style
        self._lexicon = None  # loaded on first per-word-control request
        pp = cfg.preprocess
        self.lexicon_path = pp.path.lexicon_path or None
        speakers_path = os.path.join(
            pp.path.preprocessed_path or "", "speakers.json"
        )
        self.speaker_map: Dict[str, int] = {}
        if pp.path.preprocessed_path and os.path.exists(speakers_path):
            with open(speakers_path) as f:
                self.speaker_map = json.load(f)

    def sequence(self, text: str) -> np.ndarray:
        from speakingstyle_tpu.text.g2p import preprocess_text

        t = self.cfg.preprocess.preprocessing.text
        seq = preprocess_text(
            text, t.language, self.lexicon_path, list(t.text_cleaners)
        )
        return np.asarray(seq, np.int32)

    def speaker(self, spec) -> int:
        """Registry-validated speaker resolution: names must exist in
        speakers.json; numeric ids must fall inside the registry when
        one is loaded (an unknown id would silently index a random
        embedding row — the multi-speaker API validates instead)."""
        if isinstance(spec, int):
            idx = spec
        else:
            s = str(spec)
            if s in self.speaker_map:
                return self.speaker_map[s]
            if not s.lstrip("-").isdigit():
                raise ValueError(f"unknown speaker {spec!r}")
            idx = int(s)
        if self.speaker_map and not (
            0 <= idx < max(len(self.speaker_map),
                           max(self.speaker_map.values()) + 1)
        ):
            raise ValueError(
                f"speaker id {idx} outside the registry "
                f"(0..{len(self.speaker_map) - 1})"
            )
        return idx

    def resolve_style(self, payload: Dict):
        """(style_vectors | None, ref_mel | None, degraded) for one
        request payload — exactly one of the first two is non-None.

        Graceful degradation: when the style *encoder* fails (a device
        error, not a client mistake — ValueError still means 400), the
        request proceeds on the default style (all-zero FiLM) with
        ``degraded=True``, which the HTTP layer surfaces as
        ``X-Style-Degraded: 1`` instead of failing the synthesis."""
        if not self.cfg.model.use_reference_encoder:
            return None, None, False  # no FiLM conditioning in this model
        style_id = payload.get("style_id")
        ref_audio = payload.get("ref_audio")
        if style_id is not None and ref_audio is not None:
            raise ValueError('pass "style_id" OR "ref_audio", not both')
        if style_id is not None:
            if self.style is None:
                raise ValueError(
                    "style_id requires a style service (the model has no "
                    "reference encoder)"
                )
            # pure cache lookup — nothing to degrade; a miss stays 400
            entry = self.style.get(str(style_id))
            if entry is None:
                raise ValueError(
                    f"unknown style_id {style_id!r} (upload the reference "
                    "via POST /styles first)"
                )
            return entry, None, False
        if ref_audio is not None:
            path = confined_ref_path(self.cfg, str(ref_audio))
            if self.style is not None:
                with open(path, "rb") as f:
                    data = f.read()
                try:
                    return self.style.encode_wav_bytes(data), None, False
                except ValueError:
                    raise  # malformed reference: the client's problem
                except Exception as e:
                    self._style_encode_failed(e)
                    return self.style.fallback_style(), None, True
            return None, load_ref_mel(self.cfg, path), False
        if self.default_ref_mel is None:
            raise ValueError(
                'no reference style: pass "style_id" (POST /styles), '
                '"ref_audio" (a serve.style.ref_dir path), or start the '
                "server with --ref_audio"
            )
        if self.style is not None:
            try:
                return self.style.encode_mel(self.default_ref_mel), None, \
                    False
            except ValueError:
                raise
            except Exception as e:
                self._style_encode_failed(e)
                return self.style.fallback_style(), None, True
        return None, self.default_ref_mel, False

    def _style_encode_failed(self, e: BaseException) -> None:
        """Degradation is absorbed, never silent: the failure lands on
        the style service's registry (same counter the engine-side
        fallback uses) before the request proceeds on the default style."""
        self.style.registry.counter(
            "serve_style_encode_failures_total",
            labels={"error": type(e).__name__},
            help="reference-encoder dispatch failures absorbed by "
                 "the default-style fallback",
        ).inc()

    def controls_and_sequence(self, text: str, payload: Dict):
        """(sequence, p/e/d controls) for one request. Scalar controls
        ride the plain G2P path; a per-WORD list (the notebooks'
        fine-control workflow, e.g. ``"duration_control": [1.0, 2.5,
        1.0]``) needs word→phoneme spans, so English text goes through
        the span-preserving G2P and each list expands to a per-phoneme
        array the engine pads to the dispatch bucket."""
        keys = ("pitch_control", "energy_control", "duration_control")
        raw = {}
        for key in keys:
            v = payload.get(key, 1.0)
            if isinstance(v, bool) or not (
                isinstance(v, (int, float))
                or (isinstance(v, list)
                    and v and all(isinstance(x, (int, float)) for x in v))
            ):
                raise ValueError(
                    f"{key} must be a number or a per-word list of numbers"
                )
            raw[key] = v
        if not any(isinstance(v, list) for v in raw.values()):
            return self.sequence(text), [float(raw[k]) for k in keys]
        if self.cfg.preprocess.preprocessing.text.language != "en":
            raise ValueError(
                "per-word control lists require English text (word spans "
                "come from the English G2P)"
            )
        from speakingstyle_tpu.control import (
            english_word_spans,
            expand_word_controls,
            spans_to_sequence,
        )
        from speakingstyle_tpu.text.g2p import read_lexicon

        if self._lexicon is None:
            self._lexicon = (
                read_lexicon(self.lexicon_path) if self.lexicon_path else {}
            )
        spans = english_word_spans(text, self._lexicon)
        sequence = spans_to_sequence(
            spans, self.cfg.preprocess.preprocessing.text.text_cleaners
        )
        controls = []
        for key in keys:
            v = raw[key]
            if isinstance(v, list):
                if len(v) != len(spans):
                    raise ValueError(
                        f"{key} lists one factor per word: got {len(v)} "
                        f"factors for {len(spans)} words"
                    )
                controls.append(np.asarray(
                    expand_word_controls(spans, [float(x) for x in v]),
                    np.float32,
                ))
            else:
                controls.append(float(v))
        return sequence, controls

    def request(self, req_id: str, payload: Dict) -> SynthesisRequest:
        text = payload.get("text")
        if not text or not isinstance(text, str):
            raise ValueError('payload must carry a non-empty "text" string')

        priority = payload.get("priority")
        if priority is not None and not isinstance(priority, str):
            raise ValueError("priority must be a string class name")
        style_vec, ref_mel, degraded = self.resolve_style(payload)
        spec = payload.get("speaker_id", payload.get("speaker"))
        speaker = self.speaker(spec) if spec is not None else 0
        # per-speaker style validation: a style bound to a registry
        # speaker (POST /styles?speaker=NAME) refuses to drive a
        # different explicit speaker — mixing them is almost always a
        # client bug in a multi-speaker deployment
        if style_vec is not None and style_vec.speaker is not None:
            bound = self.speaker(style_vec.speaker)
            if spec is None:
                speaker = bound
            elif speaker != bound:
                raise ValueError(
                    f"style {style_vec.key[:12]}... is bound to speaker "
                    f"{style_vec.speaker!r}; request named a different "
                    "speaker"
                )
        sequence, (p_c, e_c, d_c) = self.controls_and_sequence(text, payload)
        return SynthesisRequest(
            id=req_id,
            sequence=sequence,
            ref_mel=ref_mel,
            style=style_vec,
            speaker=speaker,
            raw_text=text,
            p_control=p_c,
            e_control=e_c,
            d_control=d_c,
            priority=priority,
            style_degraded=degraded,
        )


def confined_ref_path(cfg: Config, path: str) -> str:
    """Resolve a request-supplied server-side reference path inside the
    ``serve.style.ref_dir`` allowlist. Absolute paths, ``..`` segments,
    and symlink escapes are rejected (ValueError -> HTTP 400); with no
    ref_dir configured, path-based references are disabled entirely —
    uploads go through POST /styles."""
    ref_dir = cfg.serve.style.ref_dir
    if not ref_dir:
        raise ValueError(
            'server-side "ref_audio" paths are disabled (serve.style.'
            "ref_dir is unset): upload the reference via POST /styles"
        )
    norm = path.replace("\\", "/")
    if os.path.isabs(path) or ".." in norm.split("/"):
        raise ValueError(
            f"ref_audio path {path!r} escapes the reference directory"
        )
    base = os.path.realpath(ref_dir)
    full = os.path.realpath(os.path.join(base, path))
    if os.path.commonpath([base, full]) != base:
        raise ValueError(
            f"ref_audio path {path!r} escapes the reference directory"
        )
    if not os.path.isfile(full):
        raise ValueError(f"ref_audio path {path!r} does not exist")
    return full


def load_ref_mel(cfg: Config, wav_path: str) -> np.ndarray:
    """Reference wav -> [T, n_mels] normalized log-mel (CLI single-mode
    pipeline, shared with cli/synthesize.py). Trusted-path helper: the
    HTTP layer never calls this with request-supplied paths except
    through ``confined_ref_path``."""
    from speakingstyle_tpu.audio.tools import load_wav
    from speakingstyle_tpu.serving.style import mel_from_wav_array

    pp = cfg.preprocess.preprocessing
    wav, _ = load_wav(wav_path, target_sr=pp.audio.sampling_rate)
    return mel_from_wav_array(cfg, wav)


class SynthesisServer:
    """Bind a dispatch backend + frontend behind an HTTP socket.

    Two backends share one server: the single-engine continuous batcher
    (pass ``engine``) and the multi-replica fleet router (pass
    ``router``; ``engine`` may be None — replicas are built by the
    router's warm-up threads). Both expose ``submit(request) -> Future``
    and ``close()``.
    """

    def __init__(
        self,
        engine: Optional[SynthesisEngine] = None,
        frontend: Optional[TextFrontend] = None,
        host: Optional[str] = None,
        port: Optional[int] = None,
        request_timeout: float = 60.0,
        events: Optional[JsonlEventLog] = None,
        profile_dir: Optional[str] = None,
        router=None,
        lifecycle=None,  # RolloutManager: gates POST /admin/rollout
        model_info: Optional[Dict] = None,  # single-engine identity
        # (fleet mode reads the router's set_model_version state instead)
        longform=None,  # LongformService; auto-built when a frontend exists
        slo=None,  # obs.slo.SloEngine; /healthz grows a burn-rate block
        probes=None,  # serving/probes.GoldenProber; /healthz probe block
    ):
        if engine is None and router is None:
            raise ValueError("SynthesisServer needs an engine or a router")
        self.engine = engine
        self.router = router
        self.lifecycle = lifecycle
        self.slo = slo
        self.probes = probes
        self._model_info = model_info
        self.cfg: Config = router.cfg if router is not None else engine.cfg
        serve = self.cfg.serve
        self.frontend = frontend
        self.registry = (
            router.registry if router is not None else engine.registry
        )
        # ONE style service serves the whole deployment: the router's
        # shared instance in fleet mode, the engine's otherwise. The
        # frontend resolves styles through it (cache-first in the handler
        # thread), and /styles reads+registers against it.
        self.style = (
            router.style if router is not None else engine.style
        )
        if frontend is not None and getattr(frontend, "style", None) is None:
            frontend.style = self.style
        self.events = events
        # the HTTP boundary's own validator gate (obs/quality.py): the
        # engine choke points already validated every wav on the way up;
        # this one turns a failed verdict into a structured 500 with an
        # X-Audio-Quality header instead of shipping the bytes
        from speakingstyle_tpu.obs.quality import QualityGate

        self.quality_gate = QualityGate(
            getattr(serve, "quality", None),
            self.cfg.preprocess.preprocessing.audio.sampling_rate,
            registry=self.registry, events=events,
        )
        if router is not None:
            self.batcher = None
            self.backend = router
        else:
            self.batcher = ContinuousBatcher(engine, events=events)
            self.backend = self.batcher
        self.request_timeout = request_timeout
        # long-form chapters (POST /synthesize/longform): the chunked
        # tier needs only the frontend + backend already in hand, so the
        # service is built by default; a ring tier rides in only when the
        # caller wires one explicitly (cli/serve.py) via the
        # ``longform`` ctor arg — it needs its own seq-mesh programs
        if longform is None and frontend is not None:
            from speakingstyle_tpu.serving.longform import LongformService

            longform = LongformService(
                self.cfg, frontend, self.backend,
                engine=engine,
                fault_plan=getattr(
                    engine if engine is not None else router,
                    "fault_plan", None,
                ),
                registry=self.registry, events=events,
                quality=self.quality_gate,
            )
        self.longform = longform
        # frontend overlap (serving/frontend.py): with workers > 0 the
        # handler submits a PendingRequest and the G2P runs on the pool,
        # hidden under the backend's coalescing wait; 0 = inline frontend
        # on the handler thread (the pre-pipeline behavior)
        self.frontend_pool = (
            FrontendPool(
                frontend, serve.frontend_workers,
                registry=self.registry, events=events,
            )
            if frontend is not None and serve.frontend_workers > 0
            else None
        )
        self.started = time.monotonic()
        self.profile_dir = profile_dir or os.path.join(
            self.cfg.train.path.log_path, "serve_profile"
        )
        # in-flight chunked streams, drained before shutdown completes
        self._streams_cond = make_lock("SynthesisServer._streams_cond", kind="condition")
        self._active_streams = 0
        self._streams_gauge = self.registry.gauge(
            "serve_active_streams", help="chunked streams currently emitting"
        )
        self._ttfa_hist = self.registry.histogram(
            "serve_ttfa_seconds",
            help="request arrival -> first streamed wav chunk ready",
        )
        self._stream_overlap: Optional[int] = None
        self._shutdown_lock = make_lock("SynthesisServer._shutdown_lock")
        self._shut_down = False
        self._profile_lock = make_lock("SynthesisServer._profile_lock")  # one capture at a time
        # the request-id sequence IS the request counter: Counter.inc()
        # returns the post-increment value under the metric's own lock,
        # so there is no separate _req_counter to keep in sync
        self._requests = self.registry.counter(
            "serve_http_requests_total", help="synthesize requests admitted"
        )
        self._http_errors = self.registry.counter(
            "serve_http_errors_total", help="synthesize requests failed"
        )
        # build identity is computed once (git SHA + jax versions don't
        # change under a live server) and rides every /healthz payload
        self.build = build_info()
        self._rss_gauge = self.registry.gauge(
            "process_rss_bytes", help="resident set size of this process"
        )
        self._uptime_gauge = self.registry.gauge(
            "process_uptime_seconds", help="seconds since server start"
        )
        outer = self

        class Handler(BaseHTTPRequestHandler):
            # chunked transfer encoding (the /synthesize/stream response)
            # requires HTTP/1.1; every other response sets Content-Length,
            # so persistent connections stay correct
            protocol_version = "HTTP/1.1"

            # quiet the default per-request stderr line
            def log_message(self, fmt, *args):
                pass

            def _json(self, code: int, obj: Dict, req_id: Optional[str] = None,
                      headers: Optional[Dict[str, str]] = None,
                      trace_id: Optional[str] = None):
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                if req_id is not None:
                    self.send_header("X-Request-Id", req_id)
                if trace_id is not None:
                    # every error verdict joins its trace: grep the span
                    # ring / event log by this id
                    self.send_header("X-Trace-Id", trace_id)
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _text(self, code: int, text: str, content_type: str):
                body = text.encode()
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/healthz":
                    # readiness semantics: 503 until some replica finished
                    # its precompile, so load balancers never route into a
                    # compile storm — the body still carries the
                    # per-replica lifecycle states for the operator
                    return self._json(
                        200 if outer.is_ready() else 503, outer.stats()
                    )
                if self.path == "/metrics":
                    if outer.batcher is not None:
                        outer.batcher.refresh_gauges()
                    outer.refresh_process_gauges()
                    # cluster mode appends the fleet_* federation: every
                    # live replica's counters summed and histogram
                    # buckets MERGED (fleet p999 comes from merged
                    # buckets, never from averaged percentiles)
                    return self._text(
                        200,
                        outer.registry.prometheus_text()
                        + outer.federated_text(),
                        "text/plain; version=0.0.4; charset=utf-8",
                    )
                if self.path == "/debug/programs":
                    return self._json(200, {
                        "programs": outer.programs(),
                        "build": outer.build,
                    })
                if self.path.split("?")[0] == "/debug/spans":
                    ring = get_span_ring()
                    return self._json(200, {
                        "spans": ring.spans(),
                        "kept": {tid: ring.spans(tid)
                                 for tid in ring.kept_trace_ids()},
                        "stats": ring.stats(),
                    })
                if self.path.startswith("/debug/trace/"):
                    tid = self.path[len("/debug/trace/"):].split("?")[0]
                    if not tid:
                        return self._json(400, {
                            "error": "GET /debug/trace/<trace_id>"
                        })
                    return self._json(200, outer.trace_view(tid))
                if self.path == "/styles":
                    if outer.style is None:
                        return self._json(400, {
                            "error": "no style service (the model has no "
                                     "reference encoder)"
                        })
                    return self._json(200, {
                        "styles": outer.style.styles(),
                        "capacity": outer.style.cfg.serve.style.cache_capacity,
                    })
                return self._json(404, {"error": f"no route {self.path}"})

            def do_POST(self):
                parsed = urlparse(self.path)
                if parsed.path == "/debug/profile":
                    return self._profile(parsed)
                if parsed.path == "/admin/rollout":
                    return self._rollout()
                if parsed.path == "/styles":
                    return self._post_style(parsed)
                if parsed.path == "/synthesize/longform":
                    return self._synthesize_longform(parsed)
                if parsed.path == "/synthesize/stream":
                    return self._synthesize(parsed, stream=True)
                if parsed.path == "/synthesize":
                    return self._synthesize(parsed, stream=False)
                return self._json(404, {"error": f"no route {self.path}"})

            def _rollout(self):
                """POST /admin/rollout {"step": N}: verify checkpoint N,
                canary one replica on it, and roll the fleet — the
                RolloutManager owns the whole state machine; this
                handler only validates the request and maps outcomes
                (409 on a concurrent rollout; both committed and
                aborted are 200s carrying the outcome dict)."""
                from speakingstyle_tpu.serving.lifecycle import (
                    RolloutInProgress,
                )

                if outer.lifecycle is None:
                    return self._json(404, {
                        "error": "rollout is not enabled on this server "
                                 "(start with --enable_rollout and a fleet)"
                    })
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    payload = json.loads(self.rfile.read(n) or b"{}")
                except ValueError:
                    return self._json(400, {"error": "body must be JSON"})
                step = payload.get("step") if isinstance(payload, dict) \
                    else None
                if not isinstance(step, int) or isinstance(step, bool):
                    return self._json(400, {
                        "error": 'rollout needs an integer "step" '
                                 "(the checkpoint to roll to)"
                    })
                try:
                    result = outer.lifecycle.rollout(step)
                except RolloutInProgress as e:
                    return self._json(409, {"error": str(e)})
                return self._json(200, result)

            def _post_style(self, parsed):
                """Register a reference style: raw wav bytes in the body
                (audio/wav), or JSON {"ref_audio": <confined path>}.
                Content-addressed: the style_id IS the sha256 of the
                reference bytes, so the operation is idempotent and a
                repeat upload performs zero encoder work."""
                if outer.style is None:
                    return self._json(400, {
                        "error": "no style service (the model has no "
                                 "reference encoder)"
                    })
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    body = self.rfile.read(n) if n else b""
                    ctype = (self.headers.get("Content-Type") or "").lower()
                    speaker = None
                    q = parse_qs(parsed.query)
                    if "speaker" in q:
                        speaker = q["speaker"][0]
                    if ctype.startswith("application/json"):
                        payload = json.loads(body or b"{}")
                        speaker = payload.get("speaker", speaker)
                        ref = payload.get("ref_audio")
                        if not ref:
                            raise ValueError(
                                'JSON style registration needs "ref_audio" '
                                "(a serve.style.ref_dir path); raw wav "
                                "uploads go in an audio/wav body"
                            )
                        # the frontend's cfg carries serve.style.ref_dir
                        # (same source resolve_style confines against)
                        ref_cfg = (
                            outer.frontend.cfg
                            if outer.frontend is not None else outer.cfg
                        )
                        with open(confined_ref_path(
                            ref_cfg, str(ref)
                        ), "rb") as f:
                            body = f.read()
                    elif not body:
                        raise ValueError(
                            "empty body: POST the reference wav bytes "
                            '(audio/wav) or JSON {"ref_audio": ...}'
                        )
                    if speaker is not None and outer.frontend is not None:
                        outer.frontend.speaker(speaker)  # registry check
                    key = outer.style.digest_bytes(body)
                    entry = outer.style.get(key)
                    cached = entry is not None
                    if entry is None:
                        entry = outer.style.encode_wav_bytes(
                            body, speaker=speaker
                        )
                except (ValueError, RequestTooLarge) as e:
                    return self._json(400, {"error": str(e)})
                out = dict(entry.as_dict(), cached=cached)
                return self._json(200, out)

            def _synthesize(self, parsed, stream: bool):
                # the req_id is minted HERE and rides through frontend ->
                # batcher/router -> engine as SynthesisRequest.id, so one
                # request's http_request/serve_dispatch records (and the
                # X-Request-Id the client sees, errors included) all join
                req_id = outer.next_req_id()
                # the trace joins on req_id unless an upstream proxy
                # already opened a trace and forwarded its id
                trace_id = self.headers.get("X-Trace-Id") or req_id
                t0 = time.monotonic()
                status, err, headers = 200, None, None
                extra_body = None
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    payload = json.loads(self.rfile.read(n) or b"{}")
                    if stream and not outer.streaming_available():
                        raise ValueError(
                            "streaming requires a vocoder engine "
                            "(--griffin_lim serves mel JSON only)"
                        )
                    result = outer.synthesize(
                        payload, req_id=req_id, stream=stream,
                        trace_id=trace_id,
                    )
                except RequestTooLarge as e:
                    # structured 413: the body states the admissible
                    # ceiling and points at the long-form endpoint, so a
                    # client can route the chapter instead of guessing
                    # at the limit (RequestTooLarge IS a ValueError —
                    # this arm must come first)
                    status, err = 413, str(e)
                    extra_body = outer.too_large_body()
                except ValueError as e:
                    status, err = 400, str(e)
                except Overloaded as e:
                    # backpressure shed: NOT the shutdown path — carries
                    # the retry hint so well-behaved clients back off
                    status, err = 429, str(e)
                    headers = {
                        "Retry-After": str(max(1, int(e.retry_after_s)))
                    }
                except ShutdownError as e:
                    status, err = 503, str(e)
                except DeadlineExceeded as e:
                    # the router refused to dispatch past the class
                    # deadline budget — same verdict as a result timeout
                    status, err = 504, str(e)
                except ReplicaError as e:
                    # replica failed and the retry budget is spent: the
                    # request may succeed on a retry once the fleet
                    # re-warms — a 503, not a client error
                    status, err = 503, str(e)
                except DispatchError as e:
                    status, err = 500, str(e)
                # concurrent.futures.TimeoutError only aliases the builtin
                # from 3.11; catch both on 3.10
                except (TimeoutError, concurrent.futures.TimeoutError):
                    status, err = 504, "synthesis timed out"
                if err is not None:
                    outer._request_done(req_id, parsed.path, status, t0,
                                        trace_id=trace_id)
                    body = {"error": err, "id": req_id}
                    if extra_body:
                        body.update(extra_body)
                    return self._json(status, body, req_id=req_id,
                                      headers=headers, trace_id=trace_id)
                if stream:
                    return self._stream_response(result, req_id, parsed, t0,
                                                 trace_id=trace_id)
                extra_hdr = {}
                if result.style_degraded:
                    extra_hdr["X-Style-Degraded"] = "1"
                version = outer.model_version()
                if version is not None:
                    extra_hdr["X-Model-Version"] = version
                tier = outer.model_tier(result)
                if tier is not None:
                    extra_hdr["X-Model-Tier"] = tier
                # cluster mode: which replica process actually served
                # this — joins the req_id trail in the JSONL events
                served_by = getattr(result, "served_by", None)
                if served_by:
                    extra_hdr["X-Served-By"] = served_by
                if result.wav is None:
                    # vocoder-less engine: return the mel as JSON
                    outer._request_done(req_id, parsed.path, 200, t0,
                                        served_by=served_by,
                                        trace_id=trace_id)
                    return self._json(200, {
                        "id": result.id,
                        "mel_len": result.mel_len,
                        "mel": result.mel.tolist(),
                    }, req_id=req_id, headers=extra_hdr or None,
                        trace_id=trace_id)
                # the last gate before bytes leave the process: the
                # engine's attached verdict (or a fresh check when the
                # backend predates the choke point) — a failed wav is a
                # structured 500, never an audio/wav body
                verdict = outer.quality_gate.check_result(result)
                if verdict is not None and not verdict.ok:
                    reasons = ",".join(verdict.reasons)
                    outer._request_done(req_id, parsed.path, 500, t0,
                                        served_by=served_by,
                                        trace_id=trace_id)
                    return self._json(500, {
                        "error": "audio quality check failed",
                        "id": req_id,
                        "reasons": list(verdict.reasons),
                    }, req_id=req_id,
                        headers={"X-Audio-Quality": f"fail:{reasons}"},
                        trace_id=trace_id)
                sr = outer.cfg.preprocess.preprocessing.audio.sampling_rate
                body = wav_bytes(result.wav, sr)
                outer._request_done(req_id, parsed.path, 200, t0,
                                    served_by=served_by, trace_id=trace_id)
                self.send_response(200)
                self.send_header("Content-Type", "audio/wav")
                self.send_header("Content-Length", str(len(body)))
                self.send_header("X-Request-Id", result.id)
                self.send_header("X-Trace-Id", trace_id)
                self.send_header("X-Batch-Rows", str(result.batch_rows))
                if result.style_degraded:
                    self.send_header("X-Style-Degraded", "1")
                if version is not None:
                    self.send_header("X-Model-Version", version)
                if tier is not None:
                    self.send_header("X-Model-Tier", tier)
                if served_by:
                    self.send_header("X-Served-By", served_by)
                self.end_headers()
                self.wfile.write(body)

            def _stream_response(self, result, req_id, parsed, t0,
                                 trace_id=None):
                """Chunked audio/wav: streaming RIFF header, then PCM in
                overlap-trimmed windows as each is vocoded.

                The FIRST window is pulled and re-validated before any
                header goes on the wire (the long-form handler's idiom),
                so a stream whose very first chunk fails the quality
                gate is a clean JSON 500 with ``X-Audio-Quality``
                instead of a committed audio/wav response."""
                sr = outer.cfg.preprocess.preprocessing.audio.sampling_rate
                chunks = outer.stream_chunks(result, arrival=t0)
                try:
                    first = next(chunks, None)
                except Exception as e:
                    outer._request_done(req_id, parsed.path, 500, t0,
                                        trace_id=trace_id)
                    return self._json(500, {"error": str(e), "id": req_id},
                                      req_id=req_id, trace_id=trace_id)
                if first is not None:
                    # record=False: the vocode_collect choke point
                    # already counted this window — this check only
                    # decides the response shape
                    verdict = outer.quality_gate.check(
                        first, klass=getattr(result, "priority", None),
                        source="server", record=False,
                    )
                    if not verdict.ok:
                        reasons = ",".join(verdict.reasons)
                        outer._request_done(req_id, parsed.path, 500, t0,
                                            trace_id=trace_id)
                        return self._json(500, {
                            "error": "audio quality check failed",
                            "id": req_id,
                            "reasons": list(verdict.reasons),
                        }, req_id=req_id,
                            headers={"X-Audio-Quality": f"fail:{reasons}"},
                            trace_id=trace_id)

                def write_chunk(data: bytes):
                    self.wfile.write(b"%X\r\n" % len(data))
                    self.wfile.write(data)
                    self.wfile.write(b"\r\n")

                self.send_response(200)
                self.send_header("Content-Type", "audio/wav")
                self.send_header("Transfer-Encoding", "chunked")
                self.send_header("X-Request-Id", result.id)
                if trace_id is not None:
                    self.send_header("X-Trace-Id", trace_id)
                self.send_header("X-Batch-Rows", str(result.batch_rows))
                if result.style_degraded:
                    self.send_header("X-Style-Degraded", "1")
                version = outer.model_version()
                if version is not None:
                    self.send_header("X-Model-Version", version)
                tier = outer.model_tier(result)
                if tier is not None:
                    self.send_header("X-Model-Tier", tier)
                self.end_headers()
                try:
                    with outer.stream_scope():
                        write_chunk(wav_stream_header(sr))
                        if first is not None:
                            write_chunk(first.tobytes())
                        for wav in chunks:
                            write_chunk(wav.tobytes())
                    self.wfile.write(b"0\r\n\r\n")
                except (BrokenPipeError, ConnectionResetError):
                    # client hung up mid-stream: stop vocoding for them
                    self.close_connection = True
                    outer._request_done(req_id, parsed.path, 499, t0,
                                        trace_id=trace_id)
                    return
                except Exception as e:
                    # headers are gone — the only honest signal is a
                    # truncated chunked body (no terminal chunk)
                    self.close_connection = True
                    outer._request_done(req_id, parsed.path, 500, t0,
                                        trace_id=trace_id)
                    if outer.events is not None:
                        outer.events.emit(
                            "stream_abort", req_id=req_id,
                            error=type(e).__name__,
                        )
                    return
                outer._request_done(req_id, parsed.path, 200, t0,
                                    trace_id=trace_id)

            def _synthesize_longform(self, parsed):
                """POST /synthesize/longform: chapter in, one chunked
                audio/wav stream out.  The FIRST stitched piece is
                pulled before any header goes on the wire, so admission
                errors AND a ring-tier failure that degrades to the
                chunked tier are both reflected honestly (clean JSON
                error / an ``X-Longform-Tier`` header naming the tier
                that actually produced the audio)."""
                req_id = outer.next_req_id()
                trace_id = self.headers.get("X-Trace-Id") or req_id
                t0 = time.monotonic()
                status, err, headers, extra_body = 200, None, None, None
                try:
                    if outer.longform is None:
                        raise ValueError(
                            "long-form synthesis needs a text frontend"
                        )
                    if not outer.streaming_available():
                        raise ValueError(
                            "long-form synthesis requires a vocoder "
                            "engine (--griffin_lim serves mel JSON only)"
                        )
                    n = int(self.headers.get("Content-Length", 0))
                    payload = json.loads(self.rfile.read(n) or b"{}")
                    plan = outer.longform.admit(req_id, payload)
                    pieces = outer.longform.stream(plan)
                    first = next(pieces, None)
                    if first is not None:
                        # record=False: the Stitcher's choke point
                        # already counted this piece — this re-check
                        # only keeps a bad chapter off the wire
                        verdict = outer.quality_gate.check(
                            first, source="server", record=False,
                        )
                        if not verdict.ok:
                            reasons = ",".join(verdict.reasons)
                            status = 500
                            err = "audio quality check failed: " + reasons
                            headers = {"X-Audio-Quality": f"fail:{reasons}"}
                except RequestTooLarge as e:
                    # past even the long-form admission cap
                    status, err = 413, str(e)
                    extra_body = outer.too_large_body()
                    extra_body["max_chunks"] = \
                        outer.cfg.serve.longform.max_chunks
                except ValueError as e:
                    status, err = 400, str(e)
                except Overloaded as e:
                    status, err = 429, str(e)
                    headers = {
                        "Retry-After": str(max(1, int(e.retry_after_s)))
                    }
                except ShutdownError as e:
                    status, err = 503, str(e)
                except DeadlineExceeded as e:
                    status, err = 504, str(e)
                except ReplicaError as e:
                    status, err = 503, str(e)
                except DispatchError as e:
                    status, err = 500, str(e)
                except (TimeoutError, concurrent.futures.TimeoutError):
                    status, err = 504, "long-form synthesis timed out"
                if err is not None:
                    outer._request_done(req_id, parsed.path, status, t0,
                                        trace_id=trace_id)
                    body = {"error": err, "id": req_id}
                    if extra_body:
                        body.update(extra_body)
                    return self._json(status, body, req_id=req_id,
                                      headers=headers, trace_id=trace_id)
                sr = outer.cfg.preprocess.preprocessing.audio.sampling_rate

                def write_chunk(data: bytes):
                    self.wfile.write(b"%X\r\n" % len(data))
                    self.wfile.write(data)
                    self.wfile.write(b"\r\n")

                self.send_response(200)
                self.send_header("Content-Type", "audio/wav")
                self.send_header("Transfer-Encoding", "chunked")
                self.send_header("X-Request-Id", req_id)
                # the tier that is actually producing audio — a ring
                # failure degraded the plan before headers went out
                self.send_header("X-Longform-Tier", plan.tier)
                self.send_header("X-Longform-Chunks",
                                 str(len(plan.chunks)))
                if plan.style_degraded:
                    self.send_header("X-Style-Degraded", "1")
                version = outer.model_version()
                if version is not None:
                    self.send_header("X-Model-Version", version)
                tier = outer.model_tier()
                if tier is not None:
                    self.send_header("X-Model-Tier", tier)
                self.end_headers()
                try:
                    with outer.stream_scope():
                        write_chunk(wav_stream_header(sr))
                        if first is not None:
                            write_chunk(first.tobytes())
                        for wav in pieces:
                            write_chunk(wav.tobytes())
                    self.wfile.write(b"0\r\n\r\n")
                except (BrokenPipeError, ConnectionResetError):
                    self.close_connection = True
                    outer._request_done(req_id, parsed.path, 499, t0,
                                        trace_id=trace_id)
                    return
                except Exception as e:
                    # headers are gone — the only honest signal is a
                    # truncated chunked body (no terminal chunk)
                    self.close_connection = True
                    outer._request_done(req_id, parsed.path, 500, t0,
                                        trace_id=trace_id)
                    if outer.events is not None:
                        outer.events.emit(
                            "stream_abort", req_id=req_id,
                            error=type(e).__name__,
                        )
                    return
                outer._request_done(req_id, parsed.path, 200, t0,
                                    trace_id=trace_id)

            def _profile(self, parsed):
                if not outer.cfg.serve.debug_profile:
                    return self._json(
                        403, {"error": "serve.debug_profile is disabled"}
                    )
                raw = parse_qs(parsed.query).get("seconds", ["3"])[0]
                try:
                    seconds = float(raw)
                except ValueError:
                    return self._json(
                        400, {"error": f"seconds={raw!r} is not a number"}
                    )
                if not 0 < seconds <= 60:
                    return self._json(
                        400, {"error": "seconds must be in (0, 60]"}
                    )
                # fan-out FIRST (the replica captures run off-thread),
                # so the fleet's windows overlap the local one
                fanout = outer.profile_fanout(seconds)
                ok, out = outer.capture_profile(seconds)
                if fanout is not None:
                    out["replicas"] = fanout
                return self._json(200 if ok else 409, out)

        self.httpd = ThreadingHTTPServer(
            (host if host is not None else serve.host,
             port if port is not None else serve.port),
            Handler,
        )
        self.httpd.daemon_threads = True

    # -- request path (also used directly by tests) -------------------------

    def next_req_id(self) -> str:
        return f"req{int(self._requests.inc()):08d}"

    def too_large_body(self) -> Dict:
        """The structured 413 payload: the interactive lattice's
        admissible ceiling per axis plus the endpoint that DOES take
        chapters, so an over-limit client can route instead of guess."""
        serve = self.cfg.serve
        return {
            "max_src": serve.src_buckets[-1],
            "max_mel": serve.mel_buckets[-1],
            "max_phonemes": min(
                serve.src_buckets[-1],
                serve.mel_buckets[-1] // serve.frames_per_phoneme,
            ),
            "longform": "/synthesize/longform",
        }

    def _result_timeout(self, request) -> float:
        """Wait on a submitted future no longer than the request's class
        deadline budget (+ grace) allows.  The router resolves expired
        work as DeadlineExceeded on its own; the grace window gives it
        room to do so before the handler falls back to a bare 504.
        Batcher deployments have no SLO classes — full timeout."""
        if self.router is None:
            return self.request_timeout
        fleet = self.cfg.serve.fleet
        klass = request.priority or fleet.default_class
        override = getattr(request, "deadline_ms", None)
        if override is not None:
            budget_ms = min(float(override), fleet.max_deadline_ms)
        else:
            budget_ms = fleet.class_deadline_ms.get(klass)
        if budget_ms is None:
            return self.request_timeout
        deadline = request.arrival + (budget_ms + fleet.deadline_grace_ms) / 1e3
        remaining = deadline - time.monotonic()
        return max(0.001, min(self.request_timeout, remaining))

    def synthesize(self, payload: Dict, req_id: Optional[str] = None,
                   stream: bool = False, trace_id: Optional[str] = None):
        if req_id is None:
            req_id = self.next_req_id()
        # the ROOT span of the distributed trace: trace_id defaults to
        # the req_id join key; every downstream stage (frontend, EDF
        # queue, hedge legs, replica engine, vocode windows) parents
        # under sp.ctx, which rides the request object
        with Span("serve_request", trace_id=trace_id or req_id,
                  req_id=req_id, stream=bool(stream)) as sp:
            if self.frontend_pool is not None:
                # pipelined path: admission sees a PendingRequest
                # stand-in (id/arrival/priority/stream are known
                # pre-G2P) while the frontend resolves on a pool worker
                # under the coalescing wait. prepare -> submit ->
                # dispatch ordering matters: a shed/shutdown refusal at
                # submit wastes no frontend work
                pending = self.frontend_pool.prepare(req_id, payload,
                                                     stream=stream)
                pending.trace = sp.ctx
                future = self.backend.submit(pending)
                self.frontend_pool.dispatch(pending)
                return future.result(
                    timeout=self._result_timeout(pending))
            request = self.frontend.request(req_id, payload)
            request.stream = stream   # mel-only; windows vocode after
            request.trace = sp.ctx
            future = self.backend.submit(request)
            return future.result(timeout=self._result_timeout(request))

    # -- streaming ----------------------------------------------------------

    def streaming_available(self) -> bool:
        """Chunked streaming needs a vocoder; a griffin_lim (mel-JSON)
        deployment has none."""
        if self.router is not None:
            engines = self.router.engines()
            return not engines or engines[0].vocoder is not None
        return self.engine.vocoder is not None

    @contextlib.contextmanager
    def stream_scope(self):
        """Tracks in-flight chunked streams so shutdown can drain them."""
        with self._streams_cond:
            self._active_streams += 1
            self._streams_gauge.set(self._active_streams)
        try:
            yield
        finally:
            with self._streams_cond:
                self._active_streams -= 1
                self._streams_gauge.set(self._active_streams)
                self._streams_cond.notify_all()

    def stream_chunks(self, result, arrival: Optional[float] = None):
        """Yield int16 wav chunk arrays for a dispatched result —
        windowed vocode over precompiled lattice buckets (zero compiles);
        observes serve_ttfa_seconds at the first chunk."""
        if self.router is not None:
            yield from self.router.stream(result, arrival=arrival)
            return
        engine = self.engine
        if engine.vocoder is None:
            raise ValueError("streaming requires a vocoder engine")
        if self._stream_overlap is None:
            self._stream_overlap = streaming.resolve_overlap(
                self.cfg.serve.fleet.stream_overlap, engine.vocoder[0]
            )
        first = True
        for chunk in streaming.stream_wav(
            engine, result, self.cfg.serve.fleet.stream_window,
            self._stream_overlap, depth=self.cfg.serve.fleet.stream_depth,
        ):
            if first and arrival is not None:
                self._ttfa_hist.observe(time.monotonic() - arrival)
            first = False
            yield chunk

    # -- readiness / introspection ------------------------------------------

    def is_ready(self) -> bool:
        """At least one replica (or the single engine) has its full
        lattice compiled — the /healthz readiness predicate."""
        if self.router is not None:
            return self.router.ready()
        return self.engine.is_ready

    def programs(self):
        """ProgramCard dicts across every live engine (fleet: replicas
        in index order), then the shared style-encoder programs once."""
        if self.router is not None:
            out = []
            for engine in self.router.engines():
                out.extend(engine.programs())
        else:
            out = list(self.engine.programs())
        if self.style is not None:
            out.extend(self.style.programs())
        return out

    def _request_done(
        self, req_id: str, path: str, status: int, t0: float,
        served_by: Optional[str] = None, trace_id: Optional[str] = None,
    ) -> None:
        dur = time.monotonic() - t0
        if status >= 400:
            self._http_errors.inc()
        self.registry.histogram(
            "serve_http_request_seconds",
            labels={"status": str(status)},
            help="HTTP handler wall time (parse + G2P + batcher wait)",
        ).observe(dur)
        if self.events is not None:
            fields = dict(req_id=req_id, path=path, status=status,
                          duration_s=dur)
            if served_by:
                # cluster mode: the replica process host joins the
                # req_id trail, so one grep follows a request from
                # admission to the host that served it
                fields["served_by"] = served_by
            if trace_id:
                fields["trace_id"] = trace_id
            self.events.emit("http_request", **fields)

    def model_info(self) -> Optional[Dict]:
        """{version, step, weights_digest} for the serving model, or
        None when no identity was ever published (tests constructing a
        bare server)."""
        if self.router is not None and self.router.model_version is not None:
            return {
                "version": self.router.model_version,
                "step": self.router.model_step,
                "weights_digest": self.router.model_digest,
            }
        return self._model_info

    def model_version(self) -> Optional[str]:
        info = self.model_info()
        return info.get("version") if info else None

    def model_tier(self, result=None) -> Optional[str]:
        """Which quality tier produced (or would produce) a response —
        the ``X-Model-Tier`` header. A result stamped by a TierRouter
        names its actual tier; otherwise the process's default tier:
        the TierRouter's fallback, or ``teacher-<precision>`` from the
        lattice's leading precision (same-bucket programs at different
        precisions are indistinguishable without this). A plain
        single-precision f32 process has nothing to disambiguate, so it
        gets None and its headers/healthz stay byte-identical to the
        pre-tier surface."""
        tier = getattr(result, "tier", None) if result is not None else None
        if tier:
            return tier
        if self.router is not None:
            if hasattr(self.router, "tier_for"):
                return self.router.tier_for(None)
            lattice = self.router.lattice
        elif self.engine is not None:
            lattice = self.engine.lattice
        else:
            return None
        precisions = tuple(getattr(lattice, "precisions", None) or ("f32",))
        if precisions == ("f32",):
            return None
        return f"teacher-{precisions[0]}"

    def trace_view(self, trace_id: str) -> Dict:
        """GET /debug/trace/<id>: assemble one trace across processes —
        the local span ring joined with every live replica's
        (best-effort), stitched into a tree with the critical path
        computed."""
        ring = get_span_ring()
        spans = {s["span_id"]: s for s in ring.spans(trace_id)
                 if s.get("span_id")}
        if self.router is not None \
                and hasattr(self.router, "fetch_remote_spans"):
            for s in self.router.fetch_remote_spans(trace_id):
                spans.setdefault(s.get("span_id"), s)
        return assemble_trace(list(spans.values()), trace_id)

    def federated_text(self) -> str:
        """The fleet_* Prometheus section (cluster mode only): the
        router's federation cache merged into one registry."""
        if self.router is None \
                or not hasattr(self.router, "federated_registry"):
            return ""
        try:
            return self.router.federated_registry().prometheus_text()
        except Exception as e:
            # a malformed scrape must never break /metrics — the local
            # section still renders, and the failure itself is a metric
            self.registry.counter(
                "serve_federation_render_errors_total",
                labels={"error": type(e).__name__},
                help="federated /metrics sections dropped by error type",
            ).inc()
            return ""

    def profile_fanout(self, seconds: float) -> Optional[Dict]:
        """Trigger jax.profiler captures on every live replica process
        (cluster mode); None when there is no fleet to fan out to."""
        if self.router is None \
                or not hasattr(self.router, "profile_fanout"):
            return None
        return self.router.profile_fanout(seconds)

    def refresh_process_gauges(self) -> None:
        """Sample process RSS + uptime into the registry (called at
        scrape so /metrics always exports a current value)."""
        rss = process_rss_bytes()
        if rss is not None:
            self._rss_gauge.set(rss)
        self._uptime_gauge.set(time.monotonic() - self.started)

    def stats(self) -> Dict:
        """The /healthz payload: a VIEW of ``registry.snapshot()``.

        The pre-obs version read ``_req_counter`` and batcher fields
        directly, without the locks the write side held; every number
        here now comes out of the registry (whose metrics carry their
        own locks), so there is no second bookkeeping path to drift.
        """
        if self.batcher is not None:
            self.batcher.refresh_gauges()
        self.refresh_process_gauges()
        snap = self.registry.snapshot()
        counters, gauges = snap["counters"], snap["gauges"]
        occupancy = {}
        for key, count in counters.items():
            if key.startswith("serve_batch_occupancy_total{"):
                rows = key.split('rows="', 1)[1].split('"', 1)[0]
                occupancy[rows] = int(count)
        out = {
            "ready": self.is_ready(),
            "uptime_s": round(time.monotonic() - self.started, 1),
            "build": self.build,
            "lattice_points": (
                len(self.engine.lattice) if self.engine is not None
                else len(self.router.lattice)
            ),
            "compile_count": int(counters.get("serve_compiles_total", 0)),
            "backend_compiles": int(
                counters.get("jax_backend_compiles_total", 0)
            ),
            "dispatches": int(counters.get("serve_dispatches_total", 0)),
            "queue_depth": int(gauges.get("serve_queue_depth", 0)),
            "batch_occupancy": dict(sorted(occupancy.items())),
            "requests": int(counters.get("serve_http_requests_total", 0)),
            "errors": int(counters.get("serve_http_errors_total", 0)),
            # the shed/reject split: backpressure 429s vs shutdown 503s
            # are different verdicts and must never share a counter
            "shed": int(counters.get("serve_shed_total", 0)),
            "rejected": int(counters.get("serve_rejected_total", 0)),
            "active_streams": int(gauges.get("serve_active_streams", 0)),
            # the style path's accounting: cached-style requests must
            # show up as hits with the encode counter standing still
            "style": {
                "entries": int(gauges.get("serve_style_cache_entries", 0)),
                "hits": int(counters.get("serve_style_cache_hits_total", 0)),
                "misses": int(
                    counters.get("serve_style_cache_misses_total", 0)
                ),
                "evictions": int(
                    counters.get("serve_style_cache_evictions_total", 0)
                ),
                "compiles": int(
                    counters.get("serve_style_compiles_total", 0)
                ),
                "encodes": int(
                    counters.get("serve_style_dispatches_total", 0)
                ),
            },
        }
        if self.router is not None:
            out["replicas"] = {
                str(i): s for i, s in sorted(self.router.states().items())
            }
            # cluster mode: the remote control plane's view — one row
            # per lease (host, age, last heartbeat, partition flag).
            # ready() above is already quorum-gated, so /healthz answers
            # 503 until at least cluster.quorum replicas hold leases
            if hasattr(self.router, "cluster_stats"):
                out["cluster"] = {
                    "quorum": self.router.ccfg.quorum,
                    "control_addr": self.router.control_addr,
                    "replicas": self.router.cluster_stats(),
                }
        # which WEIGHTS is this process serving: version string +
        # checkpoint step + digest (fleet mode tracks rollouts live via
        # router.set_model_version; single-engine mode is pinned at
        # startup by cli/serve.py)
        model = self.model_info()
        if model:
            out["model"] = dict(model)
            # same-bucket programs at different precisions serve under
            # one version string — the tier disambiguates which quality
            # level this process answers with by default
            tier = self.model_tier()
            if tier is not None:
                out["model"]["tier"] = tier
        # tiered routing (serving/tiers.py): the effective class->tier
        # map with gate fallbacks applied, plus each gated tier's
        # golden-set verdict — the canary-as-quality-door paper trail
        if self.router is not None and hasattr(self.router, "routing_table"):
            out["tiers"] = {
                "default": self.router.default_tier,
                "routing": self.router.routing_table(),
                "gates": {
                    name: (g.as_dict() if (g := self.router.gate_result(name))
                           is not None else {"shipped": True,
                                             "detail": "ungated anchor"})
                    for name in self.router.tiers()
                },
            }
        # SLO burn-rate block (obs/slo.py): per-class fast/slow window
        # burn rates + whether the multi-window alert is firing
        if self.slo is not None:
            out["slo"] = self.slo.status()
        # the audio-quality plane: validator tallies + the last failure
        # in this process, probe freshness/drift when a GoldenProber is
        # wired, and the quality SLO stream's burn view
        quality: Dict = {"validators": dict(self.quality_gate.status())}
        last = quality_last_fail()
        if last is not None:
            quality["last_fail"] = last
        if self.probes is not None:
            quality["probes"] = self.probes.status()
        if self.slo is not None and hasattr(self.slo, "quality_status"):
            quality["slo"] = self.slo.quality_status()
        out["quality"] = quality
        # present only when an Autoscaler is driving scale_to(): the
        # policy's last target plus its decision tally by reason
        if "serve_autoscale_target" in gauges:
            decisions = {}
            for key, count in counters.items():
                if key.startswith("serve_autoscale_decisions_total{"):
                    reason = key.split('reason="', 1)[1].split('"', 1)[0]
                    decisions[reason] = int(count)
            out["autoscale"] = {
                "target": int(gauges["serve_autoscale_target"]),
                "decisions": dict(sorted(decisions.items())),
            }
        return out

    def capture_profile(self, seconds: float):
        """On-demand ``jax.profiler`` window over the live serve process
        (``POST /debug/profile?seconds=N``). One capture at a time; the
        trace lands in a numbered subdirectory of ``profile_dir``."""
        import jax

        if not self._profile_lock.acquire(blocking=False):
            return False, {"error": "a profile capture is already running"}
        try:
            seq = int(self.registry.counter(
                "serve_profile_captures_total",
                help="on-demand jax.profiler captures",
            ).inc())
            trace_dir = os.path.join(self.profile_dir, f"capture_{seq:04d}")
            os.makedirs(trace_dir, exist_ok=True)
            jax.profiler.start_trace(trace_dir)
            # jaxlint: disable=JL021 reason=_profile_lock is a capture latch not a data lock; the sleep IS the capture window and contenders get a non-blocking refusal
            time.sleep(seconds)
            jax.profiler.stop_trace()
        finally:
            self._profile_lock.release()
        if self.events is not None:
            self.events.emit(
                "profile_capture", trace_dir=trace_dir, seconds=seconds
            )
        return True, {"trace_dir": trace_dir, "seconds": seconds}

    @property
    def address(self):
        return self.httpd.server_address

    def serve_forever(self):
        self.httpd.serve_forever()

    def drain_streams(self, timeout: Optional[float] = None) -> bool:
        """Block until every in-flight chunked stream finished (True) or
        the drain timeout passed (False) — the SIGTERM contract: clients
        mid-stream get their whole utterance before the process exits."""
        if timeout is None:
            timeout = self.cfg.serve.fleet.drain_timeout_s
        deadline = time.monotonic() + timeout
        with self._streams_cond:
            while self._active_streams > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._streams_cond.wait(timeout=remaining)
        return True

    def shutdown(self):
        """Idempotent: stop accepting, drain in-flight streams, then
        close the dispatch backend (which flushes admitted requests)."""
        with self._shutdown_lock:
            if self._shut_down:
                return
            self._shut_down = True
        self.httpd.shutdown()
        self.httpd.server_close()
        drained = self.drain_streams()
        if not drained and self.events is not None:
            self.events.emit(
                "shutdown_drain_timeout",
                active_streams=int(self._streams_gauge.value),
            )
        # backend first: its flush may still resolve pending frontend
        # handles, so the pool must outlive the drain
        self.backend.close()
        if self.frontend_pool is not None:
            self.frontend_pool.close()
