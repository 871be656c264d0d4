"""Unified telemetry: metrics registry, trace spans, JSONL events.

The single instrumented spine shared by training, data, and serving
(ARCHITECTURE.md "Observability"):

  * ``registry`` — thread-safe counters/gauges/bounded-bucket histograms
    with p50/p95/p99 estimates; ``snapshot()`` (dict) and
    ``prometheus_text()`` (``GET /metrics``) export surfaces;
  * ``events`` — rotating JSONL event log with a stable documented
    schema (the training run's structured record);
  * ``trace`` — lightweight monotonic-clock spans feeding both, and the
    profiler's host plane while a trace is taken;
  * ``jaxmon`` — the jax.monitoring bridge (backend compile + persistent
    cache counters, scoped ``CompileMonitor`` windows, the
    ``enable_compilation_cache`` knob);
  * ``cost`` — ``ProgramCard`` static cost/memory accounting for
    compiled XLA executables (per-program FLOPs/bytes/peak memory,
    achieved-FLOP/s export);
  * ``buildinfo`` — build/runtime identity (git SHA, jax versions,
    backend) + process RSS for /healthz and /metrics;
  * ``quality`` — the audio-output validator choke point (cheap
    host-side wav checks feeding the quality SLO stream);
  * ``slo`` — multi-window burn-rate accounting over the latency AND
    quality counter streams.

Zero dependencies, no jax import at module scope.
"""

from speakingstyle_tpu.obs.buildinfo import (
    array_sha256,
    build_info,
    process_rss_bytes,
    weights_digest,
)
from speakingstyle_tpu.obs.cost import (
    FLOPS_PER_SEC_BUCKETS,
    ProgramCard,
    device_memory_watermark,
    device_memory_watermarks,
    publish_program_gauges,
)
from speakingstyle_tpu.obs.events import JsonlEventLog, read_events
from speakingstyle_tpu.obs.jaxmon import (
    CompileMonitor,
    enable_compilation_cache,
    watch_compiles,
)
from speakingstyle_tpu.obs.quality import (
    QualityGate,
    WavVerdict,
    validate_wav,
)
from speakingstyle_tpu.obs.registry import (
    DEFAULT_TIME_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
)
from speakingstyle_tpu.obs.trace import Span, span

__all__ = [
    "Counter",
    "CompileMonitor",
    "DEFAULT_TIME_BUCKETS",
    "FLOPS_PER_SEC_BUCKETS",
    "Gauge",
    "Histogram",
    "JsonlEventLog",
    "MetricsRegistry",
    "ProgramCard",
    "QualityGate",
    "Span",
    "WavVerdict",
    "array_sha256",
    "build_info",
    "device_memory_watermark",
    "device_memory_watermarks",
    "enable_compilation_cache",
    "get_registry",
    "process_rss_bytes",
    "publish_program_gauges",
    "read_events",
    "span",
    "validate_wav",
    "watch_compiles",
    "weights_digest",
]
