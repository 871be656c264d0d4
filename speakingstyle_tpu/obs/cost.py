"""ProgramCard: static cost/memory accounting for compiled XLA programs.

Every hot path in this repo executes AOT-compiled XLA executables (the
serving lattice's per-bucket programs, the jitted train step). XLA
already knows what each of those programs *costs* — `cost_analysis()`
(FLOPs, bytes accessed, transcendentals) and `memory_analysis()`
(argument/output/temp/generated-code bytes) — but until now that
knowledge stayed inside the compiler while PERF.md re-derived it by
hand. A ``ProgramCard`` extracts it once, at compile time, into a plain
dataclass the telemetry layer can export:

  * the serving engine builds one card per lattice point at precompile
    and publishes ``serve_program_flops`` / ``serve_program_peak_bytes``
    gauges (``GET /metrics``) plus a ``GET /debug/programs`` JSON dump;
    each dispatch divides card FLOPs by the measured wall time into an
    achieved-FLOP/s histogram (the MFU-style number per bucket);
  * the trainer builds a card for the jitted train step after the first
    compile, emits a one-time ``program_card`` JSONL event, and folds
    achieved FLOP/s + a device-memory watermark into the per-step
    telemetry;
  * the ``obs.cli programs`` subcommand is a thin consumer.

Backends disagree wildly about these APIs: ``cost_analysis()`` may
return a dict, a list-wrapped dict, ``None``, or raise; analysis keys
carry per-operand suffixes (``bytes accessed0{}``); ``memory_analysis``
may be an object with ``*_in_bytes`` attributes, a dict, ``None``, or
missing entirely. ``ProgramCard.from_compiled`` therefore NEVER raises:
whatever it cannot extract stays ``None``, the failure is recorded in
``errors``, and the partial card remains usable — a flaky backend must
not be able to crash engine precompile or trainer startup.

Known blind spot: XLA's cost analysis
cannot see inside pallas/custom calls, so cards for programs using the
fused-attention kernel UNDER-count by the attention math the kernel
still executes. Compare against an einsum-config card for roofline
arithmetic.
"""

import dataclasses
from typing import Dict, List, Optional, Tuple

from speakingstyle_tpu.obs.registry import MetricsRegistry

# Histogram edges for achieved-FLOP/s observations: 1 MFLOP/s .. 1 EFLOP/s
# in 1/2.5/5 decade steps — wide enough for a CPU tiny model and a TPU pod,
# fine enough that the interpolated percentiles resolve utilization shifts.
FLOPS_PER_SEC_BUCKETS: Tuple[float, ...] = tuple(
    m * 10.0 ** e for e in range(6, 18) for m in (1.0, 2.5, 5.0)
) + (1e18,)

# cost_analysis keys lifted verbatim (the per-operand "bytes accessed0{}"
# variants are backend noise; these three are the stable aggregate keys)
_COST_KEYS = {
    "flops": "flops",
    "transcendentals": "transcendentals",
    "bytes accessed": "bytes_accessed",
}

# memory_analysis fields: CompiledMemoryStats attribute -> card field
_MEMORY_KEYS = {
    "argument_size_in_bytes": "argument_bytes",
    "output_size_in_bytes": "output_bytes",
    "temp_size_in_bytes": "temp_bytes",
    "alias_size_in_bytes": "alias_bytes",
    "generated_code_size_in_bytes": "generated_code_bytes",
}


@dataclasses.dataclass(frozen=True)
class ProgramCard:
    """Static cost/memory metadata for one compiled XLA executable.

    Every numeric field is Optional: ``None`` means the backend did not
    report it (never that it is zero). ``errors`` records why."""

    name: str
    flops: Optional[float] = None
    transcendentals: Optional[float] = None
    bytes_accessed: Optional[float] = None
    argument_bytes: Optional[float] = None
    output_bytes: Optional[float] = None
    temp_bytes: Optional[float] = None
    alias_bytes: Optional[float] = None
    generated_code_bytes: Optional[float] = None
    peak_bytes: Optional[float] = None
    # Pallas TPU kernels IN the program: Mosaic custom calls counted in
    # the compiled text (0 on any other backend). cost_analysis cannot see
    # into them, and a "fused" model that took its einsum path instead
    # shows up here as 0 where a TPU program should have some.
    mosaic_calls: Optional[int] = None
    errors: Tuple[str, ...] = ()

    @property
    def partial(self) -> bool:
        """True when any core quantity is missing (degraded backend)."""
        return self.flops is None or self.peak_bytes is None

    @property
    def arithmetic_intensity(self) -> Optional[float]:
        """FLOPs per HBM byte — the roofline x-coordinate."""
        if self.flops is None or not self.bytes_accessed:
            return None
        return self.flops / self.bytes_accessed

    def achieved_flops_per_sec(self, seconds: float) -> Optional[float]:
        """Card FLOPs over a measured wall time (the MFU numerator)."""
        if self.flops is None or seconds <= 0:
            return None
        return self.flops / seconds

    def as_dict(self) -> Dict:
        """JSON-ready dict (the /debug/programs and event-log spelling)."""
        out = {
            f.name: getattr(self, f.name)
            for f in dataclasses.fields(self)
            if f.name != "errors"
        }
        out["partial"] = self.partial
        out["arithmetic_intensity"] = self.arithmetic_intensity
        if self.errors:
            out["errors"] = list(self.errors)
        return out

    @classmethod
    def from_compiled(cls, compiled, name: str) -> "ProgramCard":
        """Extract a card from anything shaped like a jax ``Compiled``
        executable. Degrades field-by-field; NEVER raises."""
        fields: Dict[str, Optional[float]] = {}
        errors: List[str] = []
        cost = _extract_cost(compiled, errors)
        for src, dst in _COST_KEYS.items():
            v = cost.get(src)
            fields[dst] = float(v) if isinstance(v, (int, float)) else None
        mem = _extract_memory(compiled, errors)
        for src, dst in _MEMORY_KEYS.items():
            v = mem.get(src)
            fields[dst] = float(v) if isinstance(v, (int, float)) else None
        fields["peak_bytes"] = _peak_bytes(mem, fields)
        mosaic_calls = _count_mosaic_calls(compiled, errors)
        return cls(name=name, mosaic_calls=mosaic_calls,
                   errors=tuple(errors), **fields)


MOSAIC_CALL_TARGET = "tpu_custom_call"


def _count_mosaic_calls(compiled, errors: List[str]) -> Optional[int]:
    """Occurrences of the Mosaic custom-call target in the compiled text;
    reading the text never compiles. None when the backend has no text."""
    try:
        text = compiled.as_text()
    except Exception as e:
        errors.append(f"as_text: {type(e).__name__}: {e}")
        return None
    if not isinstance(text, str):
        errors.append(f"as_text: unusable type {type(text).__name__}")
        return None
    return text.count(MOSAIC_CALL_TARGET)


def _extract_cost(compiled, errors: List[str]) -> Dict:
    """cost_analysis() -> flat dict, tolerating raise/None/list-wrapping."""
    try:
        cost = compiled.cost_analysis()
    except Exception as e:
        errors.append(f"cost_analysis: {type(e).__name__}: {e}")
        return {}
    if isinstance(cost, (list, tuple)):
        # some backends wrap one dict per device program; the programs are
        # identical (SPMD), so the first entry is the per-device cost
        cost = cost[0] if cost else None
    if cost is None:
        errors.append("cost_analysis: returned None")
        return {}
    if not hasattr(cost, "get"):
        errors.append(f"cost_analysis: unusable type {type(cost).__name__}")
        return {}
    return cost


def _extract_memory(compiled, errors: List[str]) -> Dict:
    """memory_analysis() -> flat dict from either the CompiledMemoryStats
    attribute style or a dict-returning backend; tolerates raise/None."""
    try:
        mem = compiled.memory_analysis()
    except Exception as e:
        errors.append(f"memory_analysis: {type(e).__name__}: {e}")
        return {}
    if mem is None:
        errors.append("memory_analysis: returned None")
        return {}
    if hasattr(mem, "get"):
        return mem
    out = {}
    for key in list(_MEMORY_KEYS) + ["peak_memory_in_bytes"]:
        v = getattr(mem, key, None)
        if isinstance(v, (int, float)):
            out[key] = v
    if not out:
        errors.append(f"memory_analysis: unusable type {type(mem).__name__}")
    return out


def _peak_bytes(mem: Dict, fields: Dict) -> Optional[float]:
    """The backend's own peak when it reports one, else the standard
    live-set estimate: arguments + outputs + temps + generated code minus
    aliased (donated) bytes."""
    v = mem.get("peak_memory_in_bytes")
    if isinstance(v, (int, float)):
        return float(v)
    parts = [
        fields.get(k)
        for k in ("argument_bytes", "output_bytes", "temp_bytes",
                  "generated_code_bytes")
    ]
    if all(p is None for p in parts):
        return None
    total = sum(p for p in parts if p is not None)
    alias = fields.get("alias_bytes")
    return total - (alias or 0.0)


def publish_program_gauges(
    registry: MetricsRegistry,
    card: ProgramCard,
    prefix: str,
    labels: Optional[Dict[str, str]] = None,
) -> None:
    """Export a card's headline numbers as ``<prefix>_program_flops`` /
    ``<prefix>_program_peak_bytes`` gauges (skipping missing fields)."""
    if card.flops is not None:
        registry.gauge(
            f"{prefix}_program_flops", labels=labels,
            help="XLA cost_analysis FLOPs of the compiled program",
        ).set(card.flops)
    if card.peak_bytes is not None:
        registry.gauge(
            f"{prefix}_program_peak_bytes", labels=labels,
            help="estimated peak device bytes of the compiled program",
        ).set(card.peak_bytes)


def device_memory_watermarks(
    card: Optional[ProgramCard] = None, devices=None
) -> Dict[str, float]:
    """Per-device memory watermarks: ``{"tpu:0": bytes, ...}`` keyed by
    ``platform:id`` labels — the multichip spelling of
    ``device_memory_watermark`` (gauge labels per mesh device). Falls back
    to the card's argument+temp live set, identical on every device under
    SPMD. Never raises; backends without stats yield an empty dict."""
    try:
        import jax

        devices = list(devices) if devices is not None else jax.local_devices()
    except Exception:  # jaxlint: disable=JL007
        return {}
    out: Dict[str, float] = {}
    for d in devices:
        label = f"{getattr(d, 'platform', 'dev')}:{getattr(d, 'id', '?')}"
        try:
            stats = d.memory_stats()
        except Exception:  # jaxlint: disable=JL007
            stats = None
        v = None
        if stats:
            v = stats.get("peak_bytes_in_use") or stats.get("bytes_in_use")
        if not (isinstance(v, (int, float)) and v > 0) and card is not None:
            parts = [card.argument_bytes, card.temp_bytes]
            if any(p is not None for p in parts):
                v = sum(p for p in parts if p is not None)
        if isinstance(v, (int, float)) and v > 0:
            out[label] = float(v)
    return out


def device_memory_watermark(card: Optional[ProgramCard] = None):
    """Best-effort device-memory watermark in bytes: the backend's own
    ``memory_stats()`` peak where available (TPU/GPU), else the card's
    argument+temp live set, else ``None``. Never raises — callable from
    the train-loop log boundary on any backend (CPU reports no stats)."""
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats()
    # deliberately broad: ANY backend failure (no jax, no devices, a
    # runtime that doesn't implement memory_stats) means "no stats here"
    except Exception:  # jaxlint: disable=JL007
        stats = None
    if stats:
        v = stats.get("peak_bytes_in_use") or stats.get("bytes_in_use")
        if isinstance(v, (int, float)) and v > 0:
            return float(v)
    if card is not None:
        parts = [card.argument_bytes, card.temp_bytes]
        if any(p is not None for p in parts):
            return sum(p for p in parts if p is not None)
    return None
