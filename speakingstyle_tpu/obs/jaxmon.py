"""jax.monitoring bridge: backend events folded into metrics registries.

jax's monitoring bus is process-wide: one set of module-level listeners is
installed once, on the first subscriber, and never removed (jax 0.9 has
``unregister_*``, but every registry, monitor and run below is a
subscriber of the same four listeners, so there is nothing to take off when
one of them goes); everything downstream subscribes to them:

  * ``watch_compiles(registry)`` — every backend compile event
    increments ``jax_backend_compiles_total`` (and adds its duration to
    ``jax_backend_compile_seconds_total``) in that registry (each
    ``SynthesisEngine`` subscribes its own, so ``/metrics`` exports the
    backend's own compile count next to the engine's ``.compile()``
    bookkeeping — two independent witnesses for the zero-steady-state-
    compiles invariant), and the persistent-compilation-cache events
    count into ``jax_persistent_cache_requests_total`` /
    ``jax_persistent_cache_hits_total`` — so a /metrics scrape
    distinguishes a warm start (hits ≈ requests) from a cold one
    (hits ≈ 0); ``jax_persistent_cache_misses_total`` counts the
    compiles that consulted the cache and were not served, and
    ``jax_backend_compile_missed_seconds_total`` the backend-compile
    seconds the cache did not serve (misses, and compiles that never
    consulted it). ``jax_trace_seconds_total`` and
    ``jax_lower_seconds_total`` hold jax's tracing (function to jaxpr)
    and lowering (jaxpr to MLIR) seconds, a phase counted only where no
    phase of the same kind was open on its thread (a jit traced inside
    another's trace is inside the outer's seconds already);
  * ``CompileMonitor`` — a scoped counting window (``with monitor:``),
    used by the serving tests (tests/test_serving.py) to assert the
    count is zero across a traffic window;
  * the compile phases as spans. jax opens each of its three phases with
    a scalar event (``record_scalar(event, start_time, fun_name=)``) and
    closes it with a duration and a time-span event. Each compile, and
    each trace or lowering not nested in one of its kind, is a
    ``jax.profiler.TraceAnnotation`` for as long as it is open
    (``jax_trace``, ``jax_lower``, ``jax_compile``; kept on a thread-local
    stack, last in first out, closed also where the phase raised), so a
    compile inside a profiler trace stands on the host line, on the clock
    of the device's ``XLA Ops``; inert with no profiler running. Every
    backend compile is also one ``jax_compile`` record in the process's
    span ring (``Span.record``): ``program`` (jax's ``fun_name``),
    ``outcome`` (``hit``/``miss`` from the persistent-cache events fired
    on its thread inside its scope; ``uncached`` where the cache was not
    consulted), ``trace_s``/``lower_s`` (its thread's phases since its
    previous record), ``step``, and on a miss the cache ``key`` as jax's
    compiler logs it and ``digests``, each key component's own digest
    from the ``jax._src.cache_key`` logger (which this module opens to
    DEBUG: jax then hashes each component twice a compile). It is
    parented on the thread's ambient span, else on the run that
    ``watch_run`` names; outside both it is counted, not recorded. A
    compile inside a run's loop after its first step is also a
    ``compile`` event in the run's event log.

``enable_compilation_cache()`` is the one place that decides where jax's
persistent compile cache lives: where ``JAX_COMPILATION_CACHE_DIR``
points, else ``<checkout>/.jax_cache``. Every entry point calls it before
its first compile (``__main__``, ``chip_smoke.py``'s children) and every ``ProgramRegistry`` (``parallel/registry.py``) calls
it again with the ``train.obs.compilation_cache_dir`` override, so
repeated runs skip the compiles the cache already holds.

jax is imported lazily (on first install), so this module — like the
rest of ``obs/`` — costs nothing to import in jax-free contexts
(jaxlint, the events CLI).
"""

import logging
import os
import threading
import weakref
from typing import Dict, List, Optional

from speakingstyle_tpu.obs.registry import MetricsRegistry
from speakingstyle_tpu.obs.trace import Span, current_context

# jax's three compile phases (jax/_src/dispatch.py), by the name each has as
# a span: the event opens it as a scalar and closes it as a duration
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax_lower",
    "/jax/core/compile/backend_compile_duration": "jax_compile",
}
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# plain (count-only) events from jax's persistent compilation cache
_CACHE_EVENT_COUNTERS = {
    _CACHE_REQUEST: (
        "jax_persistent_cache_requests_total",
        "compiles that consulted the persistent compilation cache",
    ),
    _CACHE_HIT: (
        "jax_persistent_cache_hits_total",
        "compiles served from the persistent compilation cache",
    ),
}
# the loggers whose DEBUG records carry a miss's key and its components
_KEY_LOGGERS = ("jax._src.compiler", "jax._src.cache_key")
_MISS_MSG = "PERSISTENT COMPILATION CACHE MISS"
_DIGEST_MSG = "get_cache_key hash of serialized"

_lock = threading.Lock()
_installed = False
_registries: List[MetricsRegistry] = []
_active_monitors: List["CompileMonitor"] = []
_run: Optional["weakref.ref"] = None  # the RunCompiles of the run in progress


_COMPILES_HELP = "XLA backend compiles observed on the jax.monitoring bus"
_COMPILE_SECONDS_HELP = (
    "seconds inside the backend-compile scope (a persistent-cache hit "
    "counts its retrieval time): the set-up cost a warm cache collapses"
)
# counters that accumulate seconds or compiles: (name, help)
_TRACE_SECONDS = ("jax_trace_seconds_total",
                  "seconds jax spent tracing functions to jaxprs (outermost "
                  "trace of a thread only)")
_LOWER_SECONDS = ("jax_lower_seconds_total",
                  "seconds jax spent lowering jaxprs to MLIR modules "
                  "(outermost lowering of a thread only)")
_MISSES = ("jax_persistent_cache_misses_total",
           "compiles that consulted the persistent compilation cache and "
           "were not served")
_MISSED_SECONDS = ("jax_backend_compile_missed_seconds_total",
                   "backend-compile seconds the persistent cache did not "
                   "serve (misses, and compiles that did not consult it)")
_PHASE_SECONDS = {"jax_trace": _TRACE_SECONDS, "jax_lower": _LOWER_SECONDS}


def _count(registry: MetricsRegistry, counter: tuple, amount: float) -> None:
    name, help_text = counter
    registry.counter(name, help=help_text).inc(amount)


class _ThreadPhases(threading.local):
    """One thread's open compile phases and what they owe its next
    ``jax_compile`` record."""

    def __init__(self):
        # [name, annotation or None, compile's cache outcome or None]
        self.open: List[list] = []
        self.trace_s = 0.0  # outermost phases since the last compile record
        self.lower_s = 0.0
        self.closed = None  # the compile the duration event closed


_thread = _ThreadPhases()


def _open_compile() -> Optional[dict]:
    """The cache outcome of the innermost compile open on this thread."""
    for name, _, info in reversed(_thread.open):
        if name == "jax_compile":
            return info
    return None


def _phase_opened(event: str, value: float = 0.0, **kwargs) -> None:
    name = _PHASES.get(event)
    if name is None:
        return
    info, annotation = None, None
    if name == "jax_compile":
        info = {"requested": False, "hit": False, "digests": {}}
    if info is not None or all(e[0] != name for e in _thread.open):
        # a trace or lowering nested in one of its kind is inside the outer
        # one's annotation already (a step's trace holds thousands)
        from jax.profiler import TraceAnnotation

        annotation = TraceAnnotation(name)
        annotation.__enter__()
    _thread.open.append([name, annotation, info])


def _close(name: str):
    """Close the innermost open phase called ``name`` and, last in first
    out, any opened after it whose close never came. Returns its entry (None
    where none was open) and whether no phase of its kind stays open."""
    stack = _thread.open
    at = next((i for i in range(len(stack) - 1, -1, -1)
               if stack[i][0] == name), None)
    entry = None
    if at is not None:
        closing = stack[at:]
        del stack[at:]
        for _, annotation, _ in reversed(closing):
            if annotation is not None:
                annotation.__exit__(None, None, None)
        entry = closing[0]
    return entry, all(e[0] != name for e in stack)


def _listener(name: str, duration_secs: float = 0.0, **kwargs) -> None:
    phase = _PHASES.get(name)
    if phase is None:
        return
    entry, outermost = _close(phase)
    with _lock:
        regs = list(_registries)
        mons = list(_active_monitors)
    if phase == "jax_compile":
        info = entry[2] if entry is not None else None
        outcome = ("uncached" if info is None or not info["requested"]
                   else "hit" if info["hit"] else "miss")
        for r in regs:
            r.counter("jax_backend_compiles_total", help=_COMPILES_HELP).inc()
            r.counter(
                "jax_backend_compile_seconds_total", help=_COMPILE_SECONDS_HELP
            ).inc(duration_secs)
            if outcome != "hit":
                _count(r, _MISSED_SECONDS, duration_secs)
            if outcome == "miss":
                _count(r, _MISSES, 1)
        for m in mons:
            m._bump()
        _thread.closed = (kwargs.get("fun_name"), outcome, info)
        return
    if not outermost:
        return
    for r in regs:
        _count(r, _PHASE_SECONDS[phase], duration_secs)
    if phase == "jax_trace":
        _thread.trace_s += duration_secs
    else:
        _thread.lower_s += duration_secs


def _span_listener(event: str, start_time: float, end_time: float,
                   **kwargs) -> None:
    """The close of a backend compile, with its wall-clock extent: its
    record in the span ring, and in a run's loop its event."""
    if event != _COMPILE_EVENT or _thread.closed is None:
        return
    program, outcome, info = _thread.closed
    _thread.closed = None
    fields = {"program": program, "outcome": outcome,
              "trace_s": _thread.trace_s, "lower_s": _thread.lower_s}
    _thread.trace_s = _thread.lower_s = 0.0
    run = _run() if _run is not None else None
    fields["step"] = run.step if run is not None else None
    if outcome == "miss":
        fields["key"] = info.get("key")
        fields["digests"] = info["digests"]
    Span.record("jax_compile", start_time, end_time - start_time,
                parent=current_context() or (run.ctx if run else None),
                **fields)
    if (run is not None and run.events is not None and run.step is not None
            and run.step > run.first_step):
        run.events.emit("compile", duration_s=end_time - start_time, **fields)


def _event_listener(name: str, *args, **kwargs) -> None:
    counter = _CACHE_EVENT_COUNTERS.get(name)
    if counter is None:
        return
    info = _open_compile()
    if info is not None:
        info["hit" if name == _CACHE_HIT else "requested"] = True
    cname, chelp = counter
    with _lock:
        regs = list(_registries)
    for r in regs:
        r.counter(cname, help=chelp).inc()


class _KeyCapture(logging.Filter):
    """Takes a miss's cache key and each key component's digest from jax's
    records into the thread's open compile. Passes on what its logger let
    through before this module opened it to DEBUG, and nothing else."""

    def __init__(self, floor: int):
        super().__init__()
        self.floor = floor

    def filter(self, record: logging.LogRecord) -> bool:
        info = _open_compile()
        if info is not None and isinstance(record.msg, str):
            if record.msg.startswith(_MISS_MSG):
                info["key"] = record.args[1]
            elif record.msg.startswith(_DIGEST_MSG):
                info["digests"][record.args[0]] = record.args[1]
        return record.levelno >= self.floor


def _ensure_installed() -> None:
    global _installed
    with _lock:
        if _installed:
            return
        import jax.monitoring

        jax.monitoring.register_scalar_listener(_phase_opened)
        jax.monitoring.register_event_duration_secs_listener(_listener)
        jax.monitoring.register_event_time_span_listener(_span_listener)
        jax.monitoring.register_event_listener(_event_listener)
        for name in _KEY_LOGGERS:
            logger = logging.getLogger(name)
            logger.addFilter(_KeyCapture(logger.getEffectiveLevel()))
            logger.setLevel(logging.DEBUG)
        _installed = True


def watch_compiles(registry: MetricsRegistry) -> None:
    """Subscribe ``registry`` to backend compile + cache events
    (idempotent)."""
    _ensure_installed()
    # touch the counters so /metrics exports 0 before the first compile
    registry.counter("jax_backend_compiles_total", help=_COMPILES_HELP)
    registry.counter(
        "jax_backend_compile_seconds_total", help=_COMPILE_SECONDS_HELP
    )
    for cname, chelp in _CACHE_EVENT_COUNTERS.values():
        registry.counter(cname, help=chelp)
    for counter in (_TRACE_SECONDS, _LOWER_SECONDS, _MISSES, _MISSED_SECONDS):
        _count(registry, counter, 0)
    with _lock:
        if not any(r is registry for r in _registries):
            _registries.append(registry)


def compile_totals(registry: MetricsRegistry) -> Dict[str, float]:
    """What a watched registry has seen of compilation so far: the
    set-up cost of a process and whether it started warm (the
    ``train_end`` event and the chip smoke's legs report exactly this)."""
    return {
        "compiles": registry.value("jax_backend_compiles_total"),
        "compile_seconds": registry.value("jax_backend_compile_seconds_total"),
        "cache_hits": registry.value("jax_persistent_cache_hits_total"),
        "cache_requests": registry.value(
            "jax_persistent_cache_requests_total"),
        "cache_misses": registry.value(_MISSES[0]),
        "missed_compile_seconds": registry.value(_MISSED_SECONDS[0]),
        "trace_seconds": registry.value(_TRACE_SECONDS[0]),
        "lower_seconds": registry.value(_LOWER_SECONDS[0]),
    }


class RunCompiles:
    """Where one training run's compiles are filed: the run's trace context
    (the parent of a compile that no span on its thread holds), the loop's
    ``step`` (None in set-up) and its ``first_step``, and the run's event
    log, which gets a ``compile`` event for each compile at a step past the
    first. ``watch_run`` makes it the process's run; the caller keeps it and
    updates ``step`` and ``events`` as the run goes. A run the caller no
    longer holds, or has closed, files nothing."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.events = None
        self.step: Optional[int] = None
        self.first_step = 0

    def close(self) -> None:
        global _run
        with _lock:
            if _run is not None and _run() is self:
                _run = None


def watch_run(ctx) -> RunCompiles:
    """File the process's compiles under the run of context ``ctx`` until
    the returned scope is closed or dropped (a later run takes over)."""
    global _run
    _ensure_installed()
    run = RunCompiles(ctx)
    with _lock:
        _run = weakref.ref(run)
    return run


CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, resolved from this file's location: the directory is part of the
# cache key, so a path that moved between runs would never hit
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compilation_cache(override: str = "") -> str:
    """Place jax's persistent compilation cache and drop the min-size/
    min-time thresholds so every program — including the serving lattice's
    small buckets — is cached. Returns the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set jax already honours it and
    no directory is set in code. Otherwise the directory is ``override``
    (``train.obs.compilation_cache_dir``) or, when that is empty,
    ``DEFAULT_CACHE_DIR``. Safe to call repeatedly and after compiles have
    happened: jax builds its cache object on the first compile and keeps
    it, so a directory that changes afterwards gets the object rebuilt."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    env_dir = os.environ.get(CACHE_DIR_ENV)
    if env_dir:
        return env_dir
    cache_dir = (
        os.path.abspath(os.path.expanduser(override)) if override
        else DEFAULT_CACHE_DIR
    )
    if jax.config.jax_compilation_cache_dir != cache_dir:
        from jax._src import compilation_cache

        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        if compilation_cache._cache_initialized:
            compilation_cache.reset_cache()
    return cache_dir


class CompileMonitor:
    """Scoped backend-compile counter (``with monitor: ... monitor.count``)."""

    def __init__(self):
        self.count = 0
        self._mlock = threading.Lock()

    def _bump(self) -> None:
        with self._mlock:
            self.count += 1

    def __enter__(self) -> "CompileMonitor":
        _ensure_installed()
        with _lock:
            _active_monitors.append(self)
        return self

    def __exit__(self, *exc) -> bool:
        with _lock:
            _active_monitors.remove(self)
        return False
