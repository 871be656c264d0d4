"""jax.monitoring bridge: backend events folded into metrics registries.

jax's monitoring bus has no unregister API, so exactly ONE pair of
module-level listeners is ever installed; everything downstream
subscribes to them:

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
    (hits ≈ 0; misses are requests − hits);
  * ``CompileMonitor`` — a scoped counting window (``with monitor:``),
    used by the serving tests (tests/test_serving.py) to assert the
    count is zero across a traffic window.

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

import os
import threading
from typing import Dict, List

from speakingstyle_tpu.obs.registry import MetricsRegistry

_COMPILE_EVENT = "/jax/core/compile/backend_compile"
# plain (count-only) events from jax's persistent compilation cache
_CACHE_EVENT_COUNTERS = {
    "/jax/compilation_cache/compile_requests_use_cache": (
        "jax_persistent_cache_requests_total",
        "compiles that consulted the persistent compilation cache",
    ),
    "/jax/compilation_cache/cache_hits": (
        "jax_persistent_cache_hits_total",
        "compiles served from the persistent compilation cache",
    ),
}

_lock = threading.Lock()
_installed = False
_registries: List[MetricsRegistry] = []
_active_monitors: List["CompileMonitor"] = []


_COMPILES_HELP = "XLA backend compiles observed on the jax.monitoring bus"
_COMPILE_SECONDS_HELP = (
    "seconds inside the backend-compile scope (a persistent-cache hit "
    "counts its retrieval time): the set-up cost a warm cache collapses"
)


def _listener(name: str, duration_secs: float = 0.0, **kwargs) -> None:
    if _COMPILE_EVENT not in name:
        return
    with _lock:
        regs = list(_registries)
        mons = list(_active_monitors)
    for r in regs:
        r.counter("jax_backend_compiles_total", help=_COMPILES_HELP).inc()
        r.counter(
            "jax_backend_compile_seconds_total", help=_COMPILE_SECONDS_HELP
        ).inc(duration_secs)
    for m in mons:
        m._bump()


def _event_listener(name: str, *args, **kwargs) -> None:
    counter = _CACHE_EVENT_COUNTERS.get(name)
    if counter is None:
        return
    cname, chelp = counter
    with _lock:
        regs = list(_registries)
    for r in regs:
        r.counter(cname, help=chelp).inc()


def _ensure_installed() -> None:
    global _installed
    with _lock:
        if _installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_listener)
        jax.monitoring.register_event_listener(_event_listener)
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
    }


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
