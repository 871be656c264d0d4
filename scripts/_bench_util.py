"""Shared preamble for the on-chip experiment scripts.

One canonical copy of the three things every probe needs, so the timing
discipline (PERF.md "Timing methodology") cannot drift between scripts:

* repo-root sys.path bootstrap (the scripts run with scripts/ as
  sys.path[0]);
* the persistent compilation cache, placed by its one owner
  (obs/jaxmon.enable_compilation_cache);
* ``timeit``: JAX dispatch is asynchronous, so the timed region ends in
  ``block_until_ready`` on the last result — a timing without it measures
  the enqueue. 50 iterations.

Import as ``from _bench_util import timeit, require_tpu`` (the scripts
run with scripts/ as sys.path[0]).
"""

import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax  # noqa: E402  (after the sys.path bootstrap by design)

from speakingstyle_tpu.obs.jaxmon import enable_compilation_cache  # noqa: E402

enable_compilation_cache()

ITERS = 50


def require_tpu():
    from speakingstyle_tpu.ops import on_tpu

    if not on_tpu():
        raise RuntimeError(f"not a TPU: {jax.devices()[0]}")


def timeit(fn, *args, iters: int = ITERS):
    """ms per call of fn(*args), warm, synced with block_until_ready."""
    jax.block_until_ready(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3
