"""jax's compile phases through obs/jaxmon.py: one ``jax_compile`` ring record
per backend compile naming its program, its cache outcome and the trace and
lowering seconds before it; the counters that grow by the same seconds; a
nested trace counted once; the thread's annotation stack empty after a phase
that raised; the ``compile`` event of a compile inside a run's loop; and the
three phases on the host line of a CPU profiler trace."""

import itertools
import json
import os

import jax
import jax.numpy as jnp
import pytest

from speakingstyle_tpu.obs import (
    JsonlEventLog,
    MetricsRegistry,
    Span,
    enable_compilation_cache,
    jaxmon,
    read_events,
    trace,
    watch_compiles,
)

_serial = itertools.count()


def fresh_function(tag: str):
    """A function whose program no other test (and no earlier run of this
    one) compiled: its constant differs, so its cache key does too."""
    const = float(int.from_bytes(os.urandom(4), "little")) + next(_serial)

    def f(x):
        return x * 2.0 + const

    f.__name__ = f.__qualname__ = f"phases_{tag}"
    return f


@pytest.fixture
def ring(monkeypatch):
    """A span ring of this test's own: other tests of the process record
    into the process's ring too."""
    own = trace.SpanRing()
    monkeypatch.setattr(trace, "_process_ring", own)
    return own


@pytest.fixture
def reg():
    enable_compilation_cache()  # every program is written to the cache
    r = MetricsRegistry()
    watch_compiles(r)
    return r


def compiled_under_a_span(fn, x, name="call"):
    """``fn``'s first call inside a ring span; its context's trace id."""
    ctx = trace.new_context(f"phases-{next(_serial)}")
    with Span(name, parent=ctx):
        jax.block_until_ready(jax.jit(fn)(x))
    return ctx.trace_id


def records_of(ring, program):
    return [s for s in ring.spans() if s["name"] == "jax_compile"
            and s["fields"]["program"] == program]


def grew(before, after):
    return {k: after[k] - before[k] for k in after}


def test_first_call_is_one_record_and_the_counters_grow_by_its_seconds(ring, reg):
    fn = fresh_function("first")
    x = jax.block_until_ready(jnp.ones(8))
    before = jaxmon.compile_totals(reg)
    trace_id = compiled_under_a_span(fn, x)
    delta = grew(before, jaxmon.compile_totals(reg))

    (rec,) = records_of(ring, "jit(phases_first)")
    f = rec["fields"]
    assert rec["trace_id"] == trace_id
    assert f["outcome"] == "miss" and f["step"] is None
    assert f["trace_s"] > 0 and f["lower_s"] > 0
    assert f["key"].startswith("jit_phases_first-")
    assert {"computation", "compile_options", "XLA flags"} <= set(f["digests"])
    # the call compiled one program: the counters say what the record says
    assert delta["compiles"] == 1 and delta["cache_misses"] == 1
    assert delta["trace_seconds"] == pytest.approx(f["trace_s"])
    assert delta["lower_seconds"] == pytest.approx(f["lower_s"])
    assert delta["compile_seconds"] == pytest.approx(rec["duration_s"], rel=1e-3)
    assert delta["missed_compile_seconds"] == pytest.approx(delta["compile_seconds"])


def test_a_compile_the_persistent_cache_serves_reads_hit(ring, reg):
    fn = fresh_function("again")
    x = jax.block_until_ready(jnp.ones(8))
    compiled_under_a_span(fn, x)
    jax.clear_caches()  # the in-memory executables go; the cache's stay
    before = jaxmon.compile_totals(reg)
    compiled_under_a_span(fn, x)
    delta = grew(before, jaxmon.compile_totals(reg))

    first, second = records_of(ring, "jit(phases_again)")
    assert first["fields"]["outcome"] == "miss"
    assert second["fields"]["outcome"] == "hit"
    assert "key" not in second["fields"] and "digests" not in second["fields"]
    assert delta["cache_hits"] == 1 and delta["cache_misses"] == 0
    assert delta["missed_compile_seconds"] == 0


def test_a_jit_traced_inside_another_counts_its_seconds_once(ring, reg):
    inner = fresh_function("inner")

    def outer(x):
        return jax.jit(inner)(x) + 1.0

    outer.__name__ = outer.__qualname__ = "phases_outer"
    seen = []

    def durations(event, secs, **kw):
        if event.endswith("jaxpr_trace_duration"):
            seen.append((kw.get("fun_name"), secs))

    x = jax.block_until_ready(jnp.ones(8))
    before = jaxmon.compile_totals(reg)
    jax.monitoring.register_event_duration_secs_listener(durations)
    try:
        compiled_under_a_span(outer, x)
    finally:
        jax.monitoring.unregister_event_duration_listener(durations)
    delta = grew(before, jaxmon.compile_totals(reg))

    traced = dict(seen)
    assert {"phases_outer", "phases_inner"} <= set(traced)
    # the outer trace holds the inner one: its seconds alone are counted
    assert delta["trace_seconds"] == pytest.approx(traced["phases_outer"])
    (rec,) = records_of(ring, "jit(phases_outer)")
    assert rec["fields"]["trace_s"] == pytest.approx(traced["phases_outer"])


def test_a_phase_that_raises_leaves_the_threads_stack_empty(ring, reg, monkeypatch):
    def bad(x):
        raise ValueError("raised inside the trace")

    x = jax.block_until_ready(jnp.ones(8))
    with pytest.raises(ValueError, match="inside the trace"):
        jax.jit(bad)(x)
    assert jaxmon._thread.open == []

    from jax._src import compiler

    def refuse(*args, **kwargs):
        raise RuntimeError("the backend refused")

    monkeypatch.setattr(compiler, "backend_compile_and_load", refuse)
    before = jaxmon.compile_totals(reg)
    with pytest.raises(RuntimeError, match="backend refused"):
        compiled_under_a_span(fresh_function("refused"), x)
    assert jaxmon._thread.open == []
    # the compile that raised is counted and recorded all the same
    assert grew(before, jaxmon.compile_totals(reg))["compiles"] == 1
    (rec,) = records_of(ring, "jit(phases_refused)")
    assert rec["fields"]["outcome"] == "miss"


def test_a_compile_inside_the_loop_is_a_compile_event(ring, reg, tmp_path):
    """Filed under the run outside any span; an event past the loop's first
    step only; nothing once the run is closed."""
    x = jax.block_until_ready(jnp.ones(8))
    run_ctx = trace.new_context("run-under-test")
    run = jaxmon.watch_run(run_ctx)
    log = JsonlEventLog(str(tmp_path))
    try:
        run.events, run.first_step = log, 5
        jax.jit(fresh_function("setup"))(x)            # set-up: no step
        run.step = 5
        jax.jit(fresh_function("first_step"))(x)       # the loop's first step
        run.step = 7
        jax.jit(fresh_function("later"))(x)
    finally:
        run.close()
        log.close()
    jax.jit(fresh_function("after"))(x)

    (event,) = read_events(str(tmp_path), event="compile")
    assert event["program"] == "jit(phases_later)" and event["step"] == 7
    assert event["outcome"] == "miss" and event["duration_s"] > 0
    assert event["key"].startswith("jit_phases_later-")
    steps = {s["fields"]["program"]: s["fields"]["step"] for s in ring.spans()
             if s["name"] == "jax_compile" and s["trace_id"] == run_ctx.trace_id}
    assert steps == {"jit(phases_setup)": None, "jit(phases_first_step)": 5,
                     "jit(phases_later)": 7}
    assert not records_of(ring, "jit(phases_after)")


def test_the_three_phases_stand_on_the_host_line_in_order(tmp_path, reg):
    from benchmark.harness import tracered

    x = jax.block_until_ready(jnp.ones(8))
    inner = fresh_function("traced")

    def fn(x):
        return jax.jit(inner)(x) - 1.0

    jax.profiler.start_trace(str(tmp_path))
    try:
        with Span("phases_call"):
            jax.block_until_ready(jax.jit(fn)(x))
    finally:
        jax.profiler.stop_trace()
    host = tracered.compact(str(tmp_path))["host"]
    events = {}
    for line in host.values():
        for name, start, dur, *_ in line:
            events.setdefault(name, []).append((start, start + dur))
    (call,) = events["phases_call"]
    inside = {name: [e for e in events.get(name, ())
                     if call[0] <= e[0] and e[1] <= call[1]]
              for name in ("jax_trace", "jax_lower", "jax_compile")}
    # one of each: the inner jit's trace is inside the outer one's
    assert [len(inside[n]) for n in inside] == [1, 1, 1], json.dumps(sorted(events))
    order = [inside[n][0][0] for n in ("jax_trace", "jax_lower", "jax_compile")]
    assert order == sorted(order)
    assert jaxmon._thread.open == []
