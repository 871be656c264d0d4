"""Telemetry suite (tier-1): registry, histogram math, JSONL events, spans,
the events CLI, and the instrumented training smoke.

Layers:
  1. registry — identity/creation semantics, thread-safety under
     concurrent writers (exact totals), histogram percentiles against a
     numpy reference (error bounded by one bucket width), snapshot and
     Prometheus-text export;
  2. events — schema round-trip (every record carries ts + event),
     numpy-value coercion, size rotation, cross-rotation reads, and the
     summarize/filter CLI;
  3. spans — duration into the histogram + a joinable JSONL record, and
     the same span on the host plane of a profiler trace;
  4. the training smoke — a supertiny run_training populates
     step-time/data-wait histograms and writes train_step events with
     the documented step/loss/step_time_s/data_wait_s fields (the
     acceptance criterion for the JSONL export layer).
"""

import io
import json
import os
import threading

import numpy as np
import pytest

from speakingstyle_tpu.obs import (
    Counter,
    Gauge,
    Histogram,
    JsonlEventLog,
    MetricsRegistry,
    Span,
    get_registry,
    read_events,
)
from speakingstyle_tpu.obs import cli as obs_cli

# ---------------------------------------------------------------------------
# 1. registry
# ---------------------------------------------------------------------------


def test_registry_creation_is_idempotent_and_typed():
    reg = MetricsRegistry()
    c1 = reg.counter("a_total", help="h")
    c2 = reg.counter("a_total")
    assert c1 is c2
    # same name, different labels -> different child of the family
    c3 = reg.counter("a_total", labels={"k": "v"})
    assert c3 is not c1
    assert {m is c1 or m is c3 for m in reg.metrics_named("a_total")} == {True}
    with pytest.raises(TypeError):
        reg.gauge("a_total")


def test_counter_inc_returns_sequence_and_rejects_negative():
    c = MetricsRegistry().counter("seq_total")
    assert [int(c.inc()) for _ in range(3)] == [1, 2, 3]
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_inc_dec():
    g = MetricsRegistry().gauge("depth")
    g.set(5)
    g.inc(2)
    g.dec()
    assert g.value == 6


def test_registry_thread_safety_exact_totals():
    """Concurrent writers on one counter, one gauge, one histogram: no
    update may be lost (the whole point of the shared registry is that
    HTTP handler threads, the dispatch thread, and scrapers race it)."""
    reg = MetricsRegistry()
    c = reg.counter("hits_total")
    h = reg.histogram("lat_seconds", edges=(0.1, 1.0, 10.0))
    n_threads, n_iter = 8, 5000

    def writer(tid):
        for i in range(n_iter):
            c.inc()
            h.observe(0.05 * (1 + (i + tid) % 3))
            # creation races too: same (name, labels) from many threads
            reg.counter("hits_by_thread_total", labels={"t": str(tid)}).inc()

    threads = [threading.Thread(target=writer, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert int(c.value) == n_threads * n_iter
    assert h.count == n_threads * n_iter
    per_thread = [int(m.value) for m in reg.metrics_named("hits_by_thread_total")]
    assert per_thread == [n_iter] * n_threads


def test_histogram_percentiles_vs_numpy_reference():
    """The interpolated estimate must land within one bucket width of the
    exact numpy percentile, across distributions and quantiles."""
    rng = np.random.default_rng(0)
    edges = tuple(float(e) for e in np.geomspace(1e-4, 60.0, 24))
    for dist in (
        rng.lognormal(-4.0, 1.0, 4000),          # latency-shaped
        rng.uniform(0.001, 0.5, 4000),           # flat
        np.full(100, 0.0123),                     # degenerate: one value
    ):
        h = Histogram("x_seconds", edges=edges)
        for v in dist:
            h.observe(float(v))
        for q in (0.50, 0.95, 0.99):
            want = float(np.percentile(dist, q * 100))
            got = h.percentile(q)
            i = int(np.searchsorted(edges, want))
            lo = edges[i - 1] if i > 0 else float(dist.min())
            hi = edges[i] if i < len(edges) else float(dist.max())
            width = hi - lo
            assert abs(got - want) <= width + 1e-12, (q, got, want, width)


def test_histogram_empty_and_overflow():
    h = Histogram("x", edges=(1.0, 2.0))
    assert h.percentile(0.5) is None
    h.observe(5.0)  # overflow bin: bounded by the observed max
    assert h.percentile(0.99) == 5.0
    snap = h.snapshot()
    assert snap["count"] == 1 and snap["buckets"][2.0] == 0


def test_snapshot_and_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("req_total", help="requests").inc(3)
    reg.gauge("depth").set(7)
    reg.histogram(
        "lat_seconds", edges=(0.1, 1.0), labels={"bucket": "b1.s16.m32"}
    ).observe(0.5)
    snap = reg.snapshot()
    assert snap["counters"]["req_total"] == 3
    assert snap["gauges"]["depth"] == 7
    hist = snap["histograms"]['lat_seconds{bucket="b1.s16.m32"}']
    assert hist["count"] == 1 and hist["buckets"][1.0] == 1
    # tail keys: p999 rides every snapshot (min/max-tightened, so a
    # single observation reports itself exactly)
    assert hist["p999"] == 0.5 and hist["max"] == 0.5

    text = reg.prometheus_text()
    assert "# HELP req_total requests" in text
    assert "# TYPE req_total counter" in text
    assert "req_total 3" in text
    assert "depth 7" in text
    assert 'lat_seconds_bucket{bucket="b1.s16.m32",le="0.1"} 0' in text
    assert 'lat_seconds_bucket{bucket="b1.s16.m32",le="+Inf"} 1' in text
    assert 'lat_seconds_count{bucket="b1.s16.m32"} 1' in text
    assert 'lat_seconds_p999{bucket="b1.s16.m32"} 0.5' in text
    assert 'lat_seconds_max{bucket="b1.s16.m32"} 0.5' in text


def test_prometheus_text_skips_tail_lines_on_empty_histogram():
    reg = MetricsRegistry()
    reg.histogram("idle_seconds", edges=(0.1, 1.0))
    text = reg.prometheus_text()
    assert "idle_seconds_count 0" in text
    assert "idle_seconds_p999" not in text
    assert "idle_seconds_max" not in text


def test_default_registry_is_a_singleton():
    assert get_registry() is get_registry()


def test_retry_io_counts_retries_in_default_registry():
    """The data layer's retry-with-backoff reports into io_retries_total
    (the leading indicator of a sick filesystem on preemptible slices)."""
    from speakingstyle_tpu.training.resilience import retry_io

    before = get_registry().value("io_retries_total")
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise IOError("transient")
        return "ok"

    assert retry_io(flaky, retries=3, backoff=0.0, sleep=lambda _: None) == "ok"
    assert get_registry().value("io_retries_total") - before == 2


# ---------------------------------------------------------------------------
# 2. JSONL events
# ---------------------------------------------------------------------------


def test_event_schema_roundtrip(tmp_path):
    log = JsonlEventLog(str(tmp_path))
    log.emit("train_step", step=3, total_loss=1.25, step_time_s=0.01,
             data_wait_s=0.002)
    log.emit("rollback", step=4, rollback_n=1, restore_step=None)
    # numpy values must coerce, not crash the writer
    log.emit("val", step=np.int64(5), total_loss=np.float32(0.5),
             arr=np.asarray([1, 2]))
    log.close()
    records = list(read_events(str(tmp_path)))
    assert [r["event"] for r in records] == ["train_step", "rollback", "val"]
    for r in records:
        assert isinstance(r["ts"], float) and "event" in r
    assert records[0]["step"] == 3 and records[0]["data_wait_s"] == 0.002
    assert records[2]["step"] == 5 and records[2]["arr"] == [1, 2]
    # filtered read
    assert [r["event"] for r in read_events(str(tmp_path), event="rollback")] \
        == ["rollback"]


def test_event_rotation_keeps_order_and_bounds_files(tmp_path):
    log = JsonlEventLog(str(tmp_path), max_bytes=600, keep=2)
    for i in range(40):
        log.emit("tick", i=i)
    log.close()
    live = os.path.join(str(tmp_path), "events.jsonl")
    assert os.path.exists(live) and os.path.exists(live + ".1")
    assert not os.path.exists(live + ".3")  # keep=2 bounds the set
    assert os.path.getsize(live) <= 600
    records = list(read_events(str(tmp_path)))
    idx = [r["i"] for r in records]
    assert idx == sorted(idx)          # oldest-first across rotation
    assert idx[-1] == 39               # the newest record survives
    # a torn tail (killed writer) is skipped, not fatal
    with open(live, "a") as fh:
        fh.write('{"ts": 1.0, "event": "torn')
    assert [r["i"] for r in read_events(str(tmp_path))] == idx


def test_malformed_and_blank_lines_are_skipped(tmp_path):
    p = tmp_path / "events.jsonl"
    p.write_text('\n{"ts": 1.0, "event": "ok"}\nnot json\n')
    assert [r["event"] for r in read_events(str(p))] == ["ok"]


# ---------------------------------------------------------------------------
# 3. spans
# ---------------------------------------------------------------------------


def test_span_records_histogram_and_joinable_event(tmp_path):
    reg = MetricsRegistry()
    log = JsonlEventLog(str(tmp_path))
    with Span("serve_dispatch", registry=reg, events=log,
              labels={"bucket": "b1.s16.m32"}, req_ids=["req1", "req2"]) as sp:
        sp.note(rows=2)
    log.close()
    assert sp.duration_s is not None and sp.duration_s >= 0
    h = reg.histogram(
        "serve_dispatch_seconds", labels={"bucket": "b1.s16.m32"}
    )
    assert h.count == 1
    (rec,) = read_events(str(tmp_path))
    assert rec["event"] == "serve_dispatch"
    assert rec["req_ids"] == ["req1", "req2"] and rec["rows"] == 2
    assert rec["bucket"] == "b1.s16.m32" and rec["duration_s"] >= 0


def test_span_records_error_and_still_observes(tmp_path):
    reg = MetricsRegistry()
    log = JsonlEventLog(str(tmp_path))
    with pytest.raises(ValueError):
        with Span("op", registry=reg, events=log):
            raise ValueError("boom")
    log.close()
    (rec,) = read_events(str(tmp_path))
    assert rec["ok"] is False and rec["error"] == "ValueError"
    assert reg.histogram("op_seconds").count == 1


def test_span_lands_on_the_profilers_host_plane(tmp_path):
    """A Span closed while jax.profiler takes a trace is an event of its
    own name on a host plane of the .xplane.pb, its fields the event's
    stats, on the main thread and on a worker alike."""
    import glob

    import jax
    from jax.profiler import ProfileData

    reg = MetricsRegistry()

    def worker():
        with Span("loader_fetch_probe", registry=reg) as sp:
            sp.note(files=4)

    jax.profiler.start_trace(str(tmp_path))
    try:
        with Span("train_data_wait_probe", registry=reg, rows=2):
            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=30)
    finally:
        jax.profiler.stop_trace()
    assert not t.is_alive()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.endswith("_probe"):
                    found[ev.name] = (ev.duration_ns, dict(ev.stats))
    assert set(found) == {"train_data_wait_probe", "loader_fetch_probe"}
    assert found["train_data_wait_probe"][1]["rows"] == 2
    assert found["loader_fetch_probe"][1]["files"] == 4
    # the worker's span closed inside the main thread's
    assert 0 < found["loader_fetch_probe"][0] <= found["train_data_wait_probe"][0]
    # the histograms saw the same two spans
    assert reg.histogram("train_data_wait_probe_seconds").count == 1
    assert reg.histogram("loader_fetch_probe_seconds").count == 1


def test_span_annotation_is_inert_without_a_trace():
    """With no trace running a span's annotation is switched off (nothing
    is recorded anywhere) and its cost is far below what the step loop
    could notice: a dozen spans a step against a step of 0.3 s."""
    import time

    import jax  # noqa: F401  (the annotation exists only once jax is loaded)
    from speakingstyle_tpu.obs import trace as obs_trace

    ann = obs_trace._profiler_annotation("idle_probe")
    assert ann is not None and not ann.is_enabled()
    reg = MetricsRegistry()
    n = 2000
    t0 = time.monotonic()
    for _ in range(n):
        with Span("idle_probe", registry=reg):
            pass
    per_span = (time.monotonic() - t0) / n
    assert reg.histogram("idle_probe_seconds").count == n
    assert per_span < 1e-3  # microseconds here; a millisecond would be a fault


def test_obs_import_does_not_import_jax():
    import subprocess
    import sys

    code = ("import sys, speakingstyle_tpu.obs as o\n"
            "with o.Span('x', registry=o.MetricsRegistry()): pass\n"
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    assert subprocess.run([sys.executable, "-c", code], timeout=120).returncode == 0


# ---------------------------------------------------------------------------
# events CLI
# ---------------------------------------------------------------------------


def test_events_cli_summarize_and_filter(tmp_path, capsys):
    log = JsonlEventLog(str(tmp_path))
    for s in (1, 2):
        log.emit("train_step", step=s, total_loss=2.0 / s,
                 step_time_s=0.01, data_wait_s=0.001)
    log.emit("checkpoint_save", step=2)
    log.close()

    buf = io.StringIO()
    assert obs_cli.summarize(str(tmp_path), out=buf) == 0
    text = buf.getvalue()
    assert "train_step" in text and "2" in text
    assert "step=2" in text and "total_loss" in text

    assert obs_cli.main([str(tmp_path), "--event", "checkpoint_save"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1 and json.loads(out[0])["step"] == 2

    assert obs_cli.main([str(tmp_path), "--tail", "2"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert [json.loads(ln)["event"] for ln in out] == [
        "train_step", "checkpoint_save",
    ]


# ---------------------------------------------------------------------------
# 4. ProgramCard — extraction + degradation paths (obs/cost.py)
# ---------------------------------------------------------------------------


class _GoodCompiled:
    """Backend that reports everything (list-wrapped cost dict + the
    CompiledMemoryStats attribute style — the shapes jax actually uses)."""

    class _Mem:
        argument_size_in_bytes = 100
        output_size_in_bytes = 50
        temp_size_in_bytes = 200
        alias_size_in_bytes = 25
        generated_code_size_in_bytes = 10

    def cost_analysis(self):
        return [{"flops": 1e9, "transcendentals": 1e6,
                 "bytes accessed": 5e8, "bytes accessed0{}": 1e8}]

    def memory_analysis(self):
        return self._Mem()

    def as_text(self):
        return ('%c = custom-call(%a), custom_call_target="tpu_custom_call"\n'
                '%d = custom-call(%c), custom_call_target="tpu_custom_call"')


class _RaisingCompiled:
    def cost_analysis(self):
        raise RuntimeError("backend says no")

    def memory_analysis(self):
        raise NotImplementedError("nope")


class _NoneCompiled:
    def cost_analysis(self):
        return None

    def memory_analysis(self):
        return None


class _DictMemCompiled:
    """Dict-returning memory_analysis with the backend's own peak."""

    def cost_analysis(self):
        return {"flops": 2e9}

    def memory_analysis(self):
        return {"argument_size_in_bytes": 10, "temp_size_in_bytes": 20,
                "peak_memory_in_bytes": 999}


def test_program_card_full_extraction():
    from speakingstyle_tpu.obs import ProgramCard

    card = ProgramCard.from_compiled(_GoodCompiled(), name="p")
    assert card.flops == 1e9 and card.transcendentals == 1e6
    assert card.bytes_accessed == 5e8
    assert card.argument_bytes == 100 and card.temp_bytes == 200
    # peak estimate: args + out + temp + generated - alias
    assert card.peak_bytes == 100 + 50 + 200 + 10 - 25
    assert not card.partial and card.errors == ()
    assert card.mosaic_calls == 2  # Pallas kernels counted from the text
    assert card.arithmetic_intensity == 2.0
    assert card.achieved_flops_per_sec(0.5) == 2e9
    d = card.as_dict()
    assert d["name"] == "p" and d["partial"] is False
    json.dumps(d)  # JSON-ready


def test_program_card_degrades_on_raising_backend():
    from speakingstyle_tpu.obs import ProgramCard, publish_program_gauges

    card = ProgramCard.from_compiled(_RaisingCompiled(), name="p")
    assert card.partial and card.flops is None and card.peak_bytes is None
    assert any("cost_analysis" in e for e in card.errors)
    assert any("memory_analysis" in e for e in card.errors)
    assert card.mosaic_calls is None  # no text: unknown, never "zero"
    assert card.achieved_flops_per_sec(1.0) is None
    json.dumps(card.as_dict())
    # publishing a fully-degraded card is a no-op, not a crash
    reg = MetricsRegistry()
    publish_program_gauges(reg, card, "serve", labels={"bucket": "b1"})
    assert reg.snapshot()["gauges"] == {}


def test_program_card_degrades_on_none_returns():
    from speakingstyle_tpu.obs import ProgramCard

    card = ProgramCard.from_compiled(_NoneCompiled(), name="p")
    assert card.partial and card.flops is None
    assert any("None" in e for e in card.errors)


def test_program_card_dict_memory_and_backend_peak():
    from speakingstyle_tpu.obs import ProgramCard, publish_program_gauges

    card = ProgramCard.from_compiled(_DictMemCompiled(), name="p")
    assert card.flops == 2e9
    assert card.peak_bytes == 999  # the backend's own peak wins
    reg = MetricsRegistry()
    publish_program_gauges(reg, card, "serve", labels={"bucket": "b1"})
    snap = reg.snapshot()
    assert snap["gauges"]['serve_program_flops{bucket="b1"}'] == 2e9
    assert snap["gauges"]['serve_program_peak_bytes{bucket="b1"}'] == 999


def test_program_card_from_real_compiled_executable():
    """The real jax surface on CPU: a compiled program yields a usable,
    non-partial card."""
    import jax
    import jax.numpy as jnp

    from speakingstyle_tpu.obs import ProgramCard

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    compiled = f.lower(jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile()
    card = ProgramCard.from_compiled(compiled, name="sin_matmul")
    assert card.flops and card.flops > 0
    assert card.bytes_accessed and card.bytes_accessed > 0
    assert card.peak_bytes and card.peak_bytes > 0
    assert not card.partial


def test_device_memory_watermark_falls_back_to_card():
    """Where the backend reports no memory_stats (CPU), the watermark
    comes from the card's argument+temp live set; with no card either,
    None — never a crash."""
    import jax

    from speakingstyle_tpu.obs import ProgramCard, device_memory_watermark

    card = ProgramCard.from_compiled(_GoodCompiled(), name="p")
    wm = device_memory_watermark(card)
    assert wm is not None and wm > 0
    if jax.local_devices()[0].memory_stats() is None:  # the CPU tier-1 case
        assert wm == 100.0 + 200.0  # argument + temp bytes
        none_card = ProgramCard.from_compiled(_RaisingCompiled(), name="p")
        assert device_memory_watermark(none_card) is None
        assert device_memory_watermark(None) is None


# ---------------------------------------------------------------------------
# 5. buildinfo + jaxmon cache counters
# ---------------------------------------------------------------------------


def test_build_info_identifies_the_stack():
    from speakingstyle_tpu.obs import build_info

    info = build_info()
    json.dumps(info)
    assert info["python"]
    assert info["jax"]  # jax is importable in the test env
    assert info["backend"] and info["device_count"] >= 1
    # this repo is a git checkout, so the SHA resolves here
    assert info["git_sha"] is None or len(info["git_sha"]) == 40


def test_process_rss_is_positive():
    from speakingstyle_tpu.obs import process_rss_bytes

    rss = process_rss_bytes()
    assert rss is not None and rss > 1e6  # a python process is >1 MB


def test_persistent_cache_events_count_into_watched_registries():
    """The jaxmon bridge folds the compilation-cache monitoring events
    into every watched registry, so /metrics can tell warm from cold."""
    import jax.monitoring

    from speakingstyle_tpu.obs import watch_compiles

    reg = MetricsRegistry()
    watch_compiles(reg)
    # counters export 0 before any event (scrape-friendly)
    assert reg.value("jax_persistent_cache_hits_total") == 0
    assert reg.value("jax_persistent_cache_requests_total") == 0
    jax.monitoring.record_event(
        "/jax/compilation_cache/compile_requests_use_cache"
    )
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert reg.value("jax_persistent_cache_requests_total") == 1
    assert reg.value("jax_persistent_cache_hits_total") == 1


def _record_config_updates(monkeypatch):
    """Patch jax.config.update (and the cache reset) with recorders, so a
    placement test observes what the owner WOULD set without moving the
    session's real cache."""
    import jax
    from jax._src import compilation_cache

    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: calls.append((name, value))
    )
    monkeypatch.setattr(compilation_cache, "reset_cache", lambda: None)
    return calls


def test_compilation_cache_env_var_wins_and_no_dir_is_set_in_code(
        tmp_path, monkeypatch):
    import jax

    from speakingstyle_tpu.obs import enable_compilation_cache
    from speakingstyle_tpu.obs.jaxmon import CACHE_DIR_ENV

    # tests/conftest.py set the variable before jax was imported, so jax
    # derived its own config value from the environment
    env_dir = os.environ[CACHE_DIR_ENV]
    assert jax.config.jax_compilation_cache_dir == env_dir
    calls = _record_config_updates(monkeypatch)
    assert enable_compilation_cache() == env_dir
    # the explicit override loses to the variable too
    assert enable_compilation_cache(str(tmp_path / "override")) == env_dir
    assert not (tmp_path / "override").exists()
    names = {name for name, _ in calls}
    assert "jax_compilation_cache_dir" not in names
    assert names == {
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    }


def test_compilation_cache_default_is_fixed_checkout_path(
        tmp_path, monkeypatch):
    from speakingstyle_tpu.obs import enable_compilation_cache
    from speakingstyle_tpu.obs.jaxmon import CACHE_DIR_ENV, DEFAULT_CACHE_DIR

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert DEFAULT_CACHE_DIR == os.path.join(checkout, ".jax_cache")
    monkeypatch.delenv(CACHE_DIR_ENV)
    calls = _record_config_updates(monkeypatch)
    resolved = []
    for cwd in (tmp_path / "a", tmp_path / "b"):
        cwd.mkdir()
        monkeypatch.chdir(cwd)
        resolved.append(enable_compilation_cache())
    assert resolved == [DEFAULT_CACHE_DIR, DEFAULT_CACHE_DIR]
    assert ("jax_compilation_cache_dir", DEFAULT_CACHE_DIR) in calls
    # train.obs.compilation_cache_dir: honoured only now the variable is unset
    override = str(tmp_path / "override")
    assert enable_compilation_cache(override) == override
    assert os.path.isdir(override)
    assert calls[-1] == ("jax_compilation_cache_dir", override)


def test_cache_dir_has_one_assignment_site():
    """grep-style: the jax flag that places the persistent cache is named
    in obs/jaxmon.py and nowhere else in the program (tests aside) — five
    hard-coded copies of it used to disagree about where the cache lives."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    owner = os.path.join(root, "speakingstyle_tpu", "obs", "jaxmon.py")
    offenders = []
    for base, dirs, files in os.walk(root):
        dirs[:] = [
            d for d in dirs
            if not d.startswith(".") and d not in ("tests", "chiprun_out")
        ]
        for name in files:
            path = os.path.join(base, name)
            if not name.endswith(".py") or path == owner:
                continue
            with open(path, encoding="utf-8") as f:
                if "jax_compilation_cache_dir" in f.read():
                    offenders.append(os.path.relpath(path, root))
    assert offenders == []


# ---------------------------------------------------------------------------
# the programs CLI
# ---------------------------------------------------------------------------


def test_events_cli_programs_pretty_prints_and_rooflines(tmp_path, capsys):
    log = JsonlEventLog(str(tmp_path))
    log.emit(
        "program_card", name="train_step", flops=1.0e12,
        transcendentals=1e6, bytes_accessed=5.0e9, argument_bytes=100.0,
        output_bytes=50.0, temp_bytes=200.0, peak_bytes=350.0,
        arithmetic_intensity=200.0, partial=False,
    )
    for s in (1, 2):
        log.emit("train_step", step=s, total_loss=1.0, step_time_s=0.5,
                 data_wait_s=0.0)
    log.close()

    assert obs_cli.main(["programs", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "train_step" in out
    assert "1.00 TFLOP" in out           # card flops
    assert "2.00 TFLOP/s" in out         # 1e12 / 0.5 s mean step
    assert "intensity" in out and "200.0 FLOP/B" in out

    # --peak-flops adds the utilization row: 2e12 of 4e12 = 50%
    assert obs_cli.main(
        ["programs", str(tmp_path), "--peak-flops", "4e12"]
    ) == 0
    out = capsys.readouterr().out
    assert "50.0%" in out

    # empty log: rc 1, not a crash
    empty = tmp_path / "empty"
    empty.mkdir()
    assert obs_cli.main(["programs", str(empty)]) == 1
    capsys.readouterr()


# ---------------------------------------------------------------------------
# 6. the instrumented training smoke (the acceptance criterion)
# ---------------------------------------------------------------------------


WINDOW_FIELDS = (
    "dispatch_s", "sync_s", "log_s", "loader_read_s", "loader_fetch_s",
    "loader_collate_s", "loader_h2d_s", "loader_blocked_s", "frames_real",
    "frames_padded",
)


def test_train_smoke_populates_metrics_and_event_log(
    synthetic_preprocessed, tmp_path
):
    """A tiny run_training must (a) record step-time and data-wait into
    the registry histograms, and (b) write train_step JSONL events
    carrying the documented step/loss/step_time_s/data_wait_s fields and
    the window's share of every span of the loop and the loader, plus the
    checkpoint_save record for the final flush and a train_start that says
    where set-up's seconds went."""
    from tests.test_resilience import _train_config

    cfg = _train_config(synthetic_preprocessed, tmp_path, total=3, save=2,
                        log=1)
    reg = MetricsRegistry()
    from speakingstyle_tpu.training.trainer import run_training

    state = run_training(cfg, max_steps=3, registry=reg)
    assert int(state.step) == 3

    snap = reg.snapshot()
    assert snap["counters"]["train_steps_total"] == 3
    assert snap["counters"]["checkpoint_saves_total"] >= 1
    step_hist = snap["histograms"]["train_step_seconds"]
    wait_hist = snap["histograms"]["train_data_wait_seconds"]
    assert step_hist["count"] == 3 and step_hist["sum"] > 0
    assert wait_hist["count"] == 3 and wait_hist["p95"] is not None
    # the prefetcher reported its side of the pipeline too
    assert snap["counters"]["data_prefetch_batches_total"] >= 3
    # the loader's spans and counter, from the worker thread (no mesh: the
    # worker makes no transfer, so loader_h2d stays empty)
    for name in ("loader_fetch_seconds", "loader_collate_seconds"):
        assert snap["histograms"][name]["count"] >= 1, name
    assert snap["histograms"]["loader_h2d_seconds"]["count"] == 0
    assert (0 < snap["counters"]["loader_read_seconds_total"]
            <= snap["histograms"]["loader_fetch_seconds"]["sum"])
    assert (snap["counters"]["train_frames_padded_total"]
            >= snap["counters"]["train_frames_real_total"] > 0)
    # the ProgramCard layer: the memory watermark gauge set at every log
    # boundary (card fallback on CPU)
    assert snap["gauges"]["device_memory_watermark_bytes"] > 0

    log_dir = cfg.train.path.log_path
    steps_events = list(read_events(log_dir, event="train_step"))
    assert len(steps_events) == 3  # log_step=1
    for rec in steps_events:
        assert isinstance(rec["ts"], float)
        assert rec["step"] in (1, 2, 3)
        assert np.isfinite(rec["total_loss"])
        assert rec["step_time_s"] >= 0
        assert rec["data_wait_s"] >= 0
        assert "lr" in rec
        for field in WINDOW_FIELDS:
            assert rec[field] >= 0, field
        assert rec["step_time_s"] == pytest.approx(
            rec["dispatch_s"] + rec["sync_s"])
        # the main thread's four spans are disjoint inside the window
        assert (rec["data_wait_s"] + rec["dispatch_s"] + rec["sync_s"]
                + rec["log_s"]) <= 1.0 / rec["steps_per_sec"]
        assert 0 < rec["frames_real"] <= rec["frames_padded"]
    saves = list(read_events(log_dir, event="checkpoint_save"))
    assert saves and saves[-1]["step"] == 3  # final tail-step flush
    # one train_start event identifying the stack that ran
    (start,) = read_events(log_dir, event="train_start")
    assert start["jax"] and start["backend"] and start["device_count"] >= 1
    setup = start["setup_s"]
    assert set(setup) == {"model_init", "restore", "build_steps", "datasets",
                          "total"}
    assert all(v >= 0 for v in setup.values())
    assert sum(v for k, v in setup.items() if k != "total") <= setup["total"]
    # the same phases, each shape's first call and the card's build are in
    # the process's span ring under the run's one trace id
    from speakingstyle_tpu.obs.trace import get_span_ring

    ring = get_span_ring().spans()
    run = [s for s in ring if s["name"] == "setup_model_init"][-1]["trace_id"]
    mine = [s for s in ring if s["trace_id"] == run]
    # a compile's record finishes inside the span it happened in, so it
    # enters the ring before it: the spans alone, in the order they closed
    names = [s["name"] for s in mine if s["name"] != "jax_compile"]
    assert names[:6] == ["setup_model_init", "setup_restore",
                         "setup_build_steps", "setup_datasets", "run_start",
                         "train_data_wait"]
    firsts = [s for s in mine if s["name"] == "train_dispatch"]
    assert 1 <= len(firsts) <= 3 and "train_program_card" in names
    assert len({tuple(s["fields"]["shape"]) for s in firsts}) == len(firsts)
    # each first call's trace, lowering and compile (or load) are its child
    # records, at the loop's step; set-up's are its spans', at no step
    compiles = [s for s in mine if s["name"] == "jax_compile"]
    for first in firsts:
        kids = [c["fields"] for c in compiles
                if c["parent_span_id"] == first["span_id"]]
        assert kids and all(k["step"] is not None for k in kids)
        assert {k["outcome"] for k in kids} <= {"hit", "miss", "uncached"}
        assert sum(k["trace_s"] + k["lower_s"] for k in kids) > 0
    init = [s for s in mine if s["name"] == "setup_model_init"][-1]
    assert [c["fields"]["step"] for c in compiles
            if c["parent_span_id"] == init["span_id"]] != []
    assert all(c["fields"]["step"] is None for c in compiles
               if c["parent_span_id"] == init["span_id"])
    # one program_card event: XLA's own accounting of the step program
    (card,) = read_events(log_dir, event="program_card")
    assert card["name"] == "train_step"
    assert card["flops"] > 0 and card["bytes_accessed"] > 0
    assert card["peak_bytes"] > 0 and card["partial"] is False


def test_validation_pass_stays_out_of_the_windows_loader_fields(
    synthetic_preprocessed, tmp_path
):
    """A validation pass has a loader of its own under the same span names;
    it observes into a registry of its own, so the run's registry, whose
    deltas are a ``train_step`` event's window fields, counts the training
    loader alone."""
    import dataclasses

    from speakingstyle_tpu.training.trainer import run_training
    from tests.test_resilience import _train_config

    cfg = _train_config(synthetic_preprocessed, tmp_path, total=4, save=10,
                        log=2)
    step = dataclasses.replace(cfg.train.step, val_step=2)
    cfg = dataclasses.replace(
        cfg, train=dataclasses.replace(cfg.train, step=step))
    reg = MetricsRegistry()
    run_training(cfg, max_steps=4, registry=reg)
    log_dir = cfg.train.path.log_path
    assert len(list(read_events(log_dir, event="val"))) == 2
    # four training batches reached the step loop; validation's did not count
    assert reg.value("data_prefetch_batches_total") == 4
    padded = sum(e["frames_padded"] * 2
                 for e in read_events(log_dir, event="train_step"))
    assert padded == reg.value("train_frames_padded_total")
