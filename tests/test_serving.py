"""Serving stack: lattice covering, batcher properties, AOT engine smoke.

Three layers, mirroring the package:
  1. lattice — pure-python covering-bucket properties (no jax);
  2. batcher — deadline / coalescing / exactly-once-future properties
     against a fake engine (no jax, millisecond-fast);
  3. engine + server — the tiny-model end-to-end smoke: AOT precompile,
     serve through the batcher and over HTTP, and assert the serve loop
     performed ZERO XLA compiles after warmup (the acceptance invariant,
     checked with a jax.monitoring listener — not just the engine's own
     counter).
"""

import http.client
import json
import threading
import time

import numpy as np
import pytest

from speakingstyle_tpu.configs.config import (
    Config,
    ModelConfig,
    ReferenceEncoderConfig,
    ServeConfig,
    StyleConfig,
    TransformerConfig,
    VarianceEmbeddingConfig,
    VariancePredictorConfig,
)
from speakingstyle_tpu.serving.batcher import (
    ContinuousBatcher,
    Overloaded,
    ShutdownError,
)
from speakingstyle_tpu.serving.engine import (
    CompileMonitor,
    SynthesisRequest,
    _fill_control,
)
from speakingstyle_tpu.serving.lattice import BucketLattice, RequestTooLarge

# ---------------------------------------------------------------------------
# lattice
# ---------------------------------------------------------------------------


def test_lattice_cover_is_elementwise_smallest():
    lat = BucketLattice([1, 4, 8], [16, 32], [64, 128])
    rng = np.random.default_rng(0)
    for _ in range(200):
        n = int(rng.integers(1, 9))
        l = int(rng.integers(1, 33))
        t = int(rng.integers(1, 129))
        got = lat.cover(n, l, t)
        # covers
        assert got.b >= n and got.l_src >= l and got.t_mel >= t
        # and no strictly smaller covering point exists on any axis
        for p in lat.points():
            if p.b >= n and p.l_src >= l and p.t_mel >= t:
                assert got.b <= p.b and got.l_src <= p.l_src \
                    and got.t_mel <= p.t_mel


def test_lattice_too_large_raises_per_axis():
    lat = BucketLattice([1, 4], [16], [64])
    with pytest.raises(RequestTooLarge, match="batch"):
        lat.cover(5, 8, 32)
    with pytest.raises(RequestTooLarge, match="src"):
        lat.cover(1, 17, 32)
    with pytest.raises(RequestTooLarge, match="mel"):
        lat.cover(1, 8, 65)


def test_lattice_points_and_ordering():
    lat = BucketLattice([1, 2], [16], [32, 64])
    pts = lat.points()
    assert len(pts) == len(lat) == 4
    vols = [p.volume for p in pts]
    assert vols == sorted(vols)  # compile order: cheapest first
    assert lat.max_batch == 2 and lat.max_src == 16 and lat.max_mel == 64


def test_lattice_rejects_bad_axes():
    with pytest.raises(ValueError):
        BucketLattice([], [16], [32])
    with pytest.raises(ValueError):
        BucketLattice([4, 1], [16], [32])


# ---------------------------------------------------------------------------
# batcher (fake engine — no jax)
# ---------------------------------------------------------------------------


def _serve_cfg(**kw):
    base = dict(
        batch_buckets=[1, 2, 4], src_buckets=[16], mel_buckets=[64],
        frames_per_phoneme=2, max_wait_ms=40.0, queue_depth=64,
    )
    base.update(kw)
    return ServeConfig(**base)


class FakeEngine:
    """Engine stand-in: records dispatches, optional gate/failure."""

    class _Cfg:
        def __init__(self, serve):
            self.serve = serve

    def __init__(self, serve=None, gate=None, fail=None):
        self.cfg = self._Cfg(serve or _serve_cfg())
        self.lattice = BucketLattice.from_config(self.cfg.serve)
        self.dispatches = []  # (monotonic_time, [request ids])
        self.gate = gate      # threading.Event blocking the FIRST dispatch
        self.entered = threading.Event()  # set when the FIRST run() starts
        self.fail = fail      # exception instance to raise on every run
        self._first = True
        self.lock = threading.Lock()

    def admit(self, request):
        self.lattice.cover(1, len(request.sequence), 1)

    def run(self, requests):
        if self.gate is not None and self._first:
            self._first = False
            self.entered.set()
            self.gate.wait(timeout=10)
        if self.fail is not None:
            raise self.fail
        with self.lock:
            self.dispatches.append(
                (time.monotonic(), [r.id for r in requests])
            )
        return [f"result:{r.id}" for r in requests]


def _req(i, L=8):
    return SynthesisRequest(
        id=f"r{i}", sequence=np.ones(L, np.int32),
        ref_mel=np.zeros((4, 80), np.float32),
    )


def test_batcher_single_request_dispatches_within_max_wait():
    eng = FakeEngine(_serve_cfg(max_wait_ms=25.0))
    with ContinuousBatcher(eng) as b:
        t0 = time.monotonic()
        fut = b.submit(_req(0))
        assert fut.result(timeout=5) == "result:r0"
        dispatch_t, ids = eng.dispatches[0]
        # the lone request must not wait (noticeably) past max_wait
        assert dispatch_t - t0 <= 0.025 + 0.2
        assert ids == ["r0"]


def test_batcher_coalesces_backlog_into_one_dispatch():
    gate = threading.Event()
    eng = FakeEngine(_serve_cfg(max_wait_ms=5.0), gate=gate)
    with ContinuousBatcher(eng) as b:
        first = b.submit(_req(0))  # worker picks it up, blocks on the gate
        assert eng.entered.wait(timeout=5)
        backlog = [b.submit(_req(1 + i)) for i in range(3)]
        gate.set()
        assert first.result(timeout=5) == "result:r0"
        results = [f.result(timeout=5) for f in backlog]
    assert results == ["result:r1", "result:r2", "result:r3"]
    # the backlog coalesced into ONE dispatch (continuous batching),
    # possibly after the gated singleton
    assert [ids for _, ids in eng.dispatches] == [["r0"], ["r1", "r2", "r3"]]
    assert b.occupancy[3] == 1


def test_batcher_respects_max_batch_cap():
    gate = threading.Event()
    eng = FakeEngine(_serve_cfg(max_wait_ms=5.0), gate=gate)
    with ContinuousBatcher(eng) as b:
        futs = [b.submit(_req(i)) for i in range(9)]  # max_batch = 4
        gate.set()
        for f in futs:
            f.result(timeout=5)
    sizes = [len(ids) for _, ids in eng.dispatches]
    assert all(s <= 4 for s in sizes)
    assert sum(sizes) == 9


def test_batcher_requests_never_wait_past_deadline_when_idle():
    """Submit at a trickle slower than max_wait: every dispatch must start
    within max_wait (+scheduling slack) of its request's arrival."""
    eng = FakeEngine(_serve_cfg(max_wait_ms=20.0))
    arrivals = {}
    with ContinuousBatcher(eng) as b:
        futs = []
        for i in range(5):
            arrivals[f"r{i}"] = time.monotonic()
            futs.append(b.submit(_req(i)))
            time.sleep(0.06)  # > max_wait: each request rides alone
        for f in futs:
            f.result(timeout=5)
    for dispatch_t, ids in eng.dispatches:
        for rid in ids:
            assert dispatch_t - arrivals[rid] <= 0.020 + 0.2, (
                f"{rid} waited past its deadline"
            )


def test_batcher_engine_error_fails_only_that_batch():
    eng = FakeEngine(fail=ValueError("boom"))
    with ContinuousBatcher(eng) as b:
        fut = b.submit(_req(0))
        with pytest.raises(ValueError, match="boom"):
            fut.result(timeout=5)
        # the worker survives; later submits still get served
        eng.fail = None
        assert b.submit(_req(1)).result(timeout=5) == "result:r1"


def test_batcher_rejects_oversized_at_submit():
    eng = FakeEngine()
    with ContinuousBatcher(eng) as b:
        with pytest.raises(RequestTooLarge):
            b.submit(_req(0, L=17))  # src bucket max is 16
        # nothing was enqueued for it
        assert b.submit(_req(1)).result(timeout=5) == "result:r1"


def test_batcher_close_flushes_admitted_requests():
    gate = threading.Event()
    eng = FakeEngine(_serve_cfg(max_wait_ms=5.0), gate=gate)
    b = ContinuousBatcher(eng)
    futs = [b.submit(_req(i)) for i in range(6)]
    gate.set()
    b.close()  # flush=True: every admitted request resolves with a result
    assert [f.result(timeout=0) for f in futs] == [
        f"result:r{i}" for i in range(6)
    ]
    with pytest.raises(ShutdownError):
        b.submit(_req(99))


def test_batcher_close_noflush_fails_pending():
    gate = threading.Event()
    eng = FakeEngine(_serve_cfg(max_wait_ms=5.0), gate=gate)
    b = ContinuousBatcher(eng)
    first = b.submit(_req(0))
    # wait until [r0] is IN FLIGHT (inside engine.run) so the pending
    # submits below cannot coalesce into its batch
    assert eng.entered.wait(timeout=5)
    pending = [b.submit(_req(1 + i)) for i in range(3)]
    b_closer = threading.Thread(target=lambda: b.close(flush=False))
    b_closer.start()
    time.sleep(0.1)
    gate.set()
    b_closer.join(timeout=5)
    assert first.result(timeout=5) == "result:r0"  # in-flight completes
    for f in pending:
        with pytest.raises(ShutdownError):
            f.result(timeout=5)


def test_batcher_futures_resolve_exactly_once_under_racing_shutdown():
    """Hammer submit from several threads while another closes: every
    future that ``submit`` handed out resolves exactly once — with a
    result or ShutdownError — and none is left pending."""
    eng = FakeEngine(_serve_cfg(max_wait_ms=1.0, queue_depth=8))
    b = ContinuousBatcher(eng)
    futures = []
    flock = threading.Lock()
    stop = threading.Event()

    def submitter():
        i = 0
        while not stop.is_set():
            try:
                f = b.submit(_req(i))
            except ShutdownError:
                return
            except Overloaded:  # watermark shed under the hammer: back off
                time.sleep(0.001)
                continue
            with flock:
                futures.append(f)
            i += 1

    threads = [threading.Thread(target=submitter) for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.15)
    b.close()
    stop.set()
    for t in threads:
        t.join(timeout=5)

    assert futures, "no request was ever admitted"
    for f in futures:
        assert f.done(), "a submitted future was left pending"
        exc = f.exception(timeout=0)
        assert exc is None or isinstance(exc, ShutdownError)
    served = sum(1 for f in futures if f.exception(timeout=0) is None)
    dispatched = sum(len(ids) for _, ids in eng.dispatches)
    assert served == dispatched  # exactly-once: no result lost or duplicated


def test_batcher_dispatch_events_carry_req_ids(tmp_path):
    """The batcher's serve_dispatch JSONL record lists every request id
    in the coalesced batch — the join key that makes one request's
    records traceable through handler -> batcher -> engine."""
    from speakingstyle_tpu.obs import JsonlEventLog, read_events

    eng = FakeEngine(_serve_cfg(max_wait_ms=5.0))
    log = JsonlEventLog(str(tmp_path))
    with ContinuousBatcher(eng, events=log) as b:
        assert b.submit(_req(0)).result(timeout=5) == "result:r0"
    log.close()
    recs = list(read_events(str(tmp_path), event="serve_dispatch"))
    assert recs and recs[0]["req_ids"] == ["r0"]
    assert recs[0]["rows"] == 1 and recs[0]["duration_s"] >= 0


def test_batcher_stats_are_registry_views():
    """occupancy/dispatched/rejected are views of the registry — the
    snapshot a /metrics scrape sees and the attribute API agree."""
    eng = FakeEngine(_serve_cfg(max_wait_ms=5.0))
    with ContinuousBatcher(eng) as b:
        b.submit(_req(0)).result(timeout=5)
        snap = b.registry.snapshot()
        assert snap["counters"]["serve_batches_total"] == b.dispatched == 1
        assert snap["counters"]['serve_batch_occupancy_total{rows="1"}'] == 1
        assert b.occupancy[1] == 1
        lat = snap["histograms"]["serve_request_latency_seconds"]
        assert lat["count"] == 1 and lat["p50"] is not None


def test_fill_control_scalar_and_per_phoneme():
    # the engine leases the buffer from its pool pre-filled with the
    # neutral 1.0; _fill_control only writes the real rows' prefixes
    out = np.ones((3, 4), np.float32)
    _fill_control([2.0, np.asarray([3.0, 4.0], np.float32)], out)
    np.testing.assert_allclose(out[0], [2, 2, 2, 2])
    np.testing.assert_allclose(out[1], [3, 4, 1, 1])
    np.testing.assert_allclose(out[2], [1, 1, 1, 1])  # padding row neutral


# ---------------------------------------------------------------------------
# engine + server (tiny model, real jax)
# ---------------------------------------------------------------------------


def _tiny_cfg(**serve_kw):
    serve = dict(
        batch_buckets=[1, 2], src_buckets=[16], mel_buckets=[32],
        frames_per_phoneme=2, max_wait_ms=20.0,
        style=StyleConfig(ref_buckets=[32]),
    )
    serve.update(serve_kw)
    return Config(
        model=ModelConfig(
            transformer=TransformerConfig(
                encoder_layer=1, decoder_layer=1, encoder_hidden=16,
                decoder_hidden=16, conv_filter_size=16,
                conv_kernel_size=(3, 1),
            ),
            reference_encoder=ReferenceEncoderConfig(
                encoder_layer=1, encoder_head=2, encoder_hidden=16,
                conv_layer=1, conv_filter_size=16,
            ),
            variance_predictor=VariancePredictorConfig(filter_size=16),
            variance_embedding=VarianceEmbeddingConfig(n_bins=8),
            postnet_embedding_dim=16, postnet_layers=2,
            max_seq_len=48, compute_dtype="float32",
        ),
        serve=ServeConfig(**serve),
    )


@pytest.fixture(scope="module")
def tiny_engine():
    """One precompiled tiny engine shared by the e2e tests (the AOT
    precompile is the expensive part; sharing keeps tier-1 fast)."""
    import jax

    from speakingstyle_tpu.data.synthetic import pin_durations
    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.models.hifigan import Generator
    from speakingstyle_tpu.serving.engine import SynthesisEngine

    cfg = _tiny_cfg()
    model = build_model(cfg, n_position=49)
    variables = init_variables(model, cfg, jax.random.PRNGKey(0))
    # exactly 2 frames per phoneme, whatever the random weights predict:
    # real (nonzero) audio of a known length flows end-to-end
    variables = pin_durations(variables, 2)
    gen = Generator(
        upsample_rates=(2, 2), upsample_kernel_sizes=(4, 4),
        upsample_initial_channel=16, resblock_kernel_sizes=(3,),
        resblock_dilation_sizes=((1,),),
    )
    gparams = gen.init(
        jax.random.PRNGKey(0), np.zeros((1, 8, 80), np.float32)
    )["params"]
    engine = SynthesisEngine(cfg, variables, vocoder=(gen, gparams),
                             model=model)
    engine.precompile()
    return engine


def _mkreq(i, L=10, T=20, rng=None):
    rng = rng or np.random.default_rng(i)
    return SynthesisRequest(
        id=f"utt{i}",
        sequence=rng.integers(1, 300, L).astype(np.int32),
        ref_mel=rng.standard_normal((T, 80)).astype(np.float32),
    )


def test_engine_precompiled_full_lattice(tiny_engine):
    # 2 batch x 1 src x 1 mel acoustic points + 2 vocoder (b, t) pairs
    assert tiny_engine.compile_count == 4
    assert len(tiny_engine._acoustic) == len(tiny_engine.lattice) == 2


def test_serve_smoke_zero_compiles_after_warmup(tiny_engine):
    """The acceptance invariant: after warmup the serve loop performs
    ZERO XLA compiles, measured on the backend's own monitoring bus."""
    engine = tiny_engine
    compiles_before = engine.compile_count
    with ContinuousBatcher(engine) as batcher:
        # warmup: one dispatch per batch bucket
        for b in engine.lattice.batch_buckets:
            engine.run([_mkreq(900 + b * 10 + j) for j in range(b)])
        with CompileMonitor() as mon:
            futs = [batcher.submit(_mkreq(i)) for i in range(7)]
            results = [f.result(timeout=60) for f in futs]
    assert mon.count == 0, "the serve loop compiled after warmup"
    assert engine.compile_count == compiles_before
    # results scattered back to the right requests, audio rendered
    for i, r in enumerate(results):
        assert r.id == f"utt{i}"
        assert r.mel_len > 0          # biased duration predictor
        assert r.wav is not None and r.wav.dtype == np.int16
        assert r.wav.shape == (r.mel_len * 4,)  # tiny vocoder hop = 4
        assert r.mel.shape == (r.mel_len, 80)
        assert r.durations.shape == (10,)
    assert batcher.dispatched >= 1


def test_engine_batch_overflow_rejected_not_split(tiny_engine):
    """More requests than the largest batch bucket cannot form one
    dispatch — cover() refuses (the batcher's max_batch cap prevents this
    by construction; the engine still guards it)."""
    before = tiny_engine.compile_count
    with pytest.raises(RequestTooLarge):
        tiny_engine.cover([_mkreq(50), _mkreq(51), _mkreq(52)])
    assert tiny_engine.compile_count == before


def test_engine_compile_on_miss_is_counted(tiny_engine):
    """Without precompile, the first dispatch compiles (acoustic +
    vocoder) and the engine's counter says so — a lattice miss can never
    be a silent retrace."""
    from speakingstyle_tpu.serving.engine import SynthesisEngine
    from speakingstyle_tpu.serving.lattice import BucketLattice

    engine = SynthesisEngine(
        tiny_engine.cfg, tiny_engine.variables,
        vocoder=tiny_engine.vocoder,
        lattice=BucketLattice([1], [16], [32]),
        model=tiny_engine.model,
    )
    assert engine.compile_count == 0
    with CompileMonitor() as mon:
        engine.run([_mkreq(55)])
    assert engine.compile_count == 2  # acoustic + vocoder, counted
    assert mon.count >= 1             # and visible on the monitoring bus
    with CompileMonitor() as mon:
        engine.run([_mkreq(56)])      # warm now: zero compiles
    assert engine.compile_count == 2 and mon.count == 0


def test_engine_admit_rejects_oversized(tiny_engine):
    with pytest.raises(RequestTooLarge):
        tiny_engine.admit(_mkreq(0, L=17))  # src bucket max 16
    with pytest.raises(RequestTooLarge):
        tiny_engine.admit(_mkreq(0, L=4, T=40))  # mel bucket max 32


def test_engine_per_word_controls_change_output(tiny_engine):
    rng = np.random.default_rng(7)
    base = _mkreq(60, rng=rng)
    slow = SynthesisRequest(
        id="slow", sequence=base.sequence, ref_mel=base.ref_mel,
        d_control=2.0,
    )
    r_base, r_slow = (tiny_engine.run([base])[0], tiny_engine.run([slow])[0])
    assert r_slow.mel_len >= r_base.mel_len
    assert int(r_slow.durations.sum()) >= int(r_base.durations.sum())


def test_http_server_end_to_end(tiny_engine):
    from speakingstyle_tpu.serving.server import SynthesisServer, TextFrontend

    cfg = tiny_engine.cfg
    ref = np.random.default_rng(0).standard_normal((20, 80)).astype(np.float32)
    server = SynthesisServer(
        tiny_engine, TextFrontend(cfg, ref), host="127.0.0.1", port=0
    )
    host, port = server.address[:2]
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        conn = http.client.HTTPConnection(host, port, timeout=30)
        conn.request("POST", "/synthesize",
                     body=json.dumps({"text": "hi"}),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        assert resp.status == 200, body
        assert resp.getheader("Content-Type") == "audio/wav"
        assert body[:4] == b"RIFF" and body[8:12] == b"WAVE"

        conn.request("GET", "/healthz")
        health = json.loads(conn.getresponse().read())
        assert health["compile_count"] == tiny_engine.compile_count
        assert health["requests"] == 1
        assert sum(health["batch_occupancy"].values()) >= 1

        # malformed request -> structured 400, server stays up
        conn.request("POST", "/synthesize", body=json.dumps({}))
        resp = conn.getresponse()
        assert resp.status == 400 and b"text" in resp.read()
        conn.close()
    finally:
        server.shutdown()


def test_metrics_endpoint_and_req_id_join(tiny_engine, tmp_path):
    """GET /metrics serves Prometheus text from the engine registry —
    compile counters, queue depth, per-bucket dispatch latency — and the
    req_id minted by the HTTP handler joins its http_request event with
    the batcher's serve_dispatch event (and rides error responses too).
    /healthz must agree with the registry snapshot field-for-field: one
    accounting path."""
    from speakingstyle_tpu.obs import JsonlEventLog, read_events
    from speakingstyle_tpu.serving.server import SynthesisServer, TextFrontend

    ref = np.random.default_rng(0).standard_normal((20, 80)).astype(np.float32)
    log = JsonlEventLog(str(tmp_path))
    server = SynthesisServer(
        tiny_engine, TextFrontend(tiny_engine.cfg, ref),
        host="127.0.0.1", port=0, events=log,
        profile_dir=str(tmp_path / "prof"),
    )
    host, port = server.address[:2]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        conn = http.client.HTTPConnection(host, port, timeout=30)
        conn.request("POST", "/synthesize", body=json.dumps({"text": "hello"}))
        resp = conn.getresponse()
        req_id = resp.getheader("X-Request-Id")
        resp.read()
        assert resp.status == 200 and req_id

        conn.request("GET", "/metrics")
        m = conn.getresponse()
        text = m.read().decode()
        assert m.status == 200
        assert m.getheader("Content-Type").startswith("text/plain")
        assert "serve_compiles_total" in text
        assert "jax_backend_compiles_total" in text
        assert "serve_queue_depth" in text
        # per-bucket dispatch latency histogram (batch-1 covering bucket)
        assert 'serve_dispatch_seconds_bucket{bucket="b1.s16.m32"' in text
        assert 'serve_request_latency_seconds_count' in text
        # ProgramCard gauges minted at compile time, per lattice bucket
        assert 'serve_program_flops{bucket="b1.s16.m32",kind="acoustic"}' \
            in text
        assert 'serve_program_peak_bytes{bucket="b1.s16.m32",kind="acoustic"}' \
            in text
        # the dispatch above fed the achieved-FLOP/s (MFU-style) histogram
        assert 'serve_achieved_flops_per_sec_count{bucket="b1.s16.m32"}' \
            in text
        # persistent-cache counters from the jaxmon bridge (0 on a run
        # with no cache configured, but always exported)
        assert "jax_persistent_cache_hits_total" in text
        assert "jax_persistent_cache_requests_total" in text
        # process identity gauges, sampled at scrape
        assert "process_rss_bytes" in text
        assert "process_uptime_seconds" in text

        # /healthz is a view of the SAME snapshot
        conn.request("GET", "/healthz")
        health = json.loads(conn.getresponse().read())
        snap = server.registry.snapshot()
        assert health["compile_count"] == snap["counters"]["serve_compiles_total"]
        assert health["dispatches"] == snap["counters"]["serve_dispatches_total"]
        assert health["requests"] == snap["counters"]["serve_http_requests_total"]
        assert "queue_depth" in health and "backend_compiles" in health
        # build identity: every probe says WHAT is running
        assert health["build"]["jax"] and health["build"]["backend"]
        assert health["build"]["device_count"] >= 1

        # error responses carry the req_id too (joinable failures)
        conn.request("POST", "/synthesize", body=json.dumps({}))
        bad = conn.getresponse()
        err_id = bad.getheader("X-Request-Id")
        body = json.loads(bad.read())
        assert bad.status == 400 and body["id"] == err_id and err_id != req_id
        conn.close()
    finally:
        server.shutdown()
        log.close()
    (http_rec,) = [r for r in read_events(str(tmp_path), event="http_request")
                   if r["req_id"] == req_id]
    assert http_rec["status"] == 200 and http_rec["duration_s"] > 0
    (dispatch_rec,) = [
        r for r in read_events(str(tmp_path), event="serve_dispatch")
        if req_id in r["req_ids"]
    ]
    assert dispatch_rec["bucket"] == "b1.s16.m32"
    # the failed request produced an http_request event but no dispatch
    err_http = [r for r in read_events(str(tmp_path), event="http_request")
                if r["req_id"] == err_id]
    assert err_http and err_http[0]["status"] == 400
    assert not any(err_id in r["req_ids"] for r in
                   read_events(str(tmp_path), event="serve_dispatch"))


def test_engine_builds_program_cards_at_precompile(tiny_engine):
    """Every compiled executable carries a ProgramCard: one acoustic card
    per lattice point plus the vocoder (b, t) pairs, each with real
    numbers on CPU — and reading them never compiled anything."""
    progs = tiny_engine.programs()
    acoustic = [p for p in progs if p["name"].startswith("acoustic:")]
    vocoder = [p for p in progs if p["name"].startswith("vocoder:")]
    assert len(acoustic) == len(tiny_engine.lattice) == 2
    assert len(vocoder) == 2  # 2 batch buckets x 1 mel bucket
    from speakingstyle_tpu.serving.engine import bucket_label

    assert {p["name"] for p in acoustic} == {
        f"acoustic:{bucket_label(b)}" for b in tiny_engine.lattice.points()
    }
    for p in progs:
        assert p["flops"] > 0 and p["bytes_accessed"] > 0
        assert p["peak_bytes"] > 0 and p["partial"] is False
        json.dumps(p)
    # the bigger batch costs more FLOPs than the smaller one
    by_name = {p["name"]: p for p in acoustic}
    assert by_name["acoustic:b2.s16.m32"]["flops"] > \
        by_name["acoustic:b1.s16.m32"]["flops"]


def test_debug_programs_endpoint(tiny_engine):
    """GET /debug/programs dumps one JSON ProgramCard per compiled
    program, plus the build identity."""
    from speakingstyle_tpu.serving.server import SynthesisServer, TextFrontend

    server = SynthesisServer(
        tiny_engine, TextFrontend(tiny_engine.cfg, None),
        host="127.0.0.1", port=0,
    )
    host, port = server.address[:2]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        conn = http.client.HTTPConnection(host, port, timeout=30)
        conn.request("GET", "/debug/programs")
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200
        # engine programs first, then the style-encoder programs once
        assert body["programs"] == (
            tiny_engine.programs() + tiny_engine.style.programs()
        )
        assert len(body["programs"]) == (
            tiny_engine.compile_count + tiny_engine.style.compile_count
        )
        assert body["build"]["backend"]
        conn.close()
    finally:
        server.shutdown()


def test_debug_profile_endpoint(tiny_engine, tmp_path):
    """POST /debug/profile pulls a jax.profiler trace from the live
    process; bad parameters are structured 400s."""
    from speakingstyle_tpu.serving.server import SynthesisServer, TextFrontend

    server = SynthesisServer(
        tiny_engine, TextFrontend(tiny_engine.cfg, None),
        host="127.0.0.1", port=0, profile_dir=str(tmp_path / "prof"),
    )
    host, port = server.address[:2]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        conn = http.client.HTTPConnection(host, port, timeout=60)
        conn.request("POST", "/debug/profile?seconds=0.2")
        resp = conn.getresponse()
        out = json.loads(resp.read())
        assert resp.status == 200, out
        assert out["seconds"] == 0.2
        import os

        assert os.path.isdir(out["trace_dir"])

        conn.request("POST", "/debug/profile?seconds=bogus")
        resp = conn.getresponse()
        assert resp.status == 400 and b"seconds" in resp.read()

        conn.request("POST", "/debug/profile?seconds=999")
        resp = conn.getresponse()
        assert resp.status == 400 and b"(0, 60]" in resp.read()
        conn.close()
    finally:
        server.shutdown()


def test_render_result_writes_wav(tiny_engine, tmp_path):
    from speakingstyle_tpu.synthesis import render_result

    result = tiny_engine.run([_mkreq(70)])[0]
    path = render_result(result, tiny_engine.cfg, str(tmp_path))
    import scipy.io.wavfile

    sr, wav = scipy.io.wavfile.read(path)
    assert sr == 22050 and wav.dtype == np.int16
    assert len(wav) == result.mel_len * 4
