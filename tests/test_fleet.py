"""Fleet serving + chunked streaming (tier-1).

Four layers, mirroring the new subsystem:
  1. streaming math — receptive field, window plan (no jax);
  2. router scheduling — EDF ordering under contention, shed-vs-reject
     counter split, watermark hysteresis, drain — against fake engines
     (no jax, millisecond-fast);
  3. engine streaming — chunked reassembly equals the non-streaming wav
     bit-exactly modulo the overlap tail, over precompiled buckets only;
  4. multi-replica e2e — tiny real engines behind the router + HTTP
     server: readiness 503 -> 200, chunked /synthesize/stream, and the
     acceptance invariant that steady-state fleet serving performs ZERO
     XLA compiles on any replica.
"""

import dataclasses
import http.client
import json
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from speakingstyle_tpu.configs.config import (
    Config,
    FleetConfig,
    ModelConfig,
    ReferenceEncoderConfig,
    ServeConfig,
    StyleConfig,
    TransformerConfig,
    VarianceEmbeddingConfig,
    VariancePredictorConfig,
)
from speakingstyle_tpu.obs import MetricsRegistry
from speakingstyle_tpu.serving import streaming
from speakingstyle_tpu.serving.batcher import (
    ContinuousBatcher,
    Overloaded,
    ShutdownError,
)
from speakingstyle_tpu.serving.engine import CompileMonitor, SynthesisRequest
from speakingstyle_tpu.serving.fleet import (
    DRAINING,
    READY,
    STOPPED,
    WARMING,
    FleetRouter,
)
from speakingstyle_tpu.serving.lattice import BucketLattice, RequestTooLarge

# ---------------------------------------------------------------------------
# streaming math (no jax)
# ---------------------------------------------------------------------------


def test_receptive_field_tiny_and_flagship():
    from speakingstyle_tpu.models.hifigan import Generator

    tiny = Generator(
        upsample_rates=(2, 2), upsample_kernel_sizes=(4, 4),
        upsample_initial_channel=16, resblock_kernel_sizes=(3,),
        resblock_dilation_sizes=((1,),),
    )
    flagship = Generator()
    rf_tiny = streaming.receptive_field_frames(tiny)
    rf_flag = streaming.receptive_field_frames(flagship)
    assert 0 < rf_tiny < rf_flag  # more stages + bigger kernels = wider
    assert rf_flag < 64           # and still far below a lattice bucket
    # resolve_overlap: explicit config wins, 0 derives
    assert streaming.resolve_overlap(5, tiny) == 5
    assert streaming.resolve_overlap(0, tiny) == rf_tiny


def test_stream_plan_covers_exactly_once():
    for mel_len, window, overlap in [(24, 8, 7), (1, 8, 3), (17, 5, 2),
                                     (40, 40, 10)]:
        spans = list(streaming.stream_plan(mel_len, window, overlap))
        # emitted spans tile [0, mel_len) without gap or overlap
        assert spans[0][0] == 0 and spans[-1][1] == mel_len
        for (s0, e0, lo, hi), (s1, _, _, _) in zip(spans, spans[1:]):
            assert e0 == s1
        for s, e, lo, hi in spans:
            assert lo <= max(0, s - overlap) or lo == 0
            assert 0 <= lo <= s < e <= hi <= mel_len
    assert list(streaming.stream_plan(0, 8, 4)) == []


# ---------------------------------------------------------------------------
# router scheduling (fake engines — no jax)
# ---------------------------------------------------------------------------


def _fleet_cfg(**fleet_kw):
    fleet = dict(queue_depth=32, stream_window=8)
    fleet.update(fleet_kw)
    return Config(serve=ServeConfig(
        batch_buckets=[1], src_buckets=[16], mel_buckets=[64],
        frames_per_phoneme=2, max_wait_ms=5.0,
        fleet=FleetConfig(**fleet),
    ))


class FakeFleetEngine:
    """Replica stand-in: records dispatch order, optional gate."""

    def __init__(self, gate=None):
        self.dispatches = []      # request ids, in dispatch order
        self.gate = gate          # Event blocking the FIRST dispatch
        self.entered = threading.Event()
        self._first = True
        self.lock = threading.Lock()

    def precompile(self):
        return 0.0

    def run(self, requests):
        if self.gate is not None and self._first:
            self._first = False
            self.entered.set()
            self.gate.wait(timeout=10)
        with self.lock:
            self.dispatches.extend(r.id for r in requests)
        return [SimpleNamespace(id=r.id, bucket=None, mel_len=1)
                for r in requests]


def _req(i, L=8, T=4, **kw):
    return SynthesisRequest(
        id=f"r{i}", sequence=np.ones(L, np.int32),
        ref_mel=np.zeros((T, 80), np.float32), **kw,
    )


def test_router_edf_ordering_under_contention():
    """Interactive requests admitted AFTER a batch backlog still dispatch
    first: the pending heap orders by SLO deadline, not arrival."""
    gate = threading.Event()
    eng = FakeFleetEngine(gate=gate)
    router = FleetRouter(lambda reg: eng, _fleet_cfg(), replicas=1)
    assert router.wait_ready(timeout=10)
    futs = [router.submit(_req(0))]              # occupies the worker
    assert eng.entered.wait(timeout=10)
    # backlog: batch first, interactive afterwards — interactive still wins
    futs.append(router.submit(_req(1, priority="batch")))
    futs.append(router.submit(_req(2, priority="batch")))
    futs.append(router.submit(_req(3, priority="interactive")))
    futs.append(router.submit(_req(4, priority="interactive")))
    gate.set()
    for f in futs:
        f.result(timeout=10)
    router.close()
    # r0 was in flight; then EDF: interactive (earlier deadlines) before
    # batch, FIFO within a class
    assert eng.dispatches == ["r0", "r3", "r4", "r1", "r2"]


def test_router_shed_vs_reject_counters():
    """Backpressure sheds count serve_shed_total and raise Overloaded
    (429 + Retry-After); shutdown refusals count serve_rejected_total and
    raise ShutdownError — never the same counter."""
    reg = MetricsRegistry()
    gate = threading.Event()

    def factory(registry):
        gate.wait(timeout=30)   # hold the replica in WARMING: no dispatch
        return FakeFleetEngine()

    cfg = _fleet_cfg(queue_depth=4, shed_high_watermark=0.5,
                     shed_low_watermark=0.25, shed_retry_after_s=3.0)
    router = FleetRouter(factory, cfg, replicas=1, registry=reg)
    assert router.states() == {0: WARMING}
    futs, sheds = [], 0
    for i in range(6):
        try:
            futs.append(router.submit(_req(i)))
        except Overloaded as e:
            sheds += 1
            assert e.retry_after_s == 3.0
    assert sheds == 4  # depth 2 = high watermark of a 4-deep queue
    snap = reg.snapshot()["counters"]
    assert snap["serve_shed_total"] == 4
    assert snap["serve_rejected_total"] == 0
    gate.set()
    router.close(flush=False)
    with pytest.raises(ShutdownError):
        router.submit(_req(99))
    snap = reg.snapshot()["counters"]
    assert snap["serve_rejected_total"] == 1
    assert snap["serve_shed_total"] == 4  # unchanged by shutdown
    for f in futs:  # pending futures failed, not stranded
        assert isinstance(f.exception(timeout=5), ShutdownError)


def test_router_admission_validates_class_and_geometry():
    router = FleetRouter(lambda reg: FakeFleetEngine(), _fleet_cfg(),
                         replicas=1)
    with pytest.raises(ValueError, match="priority class"):
        router.submit(_req(0, priority="best-effort"))
    with pytest.raises(RequestTooLarge):
        router.submit(_req(1, L=17))  # src bucket max 16
    router.close()


def test_router_scale_to_drains_replicas():
    eng0, eng1 = FakeFleetEngine(), FakeFleetEngine()
    engines = [eng0, eng1]
    router = FleetRouter(lambda reg: engines.pop(0), _fleet_cfg(),
                         replicas=2)
    assert router.wait_ready(timeout=10, n=2)
    router.scale_to(1)
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        states = router.states()
        if states[1] in (DRAINING, STOPPED) and states[0] == READY:
            break
        time.sleep(0.01)
    assert router.states()[0] == READY
    assert router.states()[1] in (DRAINING, STOPPED)
    # the surviving replica still serves
    assert router.submit(_req(5)).result(timeout=10).id == "r5"
    router.close()
    assert all(s == STOPPED for s in router.states().values())


class _FakeBatcherEngine:
    """Minimal duck-typed engine for ContinuousBatcher (gate-able)."""

    class _Cfg:
        def __init__(self, serve):
            self.serve = serve

    def __init__(self, serve, gate=None):
        self.cfg = self._Cfg(serve)
        self.lattice = BucketLattice.from_config(serve)
        self.gate = gate
        self.entered = threading.Event()
        self._first = True

    def admit(self, request):
        self.lattice.cover(1, len(request.sequence), 1)

    def run(self, requests):
        if self.gate is not None and self._first:
            self._first = False
            self.entered.set()
            self.gate.wait(timeout=10)
        return [SimpleNamespace(id=r.id, bucket=None) for r in requests]


def test_batcher_shed_split_from_shutdown_reject():
    """The single-engine batcher carries the same split: watermark sheds
    raise Overloaded + count serve_shed_total; shutdown refusals raise
    ShutdownError + count serve_rejected_total."""
    gate = threading.Event()
    serve = ServeConfig(
        batch_buckets=[1, 2, 4], src_buckets=[16], mel_buckets=[64],
        frames_per_phoneme=2, max_wait_ms=5.0, queue_depth=4,
    )
    eng = _FakeBatcherEngine(serve, gate=gate)
    b = ContinuousBatcher(eng)
    first = b.submit(_req(0, T=1))
    assert eng.entered.wait(timeout=5)   # worker busy: queue accumulates
    sheds = 0
    for i in range(1, 6):
        try:
            b.submit(_req(i, T=1))
        except Overloaded:
            sheds += 1
    assert sheds > 0
    assert b.shed == sheds
    rejected_before = b.rejected
    gate.set()
    b.close()
    with pytest.raises(ShutdownError):
        b.submit(_req(99, T=1))
    assert b.rejected == rejected_before + 1
    assert b.shed == sheds  # shutdown does not touch the shed counter
    first.result(timeout=5)


# ---------------------------------------------------------------------------
# engine streaming + multi-replica e2e (tiny model, real jax)
# ---------------------------------------------------------------------------


def _tiny_cfg(**fleet_kw):
    fleet = dict(stream_window=8, queue_depth=32)
    fleet.update(fleet_kw)
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
        serve=ServeConfig(
            batch_buckets=[1, 2], src_buckets=[16], mel_buckets=[32],
            frames_per_phoneme=2, max_wait_ms=20.0,
            fleet=FleetConfig(**fleet),
            style=StyleConfig(ref_buckets=[32]),
        ),
    )


@pytest.fixture(scope="module")
def tiny_parts():
    """Model/weights/vocoder built once; engines (which own the compiled
    programs) are constructed per test/replica from these."""
    import jax

    from speakingstyle_tpu.data.synthetic import pin_durations
    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.models.hifigan import Generator

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
    return cfg, model, variables, gen, gparams


@pytest.fixture(scope="module")
def tiny_fleet_engine(tiny_parts):
    """One precompiled tiny engine shared by the streaming tests."""
    from speakingstyle_tpu.serving.engine import SynthesisEngine

    cfg, model, variables, gen, gparams = tiny_parts
    engine = SynthesisEngine(cfg, variables, vocoder=(gen, gparams),
                             model=model)
    engine.precompile()
    return engine


def _mkreq(i, L=10, T=20, **kw):
    rng = np.random.default_rng(i)
    return SynthesisRequest(
        id=f"utt{i}",
        sequence=rng.integers(1, 300, L).astype(np.int32),
        ref_mel=rng.standard_normal((T, 80)).astype(np.float32),
        **kw,
    )


def test_stream_reassembly_bit_exact_modulo_overlap(tiny_fleet_engine):
    """Chunked windows concatenated == the non-streaming wav, bit for
    bit, up to the final overlap tail (where the full vocode sees the
    acoustic model's past-end free-run frames and the stream sees
    silence) — and the whole stream performs ZERO compiles."""
    engine = tiny_fleet_engine
    gen, _ = engine.vocoder
    hop = gen.hop_factor
    window = engine.cfg.serve.fleet.stream_window
    overlap = streaming.resolve_overlap(
        engine.cfg.serve.fleet.stream_overlap, gen
    )
    full = engine.run([_mkreq(40)])[0]
    sres = engine.run([_mkreq(40, stream=True)])[0]
    assert sres.wav is None and sres.mel_len == full.mel_len
    with CompileMonitor() as mon:
        chunks = list(streaming.stream_wav(engine, sres, window, overlap))
    assert mon.count == 0, "streaming compiled in steady state"
    assert len(chunks) == -(-full.mel_len // window)
    wav = np.concatenate(chunks)
    assert wav.dtype == np.int16 and wav.shape == (full.mel_len * hop,)
    head = (full.mel_len - overlap) * hop
    assert head > 0
    np.testing.assert_array_equal(wav[:head], full.wav[:head])


def test_vocode_window_rejects_bad_shapes(tiny_fleet_engine):
    with pytest.raises(ValueError, match="mel window"):
        tiny_fleet_engine.vocode_window(np.zeros((4, 3), np.float32))
    with pytest.raises(RequestTooLarge):
        tiny_fleet_engine.vocode_window(np.zeros((33, 80), np.float32))


def test_multi_replica_e2e_zero_steady_state_compiles(tiny_parts):
    """The acceptance invariant at fleet scale: two replicas, mixed
    stream/non-stream traffic, and after per-replica warmup the backend
    monitoring bus sees ZERO compiles — each replica serves purely from
    its own precompiled lattice."""
    from speakingstyle_tpu.serving.engine import SynthesisEngine

    cfg, model, variables, gen, gparams = tiny_parts
    reg = MetricsRegistry()

    def factory(registry):
        return SynthesisEngine(cfg, variables, vocoder=(gen, gparams),
                               model=model, registry=registry)

    with FleetRouter(factory, cfg, replicas=2, registry=reg) as router:
        assert router.wait_ready(timeout=300, n=2)
        engines = router.engines()
        assert len(engines) == 2
        for engine in engines:
            assert engine.is_ready
            # warmup: first-execution transfer per batch bucket (the
            # compiles all happened in precompile)
            for b in engine.lattice.batch_buckets:
                engine.run([_mkreq(800 + b * 10 + j) for j in range(b)])
        compiles_before = [len(e._acoustic) + len(e._vocoder_exe)
                           for e in engines]
        total_before = reg.value("serve_compiles_total")
        with CompileMonitor() as mon:
            futs = [router.submit(_mkreq(i, stream=(i % 2 == 0)))
                    for i in range(8)]
            results = [f.result(timeout=120) for f in futs]
            for i, r in enumerate(results):
                assert r.id == f"utt{i}"
                if i % 2 == 0:
                    t0 = time.monotonic()
                    wav = np.concatenate(
                        list(router.stream(r, arrival=t0)))
                    assert wav.shape == (r.mel_len * 4,)
                else:
                    assert r.wav is not None
        assert mon.count == 0, "the fleet compiled after warmup"
        # per replica: the program tables did not grow
        assert [len(e._acoustic) + len(e._vocoder_exe)
                for e in engines] == compiles_before
        assert reg.value("serve_compiles_total") == total_before
        # both replicas actually served work and TTFA was recorded
        snap = reg.snapshot()["counters"]
        served = [v for k, v in snap.items()
                  if k.startswith("serve_replica_requests_total")]
        assert sum(served) >= 8
        assert reg.histogram("serve_ttfa_seconds").count >= 4
    assert all(s == STOPPED for s in router.states().values())


def test_fleet_http_readiness_streaming_and_drain(tiny_parts):
    """HTTP layer over the router: /healthz is 503 with replica states
    while warming and 200 once ready; /synthesize/stream returns chunked
    audio/wav whose PCM reassembles to the batch wav; shutdown drains
    in-flight streams."""
    from speakingstyle_tpu.serving.engine import SynthesisEngine
    from speakingstyle_tpu.serving.server import SynthesisServer, TextFrontend

    cfg, model, variables, gen, gparams = tiny_parts
    gate = threading.Event()

    def factory(registry):
        gate.wait(timeout=60)
        return SynthesisEngine(cfg, variables, vocoder=(gen, gparams),
                               model=model, registry=registry)

    router = FleetRouter(factory, cfg, replicas=1,
                         registry=MetricsRegistry())
    ref = np.random.default_rng(0).standard_normal((20, 80)).astype(np.float32)
    server = SynthesisServer(
        frontend=TextFrontend(cfg, ref), host="127.0.0.1", port=0,
        router=router,
    )
    host, port = server.address[:2]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        conn = http.client.HTTPConnection(host, port, timeout=120)
        conn.request("GET", "/healthz")
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 503 and body["ready"] is False
        assert body["replicas"] == {"0": WARMING}

        gate.set()
        assert router.wait_ready(timeout=300)
        conn.request("GET", "/healthz")
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200 and body["ready"] is True
        assert body["replicas"] == {"0": READY}
        assert "shed" in body and "rejected" in body

        payload = json.dumps({"text": "stream me", "priority": "batch"})
        conn.request("POST", "/synthesize", body=payload)
        resp = conn.getresponse()
        full = resp.read()
        assert resp.status == 200 and full[:4] == b"RIFF"

        conn.request("POST", "/synthesize/stream", body=payload)
        resp = conn.getresponse()
        streamed = resp.read()  # http.client reassembles the chunks
        assert resp.status == 200
        assert resp.getheader("Transfer-Encoding") == "chunked"
        assert resp.getheader("X-Request-Id")
        assert streamed[:4] == b"RIFF"
        a = np.frombuffer(full[44:], np.int16)
        b = np.frombuffer(streamed[44:], np.int16)
        assert a.shape == b.shape
        overlap = streaming.resolve_overlap(cfg.serve.fleet.stream_overlap,
                                            gen)
        head = len(a) - overlap * gen.hop_factor
        np.testing.assert_array_equal(a[:head], b[:head])
        conn.close()

        # drain: a held stream scope blocks shutdown's drain until
        # released (the SIGTERM contract)
        release = threading.Event()

        def held_stream():
            with server.stream_scope():
                release.wait(timeout=30)

        t = threading.Thread(target=held_stream, daemon=True)
        t.start()
        time.sleep(0.05)
        assert server.drain_streams(timeout=0.1) is False
        release.set()
        t.join(timeout=5)
        assert server.drain_streams(timeout=5) is True
    finally:
        release.set()
        gate.set()
        server.shutdown()


def test_fleet_config_validation():
    with pytest.raises(ValueError, match="watermarks"):
        FleetConfig(shed_high_watermark=0.3, shed_low_watermark=0.5)
    with pytest.raises(ValueError, match="replicas"):
        FleetConfig(replicas=0)
    with pytest.raises(ValueError, match="default_class"):
        FleetConfig(default_class="turbo")
    with pytest.raises(ValueError, match="class_deadline_ms"):
        FleetConfig(class_deadline_ms={"interactive": -1.0})
    with pytest.raises(ValueError, match="stream_window"):
        FleetConfig(stream_window=0)
    # the serve.fleet.* block rides train.yaml like the rest of serve.*
    cfg = FleetConfig(replicas=4, class_deadline_ms={"rt": 50.0},
                      default_class="rt")
    assert cfg.class_deadline_ms["rt"] == 50.0
