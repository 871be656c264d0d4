"""Audio-quality observability plane (tier-1).

Six layers, mirroring the subsystem:
  1. validator verdict matrix — ``validate_wav`` against every reason
     in the bounded vocabulary (pure numpy, no jax);
  2. gate accounting — counters, the quality SLO stream, the
     ``quality_fail`` KEEP_REASON trace pin, last-fail record, events;
  3. longform stitcher choke point — every emitted piece validated;
  4. golden probes — anchor pin/load with digest verification, drift
     math, the edge-triggered page, probe errors staying OUT of the
     quality stream (fake router, no jax);
  5. SLO quality stream — burn-rate windows and the edge-triggered
     ``slo_quality_alert`` carrying the pinned exemplar trace;
  6. probe isolation + degradation drill — probe traffic invisible to
     the autoscaler's pressure signals and the latency SLO counters;
     ``tier_poison`` wiring through the fleet fault block; and the
     end-to-end drill on a real tiny engine: poisoned params keep
     serving with ZERO compiles while the validators and the prober
     both catch the garbage.
"""

import dataclasses
import threading
import time
from concurrent.futures import Future
from types import SimpleNamespace

import numpy as np
import pytest

from speakingstyle_tpu.configs.config import (
    Config,
    FleetConfig,
    ModelConfig,
    QualityConfig,
    ReferenceEncoderConfig,
    ServeConfig,
    SloConfig,
    StyleConfig,
    TransformerConfig,
    VarianceEmbeddingConfig,
    VariancePredictorConfig,
)
from speakingstyle_tpu.faults import FaultPlan
from speakingstyle_tpu.obs import MetricsRegistry
from speakingstyle_tpu.obs.quality import (
    QUALITY_REASONS,
    QualityGate,
    last_fail,
    validate_wav,
)
from speakingstyle_tpu.obs.slo import SloEngine
from speakingstyle_tpu.obs.trace import SpanRing, TailSampler
from speakingstyle_tpu.serving.engine import SynthesisRequest
from speakingstyle_tpu.serving.fleet import FleetRouter
from speakingstyle_tpu.serving.longform import Stitcher
from speakingstyle_tpu.serving.probes import (
    GoldenProber,
    load_anchors,
    pin_anchors,
    probe_targets,
)

SR = 22050


def _qcfg(**kw):
    return QualityConfig(**kw)


def _speechlike(n=4096, seed=0):
    """A plausible healthy wav: a pitch-ish tone under broadband noise,
    well below full scale — must pass every validator."""
    rng = np.random.default_rng(seed)
    t = np.arange(n) / SR
    x = 0.3 * np.sin(2 * np.pi * 220 * t) + 0.05 * rng.standard_normal(n)
    return (x * 8000).astype(np.int16)


class _EventSink:
    def __init__(self):
        self.records = []

    def emit(self, event, **fields):
        self.records.append(dict(fields, event=event))


# ---------------------------------------------------------------------------
# 1. validator verdict matrix (no jax)
# ---------------------------------------------------------------------------


def test_validate_wav_passes_healthy_audio():
    v = validate_wav(_speechlike(), SR, _qcfg())
    assert v.ok and v.reasons == ()
    # white noise is the adversarial healthy case for flatness: ~0.56
    # on a single periodogram, which must stay under the 0.9 bar
    rng = np.random.default_rng(1)
    noise = (rng.standard_normal(8192) * 6000).astype(np.int16)
    v = validate_wav(noise, SR, _qcfg())
    assert v.ok
    assert 0.3 < v.flatness < 0.9


def test_validate_wav_non_finite_needs_the_float_hint():
    # int16 samples cannot carry NaN: the engine's pre-conversion
    # verdict arrives via finite= and must override
    wav = _speechlike()
    v = validate_wav(wav, SR, _qcfg(), finite=False)
    assert not v.ok and "non_finite" in v.reasons
    # float input self-checks when no hint is given
    f = np.zeros(2048, np.float32)
    f[100] = np.nan
    f[200:300] = 0.1  # keep the zero-run short of the silence bar
    v = validate_wav(f + 0.01, SR, _qcfg())
    assert "non_finite" in v.reasons


def test_validate_wav_clipping_silence_dc_flatness():
    q = _qcfg(clip_fraction_max=0.5, silence_run_ms_max=100.0,
              dc_offset_max=0.5, flatness_max=0.9)
    railed = np.full(2048, 32767, np.int16)
    v = validate_wav(railed, SR, q)
    assert not v.ok
    assert "clipping" in v.reasons and v.clip_fraction == pytest.approx(1.0)
    assert "dc_offset" in v.reasons   # a rail is also pure offset
    assert "flatness" in v.reasons    # and spectrally degenerate

    dead = _speechlike(3 * SR // 4).copy()
    dead[1000:1000 + SR // 4] = 0     # 250 ms of digital silence
    v = validate_wav(dead, SR, q)
    assert not v.ok and "silence" in v.reasons
    assert v.silence_run_ms == pytest.approx(250.0, rel=0.05)

    dc = (_speechlike() * 0).astype(np.int16) + 20000
    v = validate_wav(dc, SR, q)
    assert "dc_offset" in v.reasons

    assert set(QUALITY_REASONS) >= set(v.reasons)


def test_validate_wav_short_and_empty_edges():
    q = _qcfg(flatness_min_samples=256)
    # below flatness_min_samples the spectrum check is skipped — a
    # 100-sample constant burst must not page on flatness
    short = np.full(100, 5000, np.int16)
    v = validate_wav(short, SR, q)
    assert "flatness" not in v.reasons and v.flatness == 0.0
    v = validate_wav(np.zeros(0, np.int16), SR, q)
    assert v.ok  # empty = nothing to judge


# ---------------------------------------------------------------------------
# 2. gate accounting: counters, SLO stream, trace pin, last-fail
# ---------------------------------------------------------------------------


def test_quality_fail_is_a_keep_reason():
    assert "quality_fail" in TailSampler.KEEP_REASONS


def test_gate_accounts_verdicts_and_pins_the_trace():
    reg = MetricsRegistry()
    sink = _EventSink()
    ring = SpanRing(capacity=16, keep_traces=4)
    ring.add({"span_id": "s1", "trace_id": "t-bad", "name": "serve_request"})
    gate = QualityGate(_qcfg(), SR, registry=reg, events=sink, tier="t0",
                       trace_ring=ring, tail_sampler=TailSampler(0.0))

    ok = gate.check(_speechlike(), klass="interactive", req_id="good")
    assert ok.ok
    bad = gate.check(np.full(2048, 32767, np.int16), klass="interactive",
                     trace="t-bad", req_id="r-bad")
    assert not bad.ok

    assert reg.value("serve_quality_checks_total",
                     {"class": "interactive", "tier": "t0",
                      "source": "engine"}) == 2
    # the SLO good/bad stream the burn-rate engine differentiates
    assert reg.value("serve_quality_class_total",
                     {"class": "interactive"}) == 2
    assert reg.value("serve_quality_class_fail_total",
                     {"class": "interactive"}) == 1
    for reason in bad.reasons:
        assert reg.value(
            "serve_quality_fail_total",
            {"class": "interactive", "tier": "t0", "reason": reason},
        ) == 1
    # the failing wav pinned its trace exactly like a latency incident
    assert "t-bad" in ring.kept_trace_ids()
    assert ring.last_pinned_trace_id == "t-bad"
    lf = last_fail()
    assert lf is not None and lf["req_id"] == "r-bad"
    assert lf["trace_id"] == "t-bad" and lf["tier"] == "t0"
    ev = [r for r in sink.records if r["event"] == "quality_fail"]
    assert len(ev) == 1 and ev[0]["req_id"] == "r-bad"


def test_gate_record_false_and_disabled_paths():
    reg = MetricsRegistry()
    gate = QualityGate(_qcfg(), SR, registry=reg)
    # record=False (the HTTP boundary re-check): verdict computed,
    # process tallies bumped, but NO metric/SLO/event planes touched
    v = gate.check(np.full(2048, 32767, np.int16), klass="interactive",
                   record=False)
    assert not v.ok
    assert gate.status() == {"enabled": True, "checked": 1, "failed": 1}
    assert reg.value("serve_quality_class_total",
                     {"class": "interactive"}) == 0
    # a disabled gate is a no-op that always passes
    off = QualityGate(_qcfg(enabled=False), SR, registry=reg)
    assert off.check(np.full(64, 32767, np.int16)).ok
    assert off.status()["enabled"] is False


def test_gate_check_result_reuses_the_engine_verdict():
    gate = QualityGate(_qcfg(), SR)
    sentinel = object()
    assert gate.check_result(SimpleNamespace(quality=sentinel)) is sentinel
    assert gate.check_result(
        SimpleNamespace(quality=None, wav=None)) is None  # mel-only
    v = gate.check_result(SimpleNamespace(
        quality=None, wav=np.full(2048, 32767, np.int16), priority=None,
        tier=None, trace=None, id="x"))
    assert v is not None and not v.ok


# ---------------------------------------------------------------------------
# 3. longform stitcher choke point
# ---------------------------------------------------------------------------


def test_stitcher_validates_every_emitted_piece():
    reg = MetricsRegistry()
    gate = QualityGate(_qcfg(silence_run_ms_max=10.0), SR, registry=reg)
    st = Stitcher(
        4, quality_check=lambda w: gate.check(w, klass="batch",
                                              source="longform"),
    )
    pieces = st.feed(_speechlike(1024, seed=2))
    pieces += st.feed(np.zeros(1024, np.int16))  # a dead chunk
    pieces += st.finish()
    n = reg.value("serve_quality_checks_total",
                  {"class": "batch", "tier": "default",
                   "source": "longform"})
    assert n == len(pieces) > 0
    assert reg.value("serve_quality_class_fail_total",
                     {"class": "batch"}) >= 1  # the dead chunk was caught


# ---------------------------------------------------------------------------
# 4. golden probes: anchors, drift, the edge (fake router — no jax)
# ---------------------------------------------------------------------------


def _probe_cfg(**qkw):
    q = dict(probe_mel_tolerance=0.5, probe_style_tolerance=0.5,
             probe_interval_s=0.01)
    q.update(qkw)
    return Config(serve=ServeConfig(
        batch_buckets=[1, 2, 4], src_buckets=[16], mel_buckets=[64],
        frames_per_phoneme=2, max_wait_ms=5.0,
        style=StyleConfig(ref_buckets=[32]),
        quality=QualityConfig(**q),
    ))


class _CannedRouter:
    """Fake single-tier router: deterministic mel per request id, with
    a mutable ``scale`` (drift injection) and ``boom`` (availability
    failure injection)."""

    tier = "t0"

    def __init__(self):
        self.scale = 1.0
        self.boom = False
        self.submitted = []

    def _mel(self, req):
        rng = np.random.default_rng(abs(hash(req.id)) % 2**31)
        return (rng.standard_normal((24, 80)).astype(np.float32)
                * self.scale)

    def submit(self, req):
        self.submitted.append(req)
        fut = Future()
        if self.boom:
            fut.set_exception(RuntimeError("replica unreachable"))
        else:
            fut.set_result(SimpleNamespace(mel=self._mel(req), mel_len=24))
        return fut


class _CannedStyle:
    """Fake StyleService: encode_live only (the prober must never touch
    the cache-inserting paths)."""

    def __init__(self):
        self.scale = 1.0

    def encode_live(self, mel, speaker=None):
        base = np.asarray(mel, np.float32).mean(axis=0)[:8]
        return SimpleNamespace(gamma=base * self.scale,
                               beta=-base * self.scale)


def test_probe_targets_shapes():
    r = _CannedRouter()
    assert probe_targets(r) == [("t0", r)]
    tiered = SimpleNamespace(tiers=lambda: ["a", "b"],
                             router_for=lambda t: t + "!")
    assert probe_targets(tiered) == [("a", "a!"), ("b", "b!")]


def test_anchor_pin_load_and_digest_verification(tmp_path):
    cfg = _probe_cfg()
    router = _CannedRouter()
    style = _CannedStyle()
    d = str(tmp_path / "anchors")
    manifest = pin_anchors(router, cfg, d, style=style)
    size = cfg.serve.tiers.golden_set_size
    assert len(manifest["tiers"]["t0"]) == size
    assert len(manifest["style"]) == size
    # every probe rode the probe class, never a tenant class
    assert {r.priority for r in router.submitted} == {"probe"}

    m2, mels, styles = load_anchors(d)
    assert set(mels["t0"]) == set(manifest["tiers"]["t0"])
    assert all(g.shape == b.shape for g, b in styles.values())

    # corrupt one anchor on disk: load must refuse, not re-baseline
    gid = sorted(mels["t0"])[0]
    path = tmp_path / "anchors" / "t0" / f"{gid}.npz"
    np.savez(path, mel=np.zeros((24, 80), np.float32))
    with pytest.raises(ValueError, match="digest mismatch"):
        load_anchors(d)


def test_prober_drift_edge_and_quality_stream(tmp_path):
    cfg = _probe_cfg()
    reg = MetricsRegistry()
    sink = _EventSink()
    router = _CannedRouter()
    style = _CannedStyle()
    prober = GoldenProber(router, cfg, style=style, registry=reg,
                          events=sink, anchor_dir=str(tmp_path),
                          start=False)
    prober.pin()
    size = cfg.serve.tiers.golden_set_size

    # healthy round: drift 0, the probe class's good stream grows
    s = prober.probe_once()
    assert s["tiers"]["t0"]["mel_drift"] == pytest.approx(0.0)
    assert s["style_drift"] == pytest.approx(0.0)
    # no edge has fired yet: the alerting map carries no keys
    assert not any(prober.alerting().values())
    assert reg.value("serve_quality_class_total",
                     {"class": "probe"}) == 2 * size  # mel + style legs
    assert reg.value("serve_quality_class_fail_total",
                     {"class": "probe"}) == 0
    assert reg.value("serve_probe_total",
                     {"tier": "t0", "outcome": "ok"}) == size

    # drifted fleet: edge fires ONCE, stream counts bad, gauges move
    router.scale = 10.0
    style.scale = 10.0
    s = prober.probe_once()
    assert s["tiers"]["t0"]["mel_drift"] > cfg.serve.quality.probe_mel_tolerance
    assert prober.alerting() == {"t0": True, "style": True}
    assert reg.value("serve_probe_drift_alerts_total", {"tier": "t0"}) == 1
    assert reg.value("serve_quality_class_fail_total",
                     {"class": "probe"}) == 2 * size
    prober.probe_once()  # sustained drift: edge-triggered, no re-count
    assert reg.value("serve_probe_drift_alerts_total", {"tier": "t0"}) == 1
    assert [r["event"] for r in sink.records
            if r["event"].startswith("probe_drift")] \
        == ["probe_drift_alert", "probe_drift_alert"]  # t0 + style

    # recovery resolves the edge
    router.scale = 1.0
    style.scale = 1.0
    prober.probe_once()
    assert prober.alerting() == {"t0": False, "style": False}
    assert "probe_drift_resolved" in [r["event"] for r in sink.records]

    st = prober.status()
    assert st["pinned"] and st["rounds"] == 4
    assert st["tiers"]["t0"]["alerting"] is False
    assert st["last_unix_ts"] <= time.time()


def test_probe_errors_stay_out_of_the_quality_stream(tmp_path):
    # availability failures are the chaos plane's problem: they count
    # as probe errors, never as quality stream bad (no false page on a
    # flaky replica)
    cfg = _probe_cfg()
    reg = MetricsRegistry()
    sink = _EventSink()
    router = _CannedRouter()
    prober = GoldenProber(router, cfg, registry=reg, events=sink,
                          anchor_dir=str(tmp_path), start=False)
    prober.pin()
    before = reg.value("serve_quality_class_total", {"class": "probe"})
    router.boom = True
    s = prober.probe_once()
    assert s["tiers"]["t0"]["outcomes"]["error"] \
        == cfg.serve.tiers.golden_set_size
    assert reg.value("serve_quality_class_total",
                     {"class": "probe"}) == before
    assert reg.value("serve_quality_class_fail_total",
                     {"class": "probe"}) == 0
    assert prober.alerting().get("t0", False) is False
    assert all(r["stage"] == "result" for r in sink.records
               if r["event"] == "probe_error")


def test_prober_requires_an_anchor_dir():
    with pytest.raises(ValueError, match="anchor_dir"):
        GoldenProber(_CannedRouter(), _probe_cfg(), start=False)


# ---------------------------------------------------------------------------
# 5. SLO quality stream: burn windows + edge-triggered page
# ---------------------------------------------------------------------------


def test_slo_quality_stream_pages_and_carries_the_pinned_trace():
    reg = MetricsRegistry()
    sink = _EventSink()
    ring = SpanRing(capacity=16, keep_traces=4)
    ring.add({"span_id": "s1", "trace_id": "t-garbage"})
    ring.pin("t-garbage")  # what a failing validator just did
    scfg = SloConfig(
        objectives={"interactive": 0.999},
        quality_objectives={"interactive": 0.99, "probe": 0.99},
        fast_window_s=60.0, slow_window_s=600.0,
        fast_burn_threshold=14.4, slow_burn_threshold=6.0, tick_s=5.0,
    )
    eng = SloEngine(reg, scfg, events=sink, trace_ring=ring, start=False)
    total = reg.counter("serve_quality_class_total",
                        labels={"class": "interactive"})
    bad = reg.counter("serve_quality_class_fail_total",
                      labels={"class": "interactive"})
    t0 = 1000.0
    total.inc(1000)
    eng.step(now=t0)
    assert eng.quality_alerting() == {"interactive": False, "probe": False}

    # 300 garbage wavs over 1000: ratio 0.3 over a 1% budget = burn 30
    total.inc(1000)
    bad.inc(300)
    eng.step(now=t0 + 30.0)
    assert eng.quality_alerting()["interactive"] is True
    assert eng.quality_burn_rate("interactive", "fast") \
        == pytest.approx(30.0)
    assert reg.value("serve_slo_quality_burn_rate",
                     {"class": "interactive", "window": "fast"}) \
        == pytest.approx(30.0)
    assert reg.value("serve_slo_quality_alerts_total",
                     {"class": "interactive"}) == 1
    alert = [r for r in sink.records if r["event"] == "slo_quality_alert"]
    assert len(alert) == 1 and alert[0]["klass"] == "interactive"
    assert alert[0]["trace_id"] == "t-garbage"  # jump-to-trace handle
    # the latency stream did NOT page: quality is its own stream
    assert eng.step(now=t0 + 35.0) == {"interactive": False}
    assert len([r for r in sink.records
                if r["event"] == "slo_quality_alert"]) == 1  # edge

    # clean wavs push the bad sample past both windows: resolved
    total.inc(100_000)
    eng.step(now=t0 + 400.0)
    eng.step(now=t0 + 700.0)
    assert eng.quality_alerting()["interactive"] is False
    assert sink.records[-1]["event"] == "slo_quality_resolved"
    qs = eng.quality_status()["interactive"]
    assert qs["objective"] == 0.99 and qs["alerting"] is False


# ---------------------------------------------------------------------------
# 6. probe isolation from the autoscaler + tier_poison wiring
# ---------------------------------------------------------------------------


def _fleet_cfg(**fleet_kw):
    fleet = dict(queue_depth=32, stream_window=8)
    fleet.update(fleet_kw)
    return Config(serve=ServeConfig(
        batch_buckets=[1], src_buckets=[16], mel_buckets=[64],
        frames_per_phoneme=2, max_wait_ms=5.0,
        fleet=FleetConfig(**fleet),
    ))


class _FakeEngine:
    """Replica stand-in; optional gate blocks the FIRST dispatch."""

    def __init__(self, gate=None):
        self.dispatches = []
        self.gate = gate
        self.entered = threading.Event()
        self._first = True
        self.poisoned = False

    def precompile(self):
        return 0.0

    def poison_params(self, precision=None, scale=1e3):
        self.poisoned = True
        return precision or "float32"

    def run(self, requests):
        if self.gate is not None and self._first:
            self._first = False
            self.entered.set()
            self.gate.wait(timeout=10)
        self.dispatches.extend(r.id for r in requests)
        return [SimpleNamespace(id=r.id, bucket=None, mel_len=1)
                for r in requests]


def _req(i, **kw):
    return SynthesisRequest(
        id=f"r{i}", sequence=np.ones(8, np.int32),
        ref_mel=np.zeros((4, 80), np.float32), **kw,
    )


def test_probe_class_is_invisible_to_autoscaler_signals():
    reg = MetricsRegistry()
    gate = threading.Event()
    eng = _FakeEngine(gate=gate)
    router = FleetRouter(lambda r: eng, _fleet_cfg(), replicas=1,
                         registry=reg)
    try:
        assert router.wait_ready(timeout=10)
        futs = [router.submit(_req(0, priority="probe"))]
        assert eng.entered.wait(timeout=10)  # probe-only in-flight claim
        futs.append(router.submit(_req(1, priority="probe")))
        futs.append(router.submit(_req(2, priority="probe")))
        futs.append(router.submit(_req(3, priority="interactive")))
        # heap holds 2 probes + 1 tenant: the autoscaler's queue signal
        # sees ONLY the tenant; a probe-only claim is not "busy"
        assert router.pending_depth() == 1
        assert router.occupancy() == 0.0
        gate.set()
        for f in futs:
            f.result(timeout=10)
        # probes on their own admission family, never the tenant's
        assert reg.value("serve_probe_requests_total") == 3
        assert reg.value("serve_class_requests_total",
                         {"class": "probe"}) == 0
        assert reg.value("serve_class_requests_total",
                         {"class": "interactive"}) == 1
    finally:
        gate.set()
        router.close()


def test_tier_poison_fault_poisons_in_place_and_keeps_serving():
    eng = _FakeEngine()
    plan = FaultPlan()
    router = FleetRouter(lambda r: eng, _fleet_cfg(), replicas=1,
                         fault_plan=plan)
    try:
        assert router.wait_ready(timeout=10)
        router.submit(_req(0)).result(timeout=10)
        assert eng.poisoned is False
        plan.arm("tier_poison", router.dispatch_total + 1)
        # the poisoning dispatch SUCCEEDS — no raise, no failover, the
        # audio is garbage only the quality plane can see
        router.submit(_req(1)).result(timeout=10)
        assert eng.poisoned is True
        assert router.states() == {0: "ready"}
        router.submit(_req(2)).result(timeout=10)  # still serving
        assert eng.dispatches == ["r0", "r1", "r2"]
    finally:
        router.close()


# ---------------------------------------------------------------------------
# 7. the degradation drill on a real tiny engine (jax, module-scoped)
# ---------------------------------------------------------------------------


def _tiny_cfg():
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
            batch_buckets=[1], src_buckets=[16], mel_buckets=[32],
            frames_per_phoneme=2, max_wait_ms=5.0,
            style=StyleConfig(ref_buckets=[32]),
        ),
    )


@pytest.fixture(scope="module")
def tiny_engine():
    """(cfg, registry, engine): one precompiled tiny engine shared by
    the real-audio choke-point tests."""
    import jax

    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.models.hifigan import Generator
    from speakingstyle_tpu.serving.engine import SynthesisEngine

    cfg = _tiny_cfg()
    model = build_model(cfg, n_position=49)
    variables = init_variables(model, cfg, jax.random.PRNGKey(0))
    bias = variables["params"]["variance_adaptor"]["duration_predictor"][
        "linear_layer"]["bias"]
    variables["params"]["variance_adaptor"]["duration_predictor"][
        "linear_layer"]["bias"] = bias + 1.1
    gen = Generator(
        upsample_rates=(2, 2), upsample_kernel_sizes=(4, 4),
        upsample_initial_channel=16, resblock_kernel_sizes=(3,),
        resblock_dilation_sizes=((1,),),
    )
    gparams = gen.init(
        jax.random.PRNGKey(0), np.zeros((1, 8, 80), np.float32)
    )["params"]
    registry = MetricsRegistry()
    engine = SynthesisEngine(cfg, variables, vocoder=(gen, gparams),
                             model=model, registry=registry)
    engine.precompile()
    return cfg, registry, engine


class _EngineRouter:
    """Single-tier router facade over a bare engine (what the prober
    needs: submit -> future of one SynthesisResult)."""

    tier = "tiny"

    def __init__(self, engine):
        self.engine = engine

    def submit(self, req):
        fut = Future()
        try:
            fut.set_result(self.engine.run([req])[0])
        except Exception as e:  # pragma: no cover - surfaced by tests
            fut.set_exception(e)
        return fut


def test_engine_run_choke_point_attaches_the_verdict(tiny_engine):
    cfg, reg, engine = tiny_engine
    res = engine.run([_req(10, priority="interactive")])[0]
    assert res.quality is not None and res.quality.ok
    assert reg.value("serve_quality_checks_total",
                     {"class": "interactive", "tier": "default",
                      "source": "engine"}) >= 1
    # quality_check=False is the unchecked arm: no verdict,
    # no counter motion
    before = reg.value("serve_quality_class_total",
                       {"class": "interactive"})
    res = engine.run([_req(11, priority="interactive",
                           quality_check=False)])[0]
    assert res.quality is None
    assert reg.value("serve_quality_class_total",
                     {"class": "interactive"}) == before


def test_streaming_window_choke_point(tiny_engine):
    cfg, reg, engine = tiny_engine
    res = engine.run([_req(12, priority="interactive", stream=True)])[0]
    mel = np.asarray(res.mel, np.float32)[: int(res.mel_len)]
    before = reg.value("serve_quality_checks_total",
                       {"class": "interactive", "tier": "default",
                        "source": "stream"})
    handle = engine.vocode_dispatch(mel, klass="interactive")
    wav = engine.vocode_collect(handle)
    assert wav.dtype == np.int16 and wav.size > 0
    assert reg.value("serve_quality_checks_total",
                     {"class": "interactive", "tier": "default",
                      "source": "stream"}) == before + 1


def test_tier_poison_drill_validators_and_probes_catch_it(
        tiny_engine, tmp_path):
    from speakingstyle_tpu.serving.engine import CompileMonitor

    cfg, reg, engine = tiny_engine
    # a poisoned net saturates unpredictably — rails (validators catch
    # clipping) or collapses to near-silence (short wavs the per-wav
    # checks legitimately pass). The probe leg is the GUARANTEED
    # detector — any departure from the pinned anchors is drift — which
    # is why the plane carries both; anchor the drill on it with a
    # tight tolerance
    cfg = dataclasses.replace(cfg, serve=dataclasses.replace(
        cfg.serve, quality=dataclasses.replace(
            cfg.serve.quality, probe_mel_tolerance=1e-3)))
    router = _EngineRouter(engine)
    prober = GoldenProber(router, cfg, registry=reg,
                          anchor_dir=str(tmp_path), start=False)
    prober.pin()
    s = prober.probe_once()
    assert s["tiers"]["tiny"]["mel_drift"] == pytest.approx(0.0)
    assert prober.alerting().get("tiny", False) is False

    engine.poison_params()
    with CompileMonitor() as mon:
        res = engine.run([_req(13, priority="interactive")])[0]
        s = prober.probe_once()
    # same shapes, same programs: the poison costs ZERO compiles —
    # nothing but the quality plane can see it
    assert mon.count == 0
    assert res.quality is not None  # the choke point ran regardless
    drift = s["tiers"]["tiny"]["mel_drift"]
    assert drift > cfg.serve.quality.probe_mel_tolerance
    assert prober.alerting()["tiny"] is True
