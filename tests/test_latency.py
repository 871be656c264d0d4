"""Latency-pipeline acceptance tests (tier-1, PR 11).

Four claims, mirroring ARCHITECTURE.md "Latency pipeline":
  1. correctness — the double-buffered stream (``stream_depth >= 2``)
     emits wav bit-identical to the sequential path at any depth,
     including the edge windows (single-window utterances, tails shorter
     than the overlap, exact window multiples);
  2. zero steady-state compiles with the pipeline on, measured on the
     backend's own monitoring bus;
  3. allocation-free, leak-free staging — ``BufferPool`` leases return
     on every path: normal collect, abandoned streams, and a dispatch
     stolen by the hang watchdog mid-flight (the PR 9 chaos path), with
     the alloc counter flat across post-warmup traffic;
  4. the frontend pool preserves PR 9 semantics — the SLO clock starts
     at admission, so a deadline expiry still resolves 504 pre-dispatch
     without ever waiting on the frontend.

Plus unit coverage for the two new primitives (``FrontendPool``,
``BufferPool``) themselves.
"""

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
from speakingstyle_tpu.faults import FaultPlan
from speakingstyle_tpu.obs import MetricsRegistry
from speakingstyle_tpu.serving import streaming
from speakingstyle_tpu.serving.batcher import ShutdownError
from speakingstyle_tpu.serving.engine import CompileMonitor, SynthesisRequest
from speakingstyle_tpu.serving.fleet import FleetRouter
from speakingstyle_tpu.serving.frontend import FrontendPool, PendingRequest
from speakingstyle_tpu.serving.pool import BufferPool
from speakingstyle_tpu.serving.resilience import DeadlineExceeded

# ---------------------------------------------------------------------------
# shared tiny model (test_serving.py's recipe + a small stream window so
# one utterance spans several windows, incl. a short tail)
# ---------------------------------------------------------------------------


def _tiny_cfg(**fleet_kw):
    fleet = dict(
        stream_window=8, rewarm_backoff_s=0.05, rewarm_backoff_max_s=1.0,
        class_deadline_ms={"interactive": 120_000.0, "batch": 240_000.0},
    )
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
            style=StyleConfig(ref_buckets=[32]),
            fleet=FleetConfig(**fleet),
        ),
    )


@pytest.fixture(scope="module")
def tiny_parts():
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
def pipe_engine(tiny_parts):
    from speakingstyle_tpu.serving.engine import SynthesisEngine

    cfg, model, variables, gen, gparams = tiny_parts
    engine = SynthesisEngine(cfg, variables, vocoder=(gen, gparams),
                             model=model)
    engine.precompile()
    return engine


def _mkreq(i, L=10, T=20, **kw):
    rng = np.random.default_rng(i)
    kw.setdefault(
        "ref_mel", rng.standard_normal((T, 80)).astype(np.float32)
    )
    return SynthesisRequest(
        id=f"utt{i}",
        sequence=rng.integers(1, 300, L).astype(np.int32),
        **kw,
    )


def _stream_params(engine):
    window = engine.cfg.serve.fleet.stream_window
    overlap = streaming.resolve_overlap(
        engine.cfg.serve.fleet.stream_overlap, engine.vocoder[0]
    )
    return window, overlap


# ---------------------------------------------------------------------------
# 1. bit-exactness: pipelined vs sequential, incl. edge windows
# ---------------------------------------------------------------------------


def test_stream_pipelined_bit_exact_vs_sequential(pipe_engine):
    """The pipeline reorders *waiting*, never the per-window math: at
    any depth the concatenated chunks equal the sequential (depth=1)
    stream bit-for-bit, and cover exactly mel_len * hop samples."""
    engine = pipe_engine
    window, overlap = _stream_params(engine)
    hop = int(engine.vocoder[0].hop_factor)
    res = engine.run([_mkreq(1, L=16, stream=True)])[0]
    assert res.mel_len > 2 * window, "fixture must span several windows"
    seq = np.concatenate(list(
        streaming.stream_wav(engine, res, window, overlap, depth=1)
    ))
    assert seq.shape == (res.mel_len * hop,) and seq.dtype == np.int16
    for depth in (2, 3, 4):
        piped = np.concatenate(list(
            streaming.stream_wav(engine, res, window, overlap, depth=depth)
        ))
        np.testing.assert_array_equal(piped, seq)


def test_stream_pipelined_bit_exact_edge_windows(pipe_engine):
    """Edge geometries where the overlap-tail logic can go wrong: a
    single short window, a tail shorter than the overlap, an exact
    window multiple, and window+1 (1-frame tail). stream_wav reads only
    (mel, mel_len), so slicing a real mel drives each case exactly."""
    engine = pipe_engine
    window, overlap = _stream_params(engine)
    hop = int(engine.vocoder[0].hop_factor)
    res = engine.run([_mkreq(2, L=16, stream=True)])[0]
    lengths = sorted({
        1, window - 1, window, window + 1, 2 * window, int(res.mel_len),
    })
    assert lengths[-1] <= res.mel_len
    for m in lengths:
        clip = SimpleNamespace(mel=res.mel[:m], mel_len=m)
        seq = np.concatenate(list(
            streaming.stream_wav(engine, clip, window, overlap, depth=1)
        ))
        piped = np.concatenate(list(
            streaming.stream_wav(engine, clip, window, overlap, depth=3)
        ))
        assert seq.shape == (m * hop,)
        np.testing.assert_array_equal(piped, seq)


def test_stream_depth_validated(pipe_engine):
    res = SimpleNamespace(mel=np.zeros((4, 80), np.float32), mel_len=4)
    with pytest.raises(ValueError, match="depth"):
        list(streaming.stream_wav(pipe_engine, res, 8, 2, depth=0))


# ---------------------------------------------------------------------------
# 2. zero steady-state compiles with the pipeline on
# ---------------------------------------------------------------------------


def test_stream_pipeline_zero_steady_state_compiles(pipe_engine):
    """After one warmup pass the pipelined stream performs ZERO XLA
    compiles — same invariant the batch path proves, measured on the
    backend's monitoring bus."""
    engine = pipe_engine
    window, overlap = _stream_params(engine)
    res = engine.run([_mkreq(3, L=16, stream=True)])[0]
    list(streaming.stream_wav(engine, res, window, overlap, depth=2))
    before = engine.compile_count
    with CompileMonitor() as mon:
        for depth in (1, 2, 3):
            chunks = list(
                streaming.stream_wav(engine, res, window, overlap,
                                     depth=depth)
            )
            assert chunks
    assert mon.count == 0, "the stream pipeline compiled after warmup"
    assert engine.compile_count == before


# ---------------------------------------------------------------------------
# 3. pool: abandoned streams and the hang-watchdog steal leak nothing
# ---------------------------------------------------------------------------


def test_abandoned_stream_returns_pooled_buffers(pipe_engine):
    """A consumer that walks away mid-stream (client disconnect) leaves
    zero leased buffers behind — the generator's finally abandons every
    in-flight handle — and later streams stay allocation-free."""
    engine = pipe_engine
    window, overlap = _stream_params(engine)
    res = engine.run([_mkreq(4, L=16, stream=True)])[0]
    list(streaming.stream_wav(engine, res, window, overlap, depth=3))
    assert engine.pool.outstanding == 0
    allocated = engine.pool.allocated
    it = streaming.stream_wav(engine, res, window, overlap, depth=3)
    next(it)                       # pipeline primed: handles in flight
    it.close()                     # consumer gone
    assert engine.pool.outstanding == 0
    chunks = list(streaming.stream_wav(engine, res, window, overlap))
    assert sum(len(c) for c in chunks) == res.mel_len * 4
    assert engine.pool.allocated == allocated, "steady state allocated"
    assert engine.pool.outstanding == 0


class _Events:
    """In-memory stand-in for the JSONL event bus (test_chaos.py's)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.records = []

    def emit(self, event, **fields):
        with self.lock:
            self.records.append((event, fields))

    def kinds(self):
        with self.lock:
            return [k for k, _ in self.records]

    def of(self, kind):
        with self.lock:
            return [dict(f) for k, f in self.records if k == kind]


def test_pool_no_leak_under_replica_hang_steal(tiny_parts):
    """The PR 9 chaos path against the real engine: a dispatch stuck
    past the hang watchdog is stolen and retried on the re-warmed
    replica; when the hung worker finishes anyway, its results are
    discarded (no duplicate audio) and every pooled staging buffer it
    leased is back — outstanding 0 on both engines, allocs flat across
    post-steal traffic."""
    from speakingstyle_tpu.serving.engine import SynthesisEngine

    cfg, model, variables, gen, gparams = tiny_parts
    cfg = _tiny_cfg(hang_watchdog_s=0.3)
    engines = []
    plan = FaultPlan()
    events = _Events()
    reg = MetricsRegistry()

    def factory(registry):
        eng = SynthesisEngine(cfg, variables, vocoder=(gen, gparams),
                              model=model, registry=registry)
        engines.append(eng)
        return eng

    with FleetRouter(factory, cfg, replicas=1, registry=reg,
                     events=events, fault_plan=plan) as router:
        assert router.wait_ready(timeout=300)
        for b in engines[0].lattice.batch_buckets:
            engines[0].run([_mkreq(700 + b * 10 + j) for j in range(b)])
        for f in [router.submit(_mkreq(i)) for i in range(2)]:
            assert f.result(timeout=120).wav is not None
        # the NEXT dispatch hangs past the watchdog, gets stolen, and
        # retries on the re-warmed (second) engine
        plan.arm("replica_hang", router.dispatch_total + 1)
        res = router.submit(_mkreq(10)).result(timeout=300)
        assert res.id == "utt10" and res.wav is not None
        assert len(engines) == 2
        rf = events.of("replica_failure")
        assert len(rf) == 1 and rf[0]["kind"] == "hang"
        # the hung worker wakes, finishes its dispatch on engine #1,
        # finds its claim stolen, and discards — releasing its leases
        deadline = time.monotonic() + 30
        while (time.monotonic() < deadline
               and "dispatch_discarded" not in events.kinds()):
            time.sleep(0.01)
        assert "dispatch_discarded" in events.kinds()
        # post-steal steady state: allocation-free and leak-free
        for f in [router.submit(_mkreq(20 + i)) for i in range(2)]:
            assert f.result(timeout=120).wav is not None
        allocated = [e.pool.allocated for e in engines]
        for f in [router.submit(_mkreq(30 + i)) for i in range(3)]:
            assert f.result(timeout=120).wav is not None
        for i, eng in enumerate(engines):
            assert eng.pool.outstanding == 0, f"engine {i} leaked a lease"
            assert eng.pool.allocated == allocated[i]


# ---------------------------------------------------------------------------
# 4. frontend pool preserves the deadline contract
# ---------------------------------------------------------------------------


class _GatedFrontend:
    """Frontend whose G2P blocks until released — models a slow/wedged
    frontend so the test can prove the 504 never waited on it."""

    def __init__(self):
        self.gate = threading.Event()
        self.calls = 0

    def request(self, req_id, payload):
        self.calls += 1
        self.gate.wait(timeout=30)
        return SimpleNamespace(id=req_id, stream=False, arrival=None)


def test_frontend_pool_deadline_still_504s_pre_dispatch():
    """The SLO clock starts at the handler's admission stamp, not at
    G2P completion: with the only replica still warming and the
    frontend wedged, the EDF sweep resolves DeadlineExceeded on budget
    — the pending handle is never waited on (still unresolved)."""
    warm_gate = threading.Event()

    def factory(reg):
        warm_gate.wait(timeout=30)
        return SimpleNamespace(precompile=lambda: 0.0,
                               run=lambda requests: [])

    cfg = _tiny_cfg(class_deadline_ms={"interactive": 60.0,
                                       "batch": 2000.0})
    reg = MetricsRegistry()
    frontend = _GatedFrontend()
    pool = FrontendPool(frontend, workers=1, registry=reg)
    router = FleetRouter(factory, cfg, replicas=1, registry=reg)
    try:
        t0 = time.monotonic()
        pending = pool.prepare("r0", {"text": "too late"})
        fut = router.submit(pending)
        pool.dispatch(pending)
        exc = fut.exception(timeout=10)
        assert isinstance(exc, DeadlineExceeded)
        assert exc.klass == "interactive" and exc.budget_ms == 60.0
        # resolved by the budget sweep, and strictly pre-dispatch: the
        # frontend never finished, so nothing ever waited on it
        assert time.monotonic() - t0 < 5.0
        assert not pending._future.done()
        assert reg.value("serve_deadline_exceeded_total",
                         {"class": "interactive"}) == 1
    finally:
        warm_gate.set()
        frontend.gate.set()
        pool.close()
        router.close()


# ---------------------------------------------------------------------------
# FrontendPool unit coverage
# ---------------------------------------------------------------------------


class _EchoFrontend:
    def __init__(self, fail_ids=()):
        self.fail_ids = set(fail_ids)

    def request(self, req_id, payload):
        if req_id in self.fail_ids:
            raise ValueError(f"bad text for {req_id}")
        return SimpleNamespace(id=req_id, text=payload.get("text"),
                               stream=False, arrival=None)


def test_frontend_pool_resolves_and_restamps():
    """The resolved request carries the handler's admission stamp and
    stream flag (deadline math identical to inline mode), and the
    frontend cost lands in serve_frontend_seconds."""
    reg = MetricsRegistry()
    with FrontendPool(_EchoFrontend(), workers=2, registry=reg) as pool:
        pending = pool.prepare("q1", {"text": "hello"}, stream=True)
        pool.dispatch(pending)
        req = pending.resolve(timeout=10)
        assert req.id == "q1" and req.text == "hello"
        assert req.stream is True
        assert req.arrival == pending.arrival
        assert pending.resolve(timeout=0) is req      # idempotent
    snap = reg.snapshot()
    assert snap["histograms"]["serve_frontend_seconds"]["count"] == 1


def test_frontend_pool_error_resolves_exceptionally():
    reg = MetricsRegistry()
    with FrontendPool(_EchoFrontend(fail_ids={"bad"}), workers=1,
                      registry=reg) as pool:
        ok, bad = pool.prepare("ok", {}), pool.prepare("bad", {})
        pool.dispatch(bad)
        pool.dispatch(ok)
        with pytest.raises(ValueError, match="bad text"):
            bad.resolve(timeout=10)
        assert ok.resolve(timeout=10).id == "ok"      # worker survived
        assert reg.value("serve_frontend_errors_total") == 1


def test_frontend_pool_close_flushes_then_refuses():
    """close() drains already-dispatched work (the prefetch discipline),
    then a post-close dispatch resolves ShutdownError — no handle is
    ever stranded."""
    pool = FrontendPool(_EchoFrontend(), workers=1)
    flushed = [pool.prepare(f"f{i}", {}) for i in range(3)]
    for p in flushed:
        pool.dispatch(p)
    pool.close()
    for p in flushed:
        assert p.resolve(timeout=10).id == p.id
    late = pool.prepare("late", {})
    pool.dispatch(late)
    with pytest.raises(ShutdownError):
        late.resolve(timeout=10)
    pool.close()                                      # idempotent


def test_pending_request_validates_priority_type():
    with pytest.raises(ValueError, match="priority"):
        PendingRequest("r0", {"priority": 3})
    assert PendingRequest("r1", {"priority": "batch"}).priority == "batch"
    assert PendingRequest("r2", {}).priority is None


def test_frontend_pool_requires_workers():
    with pytest.raises(ValueError, match="worker"):
        FrontendPool(_EchoFrontend(), workers=0)


# ---------------------------------------------------------------------------
# BufferPool unit coverage
# ---------------------------------------------------------------------------


def test_buffer_pool_lease_reuse_and_metrics():
    reg = MetricsRegistry()
    pool = BufferPool(registry=reg)
    a = pool.acquire((4, 2), np.float32, fill=0)
    assert a.shape == (4, 2) and not a.any()
    assert pool.allocated == 1 and pool.outstanding == 1
    assert reg.value("serve_pool_outstanding") == 1
    a[:] = 7.0                                        # dirty it
    pool.release(a)
    assert pool.outstanding == 0
    assert reg.value("serve_pool_outstanding") == 0
    b = pool.acquire((4, 2), np.float32, fill=0)
    assert b is a                                     # reused, not fresh
    assert not b.any(), "reused lease must be re-filled"
    assert pool.allocated == 1
    assert reg.value("serve_pool_reuses_total") == 1
    # a different (shape, dtype) is a different free-list
    c = pool.acquire((4, 2), np.int16, fill=1)
    assert c.dtype == np.int16 and (c == 1).all()
    assert pool.allocated == 2
    pool.release(b)
    pool.release(c)
    assert pool.outstanding == 0


def test_buffer_pool_double_release_is_loud():
    pool = BufferPool()
    buf = pool.acquire((3,), np.float32)
    pool.release(buf)
    with pytest.raises(ValueError, match="release"):
        pool.release(buf)
    with pytest.raises(ValueError, match="release"):
        pool.release(np.zeros((3,), np.float32))      # foreign array
    assert pool.outstanding == 0
