"""Headline benchmark: FastSpeech2 training throughput in mel-frames/sec.

Measures the full jitted training step (fwd + bwd + optimizer) on the
flagship model at the reference's paper config scale — batch 48, ~600 mel
frames per utterance ≈ 29k mel frames per step (SURVEY.md §6) — and prints
ONE JSON line {"metric", "value", "unit", "vs_baseline"}.

`vs_baseline` is relative to an estimated single-A100 PyTorch throughput of
the reference at the same batch geometry (no published numbers exist;
BASELINE.json "published": {}). The 250k denominator is DERIVED in
BASELINE_NOTES.md (two independent anchors: the reference's own 1080Ti
anecdote scaled to A100, and an A100 utilization bound over the XLA-counted
step FLOPs — both land at 200-250k; we use the top of the range so
vs_baseline is a lower bound). `python bench.py --flops` prints the
compiled step's ProgramCard (obs/cost.py — the same cost/memory
extraction serving and training export). `python bench.py --compare
OLD.json [NEW.json]` is the regression gate over the BENCH_r*.json
trajectory: diffs steps/sec and serving percentiles between two recorded
artifacts, exits non-zero past a 10% regression. The ≥3x north-star
corresponds to vs_baseline >= 3.0, i.e. >= 750k mel-frames/s/chip.

Measured perf notes (v5e single chip, 2026-07 round 1):
  * step ≈ 6.5 TFLOP (ref-encoder 1024-ch convs + decoder k=9 FFN convs
    dominate); at 90 ms/step the average rate is ~72 TFLOP/s — above the
    ~50 TFLOP/s single-op rate measured for the same conv shapes, i.e.
    the step is near the practical roofline for this architecture.
  * throughput is flat in batch (48/96/200 all ~270k frames/s pre-RNG
    fix): compute-bound, not dispatch- or batch-bound.
  * threefry dropout-mask generation cost ~15% of the step; the RBG
    default (TrainConfig.fast_prng) recovers it -> ~320k frames/s.
  * round 4 FLOP-level work (the 1.28x -> 3x plan): ``model.conv_impl``
    selects the conv lowering — the on-chip A/B crowned "xla" (the
    spatial-conv emitter, now the default; the im2col "unfold" GEMM
    projection lost by 19%), and ``model.attention_kernel="fused"``
    engages the fused-MHA pallas kernel (ops/pallas_attention.py) that
    took the step from 1.50x to 1.77x. See PERF.md for the full measured
    story. ``python bench.py --ab`` measures all variants;
    ``--inner --profile`` writes a jax.profiler trace to ./profile_trace.
"""

import json
import os
import subprocess
import sys
import threading
import time

# Estimated reference (PyTorch, unoptimized research code, fp32, Python
# length-regulator loop) single-A100 training throughput at batch 48 ×
# ~600 frames. No published number exists; BASELINE_NOTES.md derives the
# 200-250k plausible range — this is its top, making vs_baseline a lower
# bound on the true speedup.
A100_BASELINE_FRAMES_PER_SEC = 250_000.0

B, L_SRC, T_MEL = 48, 100, 600
# JAX dispatch is asynchronous: a call returns once the work is enqueued,
# so every timed region below ends in `jax.block_until_ready` on its last
# result — a timing without it measures the enqueue, not the device.
WARMUP_STEPS, BENCH_STEPS = 3, 50

# The headline measures the TPU-tuned training config (README "Performance
# knobs"): the r4 on-chip A/B measured conv_impl=xla fastest end-to-end
# (330k vs unfold's 272k frames/s on the final matrix re-run — PERF.md),
# bf16 softmax worth +14% on the einsum path, and the fused-MHA pallas
# kernel (ops/pallas_attention.py) worth another large step on top
# (443k) — its VMEM softmax is f32, so it is MORE accurate than the
# bf16-softmax einsum variant while being faster. The knobs used are
# echoed in the JSON line as "overrides".
# The default config IS the tuned config as of r4 (conv_impl=xla and
# attention_kernel=fused are the ModelConfig defaults, both chosen by
# on-chip A/B). Knobs measured and NOT adopted (PERF.md): unfold conv
# (-19%), fused_optimizer (-5%: ravel/unravel copies exceed the optax
# chain overhead), in-kernel bf16 softmax (wash). The dict stays as the
# mechanism for future A/Bs; the headline echoes it in the JSON line.
# (The fused_optimizer negative above refers to the r4 "flat" raveled
# variant; the r5 "leaf" per-leaf variant measured +0.6% and IS adopted
# below.)
TUNED_OVERRIDES = {
    "conv_impl": "xla",
    "attention_kernel": "fused",
    # r5 additions, each measured on-chip (PERF.md): fused counter-hash
    # dropout masks (+6.2%) and the per-leaf fused optimizer (+0.6%).
    # dropout_impl=hash is also the ModelConfig default; fused_optimizer
    # stays off in TrainConfig because its opt_state layout differs from
    # the optax chain's (checkpoint compatibility), which a fresh bench
    # run doesn't care about.
    "dropout_impl": "hash",
    "fused_optimizer": "leaf",
}


def _apply_overrides(cfg, overrides: dict):
    """Route each override key to the dataclass that owns it (ModelConfig
    or TrainConfig); unknown keys are a clear error instead of a confusing
    dataclasses.replace TypeError."""
    import dataclasses

    model_keys = {f.name for f in dataclasses.fields(cfg.model)}
    train_keys = {f.name for f in dataclasses.fields(cfg.train)}
    unknown = set(overrides) - model_keys - train_keys
    if unknown:
        raise ValueError(
            f"unknown override key(s) {sorted(unknown)}: not a field of "
            "ModelConfig or TrainConfig"
        )
    m = {k: v for k, v in overrides.items() if k in model_keys}
    t = {k: v for k, v in overrides.items() if k not in model_keys}
    if m:
        cfg = dataclasses.replace(cfg, model=dataclasses.replace(cfg.model, **m))
    if t:
        cfg = dataclasses.replace(cfg, train=dataclasses.replace(cfg.train, **t))
    return cfg


def make_batch(n_mels: int, rng):
    import jax.numpy as jnp

    d = T_MEL // L_SRC
    return dict(
        speakers=jnp.zeros((B,), jnp.int32),
        texts=jnp.asarray(rng.integers(1, 360, (B, L_SRC)), jnp.int32),
        src_lens=jnp.full((B,), L_SRC, jnp.int32),
        mels=jnp.asarray(rng.standard_normal((B, T_MEL, n_mels)), jnp.float32),
        mel_lens=jnp.full((B,), T_MEL, jnp.int32),
        pitches=jnp.asarray(rng.standard_normal((B, L_SRC)), jnp.float32),
        energies=jnp.asarray(rng.standard_normal((B, L_SRC)), jnp.float32),
        durations=jnp.full((B, L_SRC), d, jnp.int32),
    )


_T0 = time.monotonic()


def _is_tpu() -> bool:
    from speakingstyle_tpu.ops import on_tpu

    return on_tpu()


def _require_tpu() -> None:
    """Device numbers come from a chip or not at all: a measurement mode
    that finds no TPU raises (non-zero exit) instead of timing the CPU."""
    import jax

    if not _is_tpu():
        raise RuntimeError(
            f"no TPU: backend is {jax.default_backend()!r} — numbers from "
            "this host's CPU would be meaningless"
        )


def _mark(msg: str) -> None:
    """Timestamped stderr breadcrumb.

    The round-3 driver record was `value: null, error: timeout` with no way
    to tell WHERE the 360 s died (device acquisition? compile? execute?).
    Every stage below emits one of these; on timeout the guard tails them
    into the error field.
    """
    print(f"[bench +{time.monotonic() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def _bench_registry():
    """One ProgramRegistry per bench process: places the persistent
    compile cache (obs/jaxmon.enable_compilation_cache — a cold compile is
    the slowest part of a run; warm runs skip it) and owns every AOT
    compile below (bench_compiles_total,
    jax_persistent_cache_{hits,requests}_total)."""
    from speakingstyle_tpu.parallel.registry import ProgramRegistry

    return ProgramRegistry(counter_name="bench_compiles_total", prefix="bench")


def main(report_flops: bool = False, profile: bool = False,
         overrides: dict = None):
    _mark("importing jax")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from speakingstyle_tpu.configs.config import Config
    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.training.optim import make_optimizer
    from speakingstyle_tpu.training.state import TrainState
    from speakingstyle_tpu.training.trainer import make_train_step

    # XLA-native RBG PRNG for dropout masks (TrainConfig.fast_prng):
    # threefry mask generation alone cost ~15% of the v5e step time.
    jax.config.update("jax_default_prng_impl", "rbg")
    programs = _bench_registry()
    _mark("acquiring devices")
    _require_tpu()
    _mark(f"devices acquired: {jax.devices()}")
    cfg = Config()
    if overrides:
        cfg = _apply_overrides(cfg, overrides)
    model = build_model(cfg)
    _mark("initializing variables")
    variables = init_variables(model, cfg, jax.random.PRNGKey(0))
    tx = make_optimizer(cfg.train)
    state = TrainState.create(variables, tx)
    train_step = make_train_step(model, tx, cfg, mesh=None)
    _mark("variables initialized")

    batch = make_batch(
        cfg.preprocess.preprocessing.mel.n_mel_channels,
        np.random.default_rng(0),
    )
    batch = jax.device_put(batch)
    rng = jax.random.PRNGKey(1)

    # XLA compiler-option experiments, applied per compile:
    # BENCH_COMPILER_OPTIONS='{"xla_tpu_scoped_vmem_limit_kib": "65536"}'
    copts = json.loads(os.environ.get("BENCH_COMPILER_OPTIONS", "null"))

    if report_flops:
        # thin registry-card consumer: the same extraction the serving
        # engine and the trainer use (parallel/registry.py -> obs/cost.py),
        # so --flops, /debug/programs, and the program_card event can
        # never disagree on what a program costs
        programs.compile(
            train_step, (state, batch, rng), name="train_step",
            compiler_options=copts,
        )
        card = programs.card("train_step") or {}
        flops = card.get("flops")
        flops = flops if flops is not None else float("nan")
        out = {
            "metric": "train_step_flops",
            "value": flops,
            "unit": "FLOP/step",
            "per_frame_mflop": round(flops / (B * T_MEL) / 1e6, 1),
            "program_card": card,
        }
        if copts:
            out["compiler_options"] = copts
        print(json.dumps(out))
        return

    _mark("compile start (ProgramRegistry AOT compile)")
    compiled = programs.compile(
        train_step, (state, batch, rng), name="train_step",
        compiler_options=copts,
    )
    _mark("compile end")

    for _ in range(WARMUP_STEPS):
        state, losses = compiled(state, batch, rng)
    jax.block_until_ready(losses["total_loss"])
    _mark("warmup done; measuring")
    train_step = compiled

    if profile:
        trace_dir = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "profile_trace"
        )
        jax.profiler.start_trace(trace_dir)

    t0 = time.perf_counter()
    for _ in range(BENCH_STEPS):
        state, losses = train_step(state, batch, rng)
    jax.block_until_ready(losses["total_loss"])
    dt = time.perf_counter() - t0

    if profile:
        jax.profiler.stop_trace()
        _mark(f"trace written to {trace_dir}")

    frames_per_step = B * T_MEL
    fps = frames_per_step * BENCH_STEPS / dt
    out = {
        "metric": "train_mel_frames_per_sec",
        "value": round(fps, 1),
        "unit": "mel-frames/sec/chip",
        "vs_baseline": round(fps / A100_BASELINE_FRAMES_PER_SEC, 3),
    }
    if overrides:
        out["overrides"] = overrides
    if copts:
        # experiment compiler options change the measurement — they must
        # be attributable in the recorded line, like overrides
        out["compiler_options"] = copts
    print(json.dumps(out))


def run_breakdown():
    """Per-component step-time breakdown at bench shapes (the profiler's
    trace viewer is unavailable offline, and this answers the same
    question: where does the step actually go). Times the jitted fwd+bwd
    of each heavy module under the tuned config; compare against the full
    step time from the headline run (`python bench.py`) — the gap between
    the component sum and the full step is the variance adaptor, losses,
    optimizer, and XLA fusion overlap."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from speakingstyle_tpu.configs.config import Config
    from speakingstyle_tpu.models.factory import (
        fft_stack_from_config,
        reference_encoder_from_config,
    )
    from speakingstyle_tpu.models.postnet import PostNet

    jax.config.update("jax_default_prng_impl", "rbg")
    programs = _bench_registry()
    _require_tpu()
    cfg = _apply_overrides(Config(), TUNED_OVERRIDES)
    m = cfg.model
    dtype = jnp.dtype(m.compute_dtype)
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)

    mels = jnp.asarray(rng.standard_normal((B, T_MEL, 80)), dtype)
    dec_x = jnp.asarray(
        rng.standard_normal((B, T_MEL, m.transformer.decoder_hidden)), dtype
    )
    texts = jnp.asarray(rng.integers(1, 360, (B, L_SRC)), jnp.int32)
    # mask convention: True = padded (ops/masking.py) — all-False = all real
    src_mask = jnp.zeros((B, L_SRC), bool)
    mel_mask = jnp.zeros((B, T_MEL), bool)

    cases = [
        ("reference_encoder", reference_encoder_from_config(cfg), (mels, mel_mask)),
        ("encoder", fft_stack_from_config(cfg, "encoder"), (texts, src_mask)),
        ("decoder", fft_stack_from_config(cfg, "decoder"), (dec_x, mel_mask)),
        ("postnet", PostNet(conv_impl=m.conv_impl, dtype=dtype), (mels,)),
    ]

    results = {}
    for name, module, args in cases:
        params = module.init(key, *args)

        def loss_fn(p, mod=module, a=args):
            out = mod.apply(p, *a)
            if isinstance(out, tuple):
                return sum(
                    jnp.sum(o.astype(jnp.float32)) for o in out if o is not None
                )
            return jnp.sum(out.astype(jnp.float32))

        g = programs.compile(
            jax.grad(loss_fn), (params,), name=f"breakdown:{name}"
        )
        grads = g(params)
        jax.block_until_ready(grads)
        t0 = time.perf_counter()
        for _ in range(BENCH_STEPS):
            grads = g(params)
        jax.block_until_ready(grads)
        ms = (time.perf_counter() - t0) / BENCH_STEPS * 1e3
        results[name] = round(ms, 2)
        _mark(f"{name}: {ms:.2f} ms fwd+bwd (deterministic)")
    print(json.dumps({"metric": "component_ms_fwd_bwd", "value": results,
                      "unit": "ms", "shapes": {"B": B, "L_src": L_SRC,
                                               "T_mel": T_MEL}}))


def run_infer():
    """Inference-side benchmark: free-running acoustic synthesis and
    HiFi-GAN vocoding on the chip, reported as realtime factors (seconds
    of 22050 Hz audio generated per wall second). Complements the training
    headline; the reference has no counterpart numbers (SURVEY.md §6), so
    these lines are recorded for BASELINE_NOTES-style tracking."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from speakingstyle_tpu.configs.config import Config
    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.models.hifigan import Generator

    from speakingstyle_tpu.parallel.registry import jit_program

    jax.config.update("jax_default_prng_impl", "rbg")
    _bench_registry()  # persistent-cache + compile-bus wiring
    _require_tpu()
    cfg = _apply_overrides(Config(), TUNED_OVERRIDES)
    rng = np.random.default_rng(0)
    hop, sr = 256, 22050
    n_mels = cfg.preprocess.preprocessing.mel.n_mel_channels

    def time_realtime(fn, *args, n_frames):
        """Compile+warm fn(*args), time it, return (dt_s, realtime_x)."""
        out = fn(*args)
        jax.block_until_ready(out)
        _mark("compile+warmup done")
        t0 = time.perf_counter()
        for _ in range(BENCH_STEPS):
            out = fn(*args)
        float(out.ravel()[0])
        dt = (time.perf_counter() - t0) / BENCH_STEPS
        return dt, n_frames * hop / sr / dt

    # --- free-running acoustic model (teacher targets absent) ---
    model = build_model(cfg)
    variables = init_variables(model, cfg, jax.random.PRNGKey(0))
    _mark("acoustic init done")
    batch = {
        k: v for k, v in make_batch(n_mels, rng).items()
        if k not in ("pitches", "energies", "durations")
    }
    fwd = jit_program(
        # max_mel_len is a static shape argument (the free-running mel
        # buffer length), so it is closed over rather than traced
        lambda v, b: model.apply(v, deterministic=True, **b,
                                 max_mel_len=T_MEL,
                                 mutable=["batch_stats"])[0]["mel_postnet"]
    )
    dt, rt = time_realtime(fwd, variables, batch, n_frames=B * T_MEL)
    print(json.dumps({
        "metric": "synthesis_realtime_factor",
        "value": round(rt, 1),
        "unit": f"x realtime (acoustic mel generation, batch {B})",
        "mel_frames_per_sec": round(B * T_MEL / dt, 1),
    }))

    # --- HiFi-GAN vocoder (random weights; compute identical to trained) ---
    gen = Generator(dtype=jnp.bfloat16)
    Bv = 8
    mels = jnp.asarray(rng.standard_normal((Bv, T_MEL, n_mels)), jnp.float32)
    params = gen.init(jax.random.PRNGKey(0), mels)["params"]
    voc = jit_program(lambda p, m: gen.apply({"params": p}, m))
    dt, rt = time_realtime(voc, params, mels, n_frames=Bv * T_MEL)
    print(json.dumps({
        "metric": "hifigan_realtime_factor",
        "value": round(rt, 1),
        "unit": f"x realtime (mel->wav, batch {Bv}, bf16)",
        "samples_per_sec": round(Bv * T_MEL * hop / dt, 1),
    }))

    # --- batch-1 warm end-to-end latency: text -> wav on the host ---
    # The deployment metric the throughput rows don't show (reference:
    # synthesize.py:128-150 single mode): host G2P + free-running acoustic
    # model + HiFi-GAN + the wav's device->host read, per utterance.
    from speakingstyle_tpu.text.g2p import preprocess_text

    text = ("The quick brown fox jumps over the lazy dog and then runs "
            "far away into the quiet green hills beyond the river")
    T_lat = 640  # static mel buffer ~7.4 s of 22050 Hz audio at hop 256
    fwd1 = jit_program(
        lambda v, b: model.apply(v, deterministic=True, **b,
                                 max_mel_len=T_lat,
                                 mutable=["batch_stats"])[0]["mel_postnet"]
    )
    pp_cfg = cfg.preprocess.preprocessing

    def text_to_wav():
        seq = preprocess_text(
            text, pp_cfg.text.language, None, list(pp_cfg.text.text_cleaners)
        )
        L = max(16, -(-len(seq) // 16) * 16)
        texts = np.zeros((1, L), np.int32)
        texts[0, : len(seq)] = seq
        b = {
            "speakers": jnp.zeros((1,), jnp.int32),
            "texts": jnp.asarray(texts),
            "src_lens": jnp.asarray([len(seq)], jnp.int32),
            # reference mel for the style encoder (single mode requires
            # --ref_audio; a fixed mel stands in — same compute)
            "mels": ref_mel,
            "mel_lens": jnp.asarray([T_lat], jnp.int32),
        }
        mel = fwd1(variables, b)
        wav = voc(params, mel)  # the batch-8 jit respecializes for batch 1
        return np.asarray(wav)  # device->host: part of the user's latency

    ref_mel = jnp.asarray(rng.standard_normal((1, T_lat, n_mels)), jnp.float32)
    text_to_wav()  # compile + warm
    lat = []
    for _ in range(30):
        t0 = time.perf_counter()
        text_to_wav()
        lat.append((time.perf_counter() - t0) * 1e3)
    lat.sort()
    p50 = lat[len(lat) // 2]
    p95 = lat[int(len(lat) * 0.95)]
    audio_s = T_lat * hop / sr
    print(json.dumps({
        "metric": "synthesis_batch1_latency_ms",
        "value": round(p50, 1),
        "unit": f"ms p50 warm text->wav ({audio_s:.1f}s utterance, incl. "
                "G2P + D2H wav read)",
        "p95_ms": round(p95, 1),
        "realtime_factor": round(audio_s * 1e3 / p50, 1),
    }))


def _tiny_serve_config():
    """A deliberately small model + lattice for CPU serve measurement:
    on CPU the point is the *scheduling* win (dispatch overhead
    amortization through coalescing), which a tiny model isolates —
    labeled "tiny-cpu" in every emitted line so it can never be confused
    with a TPU number."""
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
            max_seq_len=48,
            # bf16 is software-emulated on CPU; f32 keeps the tiny model's
            # per-item compute honest
            compute_dtype="float32",
        ),
        serve=ServeConfig(
            batch_buckets=[1, 2, 4, 8, 16, 32],
            src_buckets=[16],
            mel_buckets=[32],
            frames_per_phoneme=2,
            max_wait_ms=5.0,
            queue_depth=128,
            style=StyleConfig(ref_buckets=[32], batch_buckets=[1, 8, 32]),
        ),
    )


def _serve_engine(tiny: bool, mesh=None):
    """(engine, model_label): tiny CPU engine, or the flagship config +
    random weights on an accelerator (compute identical to trained).
    ``mesh=(dp, tp)`` makes the engine a mesh-slice replica: the lattice
    compiles with explicit NamedShardings over a resolve_mesh slice —
    the --mesh-serve sweep's subject."""
    import dataclasses

    import numpy as np

    import jax

    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.serving.engine import SynthesisEngine
    from speakingstyle_tpu.serving.lattice import BucketLattice
    from speakingstyle_tpu.synthesis import get_vocoder

    if tiny:
        from speakingstyle_tpu.models.hifigan import Generator

        cfg = _tiny_serve_config()
        label = "tiny-cpu"
        gen = Generator(
            upsample_rates=(2, 2), upsample_kernel_sizes=(4, 4),
            upsample_initial_channel=16, resblock_kernel_sizes=(3,),
            resblock_dilation_sizes=((1,),),
        )
        n_mels = cfg.preprocess.preprocessing.mel.n_mel_channels
        vocoder = (gen, gen.init(
            jax.random.PRNGKey(0), np.zeros((1, 8, n_mels), np.float32)
        )["params"])
    else:
        from speakingstyle_tpu.configs.config import Config

        cfg = _apply_overrides(Config(), TUNED_OVERRIDES)
        label = "flagship"
        vocoder = get_vocoder(cfg)
    if mesh is not None:
        from speakingstyle_tpu.configs.config import ParallelConfig

        cfg = dataclasses.replace(cfg, serve=dataclasses.replace(
            cfg.serve, parallel=ParallelConfig(mesh=list(mesh))
        ))
        label = f"{label}-{mesh[0]}x{mesh[1]}"
    lattice = BucketLattice.from_config(cfg.serve)
    n_position = max(lattice.max_mel, lattice.max_src,
                     cfg.model.max_seq_len) + 1
    model = build_model(cfg, n_position=n_position)
    variables = init_variables(model, cfg, jax.random.PRNGKey(0))
    engine = SynthesisEngine(
        cfg, variables, vocoder=vocoder, lattice=lattice, model=model
    )
    return engine, label


def run_serve(duration: float = 3.0, clients=(1, 2, 4, 8, 16, 32)):
    """Offered-load sweep over the continuous-batching serve path.

    Closed-loop clients (each submits, waits, resubmits) against the
    AOT-precompiled engine + batcher; reports QPS, latency percentiles,
    the batch-occupancy histogram, and the compile counter — which MUST
    read zero after warmup (the acceptance invariant the smoke test also
    asserts). Finishes with the coalesced-vs-sequential speedup line.

    Latency percentiles come straight out of the serving stack's own
    ``serve_request_latency_seconds`` histogram (a fresh MetricsRegistry
    per load point), NOT a bench-side raw-latency list: the bench reports
    exactly what a /metrics scrape of the same traffic would.
    """
    import numpy as np

    import jax

    from speakingstyle_tpu.obs import MetricsRegistry
    from speakingstyle_tpu.serving.batcher import ContinuousBatcher
    from speakingstyle_tpu.serving.engine import CompileMonitor, SynthesisRequest

    _mark("building serve engine")
    tiny = not _is_tpu()
    engine, label = _serve_engine(tiny)
    n_mels = engine.n_mels
    serve = engine.cfg.serve
    rng = np.random.default_rng(0)
    max_src = serve.src_buckets[-1]
    max_len = min(max_src, serve.mel_buckets[-1] // serve.frames_per_phoneme)
    # steady-state style traffic is cache hits (styles repeat; that is
    # the StyleService's design premise) — this sweep measures the
    # coalescing scheduler, so requests draw from a hot reference pool;
    # the hit-rate dimension has its own sweep (run_style)
    max_ref = engine.style.lattice.max_ref if engine.style is not None else 8
    hot_refs = [
        rng.standard_normal(
            (int(rng.integers(max(8, max_ref // 2), max_ref + 1)), n_mels)
        ).astype(np.float32)
        for _ in range(8)
    ]

    def make_request(i: int) -> SynthesisRequest:
        L = int(rng.integers(max(4, max_len // 2), max_len + 1))
        return SynthesisRequest(
            id=f"bench{i}",
            sequence=rng.integers(1, 300, L).astype(np.int32),
            ref_mel=hot_refs[i % len(hot_refs)],
        )

    _mark(f"precompiling {len(engine.lattice)} lattice points")
    secs = engine.precompile()
    compiles_startup = engine.compile_count
    _mark(f"precompiled {compiles_startup} programs in {secs:.1f}s")

    # warmup: one dispatch per batch bucket (first-execution transfer and
    # dispatch-path setup; compiles already happened above)
    for b in engine.lattice.batch_buckets:
        engine.run([make_request(10_000 + b * 100 + j) for j in range(b)])

    # sequential batch-1 baseline: the pre-serving deployment model —
    # one request, one dispatch, no coalescing
    seq_n = 0
    with CompileMonitor() as mon:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < duration:
            engine.run([make_request(seq_n)])
            seq_n += 1
        seq_dt = time.perf_counter() - t0
    seq_qps = seq_n / seq_dt
    print(json.dumps({
        "metric": "serve_sequential_batch1_qps",
        "value": round(seq_qps, 2),
        "unit": "requests/sec (one dispatch per request)",
        "model": label,
        "compiles_during_run": mon.count,
    }))

    best_qps = 0.0
    zero_compiles = True
    for n_clients in clients:
        # a fresh registry per load point: its request-latency histogram
        # and occupancy counters ARE this point's report
        point = MetricsRegistry()
        batcher = ContinuousBatcher(engine, registry=point)
        stop_at = time.perf_counter() + duration

        def client(cid: int):
            i = 0
            while time.perf_counter() < stop_at:
                req = make_request(cid * 1_000_000 + i)
                try:
                    batcher.submit(req).result(timeout=60)
                except Exception:
                    return
                i += 1

        with CompileMonitor() as mon:
            threads = [
                threading.Thread(target=client, args=(c,), daemon=True)
                for c in range(n_clients)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            batcher.close()
        hist = point.histogram("serve_request_latency_seconds")
        qps = hist.count / dt
        best_qps = max(best_qps, qps)
        zero_compiles = zero_compiles and mon.count == 0

        def pct_ms(q):
            p = hist.percentile(q)
            return round(1e3 * p, 1) if p is not None else None

        print(json.dumps({
            "metric": "serve_offered_load",
            "clients": n_clients,
            "qps": round(qps, 2),
            "p50_ms": pct_ms(0.50),
            "p95_ms": pct_ms(0.95),
            "p99_ms": pct_ms(0.99),
            "p999_ms": pct_ms(0.999),
            "batch_occupancy": dict(sorted(batcher.occupancy.items())),
            "compiles_during_serve": mon.count,
            "model": label,
        }))

    print(json.dumps({
        "metric": "serve_speedup_vs_sequential",
        "value": round(best_qps / seq_qps, 2) if seq_qps else None,
        "unit": "x (best coalesced QPS / sequential batch-1 QPS)",
        "sequential_qps": round(seq_qps, 2),
        "best_qps": round(best_qps, 2),
        "zero_compiles_after_warmup": zero_compiles,
        "aot_programs": compiles_startup,
        "model": label,
    }))
    return best_qps / seq_qps if seq_qps else None


def run_latency(duration: float = 3.0):
    """Warm batch-1 closed-loop latency drill over the FULL server path
    (handler -> frontend -> batcher -> engine -> streamed chunks), once
    with the latency pipeline off (frontend_workers=0, stream_depth=1:
    the pre-pipeline serial path) and once on (pooled frontend +
    double-buffered streaming vocode).

    Per mode it records TTFA and full-utterance p50/p95/p99/p999 plus a
    per-stage p50 breakdown (frontend / queue / acoustic / vocoder /
    emit) read straight from the serving stack's own Span-fed stage
    histograms — the same numbers a /metrics scrape reports.  A
    CompileMonitor spans the measured loop: warm batch-1 serving must
    perform ZERO compiles in either mode.

    Single-core caveat, recorded in the summary line: the pipeline's win
    is overlap (frontend under the coalescing wait, vocode window k+1
    dispatched under window k's readback), so with one host core the
    on/off ratio is roughly flat here — the honest ablation is still
    recorded so a real-parallelism host has a baseline to beat.
    """
    import dataclasses

    import numpy as np

    import jax

    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.models.hifigan import Generator
    from speakingstyle_tpu.obs import MetricsRegistry
    from speakingstyle_tpu.serving.engine import (
        CompileMonitor,
        SynthesisEngine,
    )
    from speakingstyle_tpu.serving.server import SynthesisServer, TextFrontend

    base = _tiny_serve_config()
    label = "tiny-cpu" if not _is_tpu() else "flagship"

    def mode_config(workers: int, depth: int):
        # short stream windows so one utterance emits several chunks —
        # the double-buffered pipeline needs something to overlap; tight
        # batch/style buckets keep the per-mode precompile cheap (a
        # batch-1 closed loop never fills larger buckets anyway)
        fleet = dataclasses.replace(
            base.serve.fleet, stream_window=8, stream_depth=depth
        )
        serve = dataclasses.replace(
            base.serve, batch_buckets=[1, 2], frontend_workers=workers,
            fleet=fleet,
            style=dataclasses.replace(base.serve.style, batch_buckets=[1]),
        )
        return dataclasses.replace(base, serve=serve)

    _mark("building latency-drill model parts")
    n_position = max(base.serve.mel_buckets[-1], base.serve.src_buckets[-1],
                     base.model.max_seq_len) + 1
    model = build_model(base, n_position=n_position)
    variables = init_variables(model, base, jax.random.PRNGKey(0))
    # random-init duration predictors round most durations to zero; the
    # bias bump guarantees a non-trivial mel so the stream emits real
    # windows (the serving tests use the same trick)
    bias = variables["params"]["variance_adaptor"]["duration_predictor"][
        "linear_layer"]["bias"]
    variables["params"]["variance_adaptor"]["duration_predictor"][
        "linear_layer"]["bias"] = bias + 1.1
    gen = Generator(
        upsample_rates=(2, 2), upsample_kernel_sizes=(4, 4),
        upsample_initial_channel=16, resblock_kernel_sizes=(3,),
        resblock_dilation_sizes=((1,),),
    )
    n_mels = base.preprocess.preprocessing.mel.n_mel_channels
    gparams = gen.init(
        jax.random.PRNGKey(0), np.zeros((1, 8, n_mels), np.float32)
    )["params"]
    rng = np.random.default_rng(0)
    ref = rng.standard_normal((20, n_mels)).astype(np.float32)
    payload = {"text": "the quick brown fox jumps over the lazy dog "
                       "near the river bank"}

    stage_hists = {
        "frontend": "serve_frontend_seconds",
        "queue": "serve_queue_wait_seconds",
        "acoustic": "serve_acoustic_seconds",
        "vocoder": "serve_vocoder_seconds",
        "emit": "serve_emit_seconds",
    }
    by_mode = {}
    for mode, workers, depth in (("off", 0, 1), ("on", 2, 2)):
        cfg = mode_config(workers, depth)
        reg = MetricsRegistry()
        engine = SynthesisEngine(
            cfg, variables, vocoder=(gen, gparams), model=model,
            registry=reg,
        )
        _mark(f"[{mode}] precompiling {len(engine.lattice)} lattice points")
        engine.precompile()
        server = SynthesisServer(
            engine, TextFrontend(cfg, ref), host="127.0.0.1", port=0
        )
        threading.Thread(target=server.serve_forever, daemon=True).start()
        full_hist = reg.histogram(
            "bench_full_utterance_seconds",
            help="submit -> last streamed chunk consumed",
        )
        try:
            for _ in range(10):   # first-execution transfers + style cache
                result = server.synthesize(payload, stream=True)
                for _ in server.stream_chunks(result,
                                              arrival=time.monotonic()):
                    pass
            n = 0
            stop_at = time.perf_counter() + duration
            with CompileMonitor() as mon:
                while time.perf_counter() < stop_at:
                    t0 = time.monotonic()
                    result = server.synthesize(payload, stream=True)
                    for _ in server.stream_chunks(result, arrival=t0):
                        pass
                    full_hist.observe(time.monotonic() - t0)
                    n += 1
        finally:
            server.shutdown()

        def pct_ms(name, q):
            p = reg.histogram(name).percentile(q)
            return round(1e3 * p, 2) if p is not None else None

        point = {
            "metric": "serve_latency",
            "pipeline": mode,
            "frontend_workers": workers,
            "stream_depth": depth,
            "requests": n,
            "ttfa_p50_ms": pct_ms("serve_ttfa_seconds", 0.50),
            "ttfa_p95_ms": pct_ms("serve_ttfa_seconds", 0.95),
            "ttfa_p99_ms": pct_ms("serve_ttfa_seconds", 0.99),
            "ttfa_p999_ms": pct_ms("serve_ttfa_seconds", 0.999),
            "full_p50_ms": pct_ms("bench_full_utterance_seconds", 0.50),
            "full_p95_ms": pct_ms("bench_full_utterance_seconds", 0.95),
            "full_p99_ms": pct_ms("bench_full_utterance_seconds", 0.99),
            "full_p999_ms": pct_ms("bench_full_utterance_seconds", 0.999),
            "stage_p50_ms": {k: pct_ms(h, 0.50)
                             for k, h in stage_hists.items()},
            "compiles_during_run": mon.count,
            "model": label,
        }
        by_mode[mode] = point
        print(json.dumps(point))

    off, on = by_mode.get("off", {}), by_mode.get("on", {})
    ratio = (
        round(on["ttfa_p50_ms"] / off["ttfa_p50_ms"], 3)
        if on.get("ttfa_p50_ms") and off.get("ttfa_p50_ms") else None
    )
    print(json.dumps({
        "metric": "serve_latency_floor",
        "ttfa_p50_ms": on.get("ttfa_p50_ms"),
        "full_p50_ms": on.get("full_p50_ms"),
        "pipeline_on_over_off_ttfa_p50": ratio,
        "zero_compiles_warm": (off.get("compiles_during_run") == 0
                               and on.get("compiles_during_run") == 0),
        "note": "on/off ratio is an overlap measure and needs >1 host "
                "core to show; compare ttfa_p50_ms against the previous "
                "round's streaming TTFA for the floor claim",
        "model": label,
    }))
    return ratio


def run_style(duration: float = 3.0, hit_rates=(0.0, 0.5, 0.9, 1.0),
              clients: int = 16):
    """Style-path sweep: repeat-style hit-rate mix x offered load over
    the StyleService + engine (serving/style.py).

    Closed-loop clients submit through the continuous batcher; with
    probability ``hit_rate`` a request reuses one of a small hot pool of
    pre-encoded references (carrying cached (gamma, beta) — zero encoder
    work), otherwise it ships a FRESH reference mel the engine must
    resolve through the style service (cache miss -> one padded encoder
    dispatch). Per point: QPS, the cache-hit vs cold-encode latency
    split (two bench-side histograms classified by what the client
    sent), the service's own hit/miss/encode counter deltas, and a
    CompileMonitor that must read zero — the style path inherits the
    zero-steady-state-compiles invariant.
    """
    import numpy as np

    import jax

    from speakingstyle_tpu.obs import MetricsRegistry
    from speakingstyle_tpu.serving.batcher import ContinuousBatcher
    from speakingstyle_tpu.serving.engine import (
        CompileMonitor,
        SynthesisRequest,
    )

    _mark("building style-serve engine")
    tiny = not _is_tpu()
    engine, label = _serve_engine(tiny)
    style = engine.style
    n_mels = engine.n_mels
    serve = engine.cfg.serve
    max_ref = style.lattice.max_ref
    max_len = min(serve.src_buckets[-1],
                  serve.mel_buckets[-1] // serve.frames_per_phoneme)
    rng = np.random.default_rng(0)

    _mark(f"precompiling {len(engine.lattice)} synthesis + "
          f"{len(style.lattice)} style points")
    secs = engine.precompile()
    _mark(f"precompiled {engine.compile_count}+{style.compile_count} "
          f"programs in {secs:.1f}s")

    # hot pool: the repeat styles (a voice library) — encoded once here;
    # hot requests RE-SEND the same reference bytes, so the sweep
    # measures the content-addressed path end to end (digest + cache
    # hit + zero encoder work), exactly what a repeat `ref_audio` or
    # `style_id` request costs
    hot_mels = [
        rng.standard_normal((max_ref, n_mels)).astype(np.float32)
        for _ in range(8)
    ]
    style.encode_mels(hot_mels)

    def make_request(i: int, cached: bool) -> SynthesisRequest:
        L = int(rng.integers(max(4, max_len // 2), max_len + 1))
        seq = rng.integers(1, 300, L).astype(np.int32)
        if cached:
            return SynthesisRequest(
                id=f"style{i}", sequence=seq,
                ref_mel=hot_mels[i % len(hot_mels)],
            )
        t_ref = int(rng.integers(max(8, max_ref // 2), max_ref + 1))
        return SynthesisRequest(
            id=f"style{i}", sequence=seq,
            ref_mel=rng.standard_normal((t_ref, n_mels)).astype(np.float32),
        )

    # warmup: every batch bucket once, mixed cached/fresh rows
    for b in engine.lattice.batch_buckets:
        engine.run([make_request(10_000 + b * 100 + j, j % 2 == 0)
                    for j in range(b)])

    split_ratio = None
    all_zero = True
    qps_by_rate = {}
    for hit_rate in hit_rates:
        point = MetricsRegistry()
        hit_hist = point.histogram(
            "bench_style_hit_seconds",
            help="latency of requests shipping cached style vectors",
        )
        cold_hist = point.histogram(
            "bench_style_cold_seconds",
            help="latency of requests shipping a fresh reference mel",
        )
        hits0 = style.registry.value("serve_style_cache_hits_total")
        miss0 = style.registry.value("serve_style_cache_misses_total")
        enc0 = style.dispatch_count
        batcher = ContinuousBatcher(engine, registry=point)
        stop_at = time.perf_counter() + duration
        done = [0] * clients

        def client(cid: int):
            crng = np.random.default_rng(cid)
            i = 0
            while time.perf_counter() < stop_at:
                cached = bool(crng.random() < hit_rate)
                req = make_request(cid * 1_000_000 + i, cached)
                t0 = time.monotonic()
                try:
                    batcher.submit(req).result(timeout=60)
                except Exception:
                    return
                (hit_hist if cached else cold_hist).observe(
                    time.monotonic() - t0
                )
                done[cid] += 1
                i += 1

        with CompileMonitor() as mon:
            threads = [
                threading.Thread(target=client, args=(c,), daemon=True)
                for c in range(clients)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            batcher.close()
        qps = sum(done) / dt
        qps_by_rate[hit_rate] = qps
        all_zero = all_zero and mon.count == 0

        def pct_ms(hist, q):
            p = hist.percentile(q)
            return round(1e3 * p, 1) if p is not None else None

        rec = {
            "metric": "serve_style_load",
            "hit_rate": hit_rate,
            "clients": clients,
            "qps": round(qps, 2),
            "hit_p50_ms": pct_ms(hit_hist, 0.50),
            "hit_p95_ms": pct_ms(hit_hist, 0.95),
            "cold_p50_ms": pct_ms(cold_hist, 0.50),
            "cold_p95_ms": pct_ms(cold_hist, 0.95),
            "cache_hits": int(
                style.registry.value("serve_style_cache_hits_total") - hits0
            ),
            "cache_misses": int(
                style.registry.value("serve_style_cache_misses_total")
                - miss0
            ),
            "encoder_dispatches": style.dispatch_count - enc0,
            "compiles_during_serve": mon.count,
            "model": label,
        }
        if rec["hit_p50_ms"] and rec["cold_p50_ms"]:
            split_ratio = round(rec["cold_p50_ms"] / rec["hit_p50_ms"], 2)
        print(json.dumps(rec))

    base = qps_by_rate.get(hit_rates[0])
    top = qps_by_rate.get(hit_rates[-1])
    gain = round(top / base, 2) if base and top else None
    print(json.dumps({
        "metric": "serve_style_cache_qps_gain",
        "value": gain,
        "unit": "x (QPS all-cached / QPS all-cold, same offered load)",
        "qps_all_cold": round(base, 2) if base else None,
        "qps_all_cached": round(top, 2) if top else None,
        "cold_over_hit_p50": split_ratio,
        "cache_entries": len(style),
        "evictions": int(
            style.registry.value("serve_style_cache_evictions_total")
        ),
        "zero_compiles_after_warmup": all_zero,
        "model": label,
    }))
    return gain


def _fleet_proxy_config():
    """The fleet-sweep CPU config: the tiny model (scheduling isolated
    from compute, as in _tiny_serve_config) with TWO mel buckets so
    streaming windows ride a smaller vocoder bucket than full
    utterances, and a fleet block sized for the sweep."""
    import dataclasses

    from speakingstyle_tpu.configs.config import (
        FleetConfig,
        ServeConfig,
        StyleConfig,
    )

    cfg = _tiny_serve_config()
    return dataclasses.replace(cfg, serve=ServeConfig(
        batch_buckets=[1, 2, 4, 8],
        src_buckets=[16],
        mel_buckets=[24, 64],
        frames_per_phoneme=4,
        max_wait_ms=5.0,
        queue_depth=128,
        # stream_depth pinned to the sequential path: the proxy floor
        # serializes window collects per replica, so depth>1 cannot
        # overlap anything here — it only reorders a saturated queue
        # (streams' pre-queued windows cut ahead of other streams' first
        # windows, inflating TTFA tails ~10-15%), which would misread as
        # a router regression. The pipeline dimension is measured where
        # it is real: run_latency (closed-loop, actual JAX dispatch).
        fleet=FleetConfig(stream_window=8, queue_depth=256,
                          stream_depth=1),
        style=StyleConfig(ref_buckets=[64]),
    ))


class ProxyDeviceEngine:
    """CPU-proxy stand-in for an accelerator-backed replica.

    Wraps the tiny engine and adds a GIL-released per-dispatch floor
    (``time.sleep`` scaled by the dispatched mel bucket) serialized by a
    per-replica lock — i.e. each replica behaves like one busy device.
    On a single-core host the real tiny-model compute cannot
    parallelize, so without this the sweep would measure the host core,
    not the router; with it, the replicas-axis measures exactly what the
    fleet router adds or costs (admission, EDF pop contention,
    per-replica pipelines). Every emitted line carries the
    ``tiny-cpu-proxydev`` label so these numbers can never be confused
    with device throughput.
    """

    def __init__(self, inner, device_ms: float):
        self._inner = inner
        self._device_ms = device_ms
        self._device_lock = threading.Lock()

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def _occupy(self, t_mel: int):
        if self._device_ms <= 0:
            return
        with self._device_lock:  # one device: its work serializes
            time.sleep(self._device_ms / 1e3
                       * t_mel / self._inner.lattice.max_mel)

    def precompile(self):
        return self._inner.precompile()

    def run(self, requests):
        out = self._inner.run(requests)
        if out:
            self._occupy(out[0].bucket.t_mel)
        return out

    def vocode_window(self, mel):
        wav = self._inner.vocode_window(mel)
        self._occupy(self._inner.lattice.cover_window(mel.shape[0])[1])
        return wav

    # the pipelined stream path (serving/streaming.py) talks
    # dispatch/collect, not vocode_window: the device floor rides the
    # collect (the sync point), so in-flight windows still overlap the
    # host side exactly as a real device would
    def vocode_dispatch(self, mel, klass=None, trace=None):
        return self._inner.vocode_dispatch(mel, klass=klass, trace=trace)

    def vocode_collect(self, handle):
        wav = self._inner.vocode_collect(handle)
        self._occupy(self._inner.lattice.cover_window(handle.t_w)[1])
        return wav


def run_fleet(duration: float = 3.0, replica_counts=(1, 2, 4),
              clients: int = 32, device_ms: float = 20.0):
    """Fleet sweep: replicas x offered load over the SLO router, with
    chunked streaming — records time-to-first-audio p50/p95 alongside
    full-utterance latency, per replica count.

    Closed-loop clients submit STREAMING requests (alternating
    interactive/batch priority classes) and consume every chunk; TTFA
    comes from the router's own ``serve_ttfa_seconds`` histogram (what a
    /metrics scrape reports), full-utterance latency from a bench-side
    histogram observed at the last chunk. A CompileMonitor spans each
    load point: steady-state fleet serving must perform ZERO compiles on
    any replica.
    """
    import numpy as np

    import jax

    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.models.hifigan import Generator
    from speakingstyle_tpu.obs import MetricsRegistry
    from speakingstyle_tpu.serving.engine import (
        CompileMonitor,
        SynthesisEngine,
        SynthesisRequest,
    )
    from speakingstyle_tpu.serving.fleet import FleetRouter
    from speakingstyle_tpu.serving.style import StyleService

    on_tpu = _is_tpu()
    if on_tpu:
        device_ms = 0.0  # real device time: no proxy floor
    label = "tiny-cpu-proxydev" if device_ms > 0 else (
        "flagship" if on_tpu else "tiny-cpu"
    )
    _mark("building fleet model parts")
    cfg = _fleet_proxy_config()
    serve = cfg.serve
    n_position = max(serve.mel_buckets[-1], serve.src_buckets[-1],
                     cfg.model.max_seq_len) + 1
    model = build_model(cfg, n_position=n_position)
    variables = init_variables(model, cfg, jax.random.PRNGKey(0))
    gen = Generator(
        upsample_rates=(2, 2), upsample_kernel_sizes=(4, 4),
        upsample_initial_channel=16, resblock_kernel_sizes=(3,),
        resblock_dilation_sizes=((1,),),
    )
    n_mels = cfg.preprocess.preprocessing.mel.n_mel_channels
    gparams = gen.init(
        jax.random.PRNGKey(0), np.zeros((1, 8, n_mels), np.float32)
    )["params"]
    rng = np.random.default_rng(0)
    max_len = min(serve.src_buckets[-1],
                  serve.mel_buckets[-1] // serve.frames_per_phoneme)
    # hot reference pool, as in run_serve: the replicas axis measures
    # the router, not style encoding (run_style owns that dimension)
    max_ref = serve.style.ref_buckets[-1]
    hot_refs = [
        rng.standard_normal(
            (int(rng.integers(8, max_ref + 1)), n_mels)
        ).astype(np.float32)
        for _ in range(8)
    ]

    def make_request(i: int, priority: str) -> SynthesisRequest:
        L = int(rng.integers(max(4, max_len // 2), max_len + 1))
        return SynthesisRequest(
            id=f"fleet{i}",
            sequence=rng.integers(1, 300, L).astype(np.int32),
            ref_mel=hot_refs[i % len(hot_refs)],
            stream=True,
            priority=priority,
        )

    qps_by_replicas = {}
    ttfa_ratio = None
    all_zero_compiles = True
    for n_replicas in replica_counts:
        registry = MetricsRegistry()
        # one style service fleet-wide (the cli/serve.py wiring): one
        # embedding cache, one encoder lattice, first warm-up compiles it
        shared_style = StyleService(cfg, variables, registry=registry)

        def factory(reg):
            return ProxyDeviceEngine(
                SynthesisEngine(
                    cfg, variables, vocoder=(gen, gparams), model=model,
                    registry=reg, style=shared_style,
                ),
                device_ms,
            )

        _mark(f"warming {n_replicas} replicas")
        router = FleetRouter(factory, cfg, replicas=n_replicas,
                             registry=registry, style=shared_style)
        if not router.wait_ready(timeout=600, n=n_replicas):
            print(json.dumps({
                "metric": "serve_fleet_load", "replicas": n_replicas,
                "error": "replicas never became ready", "model": label,
            }))
            router.close()
            continue
        for engine in router.engines():  # first-execution transfer warmup
            for b in engine.lattice.batch_buckets:
                engine.run([make_request(10_000 + b * 100 + j, "batch")
                            for j in range(b)])
        full_hist = registry.histogram(
            "bench_full_utterance_seconds",
            help="submit -> last streamed chunk consumed",
        )
        stop_at = time.perf_counter() + duration
        done = [0] * clients

        def client(cid: int):
            i = 0
            while time.perf_counter() < stop_at:
                prio = "interactive" if (cid + i) % 2 == 0 else "batch"
                req = make_request(cid * 1_000_000 + i, prio)
                t0 = time.monotonic()
                try:
                    result = router.submit(req).result(timeout=60)
                    for _ in router.stream(result, arrival=t0):
                        pass
                except Exception:
                    time.sleep(0.002)  # shed/backoff; keep offering load
                    i += 1
                    continue
                full_hist.observe(time.monotonic() - t0)
                done[cid] += 1
                i += 1

        with CompileMonitor() as mon:
            threads = [threading.Thread(target=client, args=(c,), daemon=True)
                       for c in range(clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            router.close()
        ttfa = registry.histogram("serve_ttfa_seconds")
        qps = sum(done) / dt
        qps_by_replicas[n_replicas] = qps
        all_zero_compiles = all_zero_compiles and mon.count == 0

        def pct_ms(hist, q):
            p = hist.percentile(q)
            return round(1e3 * p, 1) if p is not None else None

        point = {
            "metric": "serve_fleet_load",
            "replicas": n_replicas,
            "clients": clients,
            "qps": round(qps, 2),
            "ttfa_p50_ms": pct_ms(ttfa, 0.50),
            "ttfa_p95_ms": pct_ms(ttfa, 0.95),
            "ttfa_p999_ms": pct_ms(ttfa, 0.999),
            "full_p50_ms": pct_ms(full_hist, 0.50),
            "full_p95_ms": pct_ms(full_hist, 0.95),
            "full_p999_ms": pct_ms(full_hist, 0.999),
            "shed": int(registry.value("serve_shed_total")),
            "compiles_during_serve": mon.count,
            "proxy_device_ms": device_ms,
            "model": label,
        }
        if n_replicas == replica_counts[0] and point["ttfa_p50_ms"] and \
                point["full_p50_ms"]:
            ttfa_ratio = round(point["ttfa_p50_ms"] / point["full_p50_ms"], 3)
        print(json.dumps(point))

    base = qps_by_replicas.get(replica_counts[0])
    top = qps_by_replicas.get(replica_counts[-1])
    scaling = round(top / base, 2) if base and top else None
    print(json.dumps({
        "metric": "serve_fleet_scaling",
        "value": scaling,
        "unit": f"x (QPS at {replica_counts[-1]} replicas / QPS at "
                f"{replica_counts[0]})",
        "qps_by_replicas": {str(k): round(v, 2)
                            for k, v in qps_by_replicas.items()},
        "ttfa_over_full_p50": ttfa_ratio,
        "zero_compiles_after_warmup": all_zero_compiles,
        "proxy_device_ms": device_ms,
        "model": label,
    }))
    return scaling


def _lock_witness_stats():
    """Lock-witness numbers for a drill point, or empties when
    SPEAKINGSTYLE_CHECKS is off.  TrackedLock exports to the
    process-global registry (not the drill's own), so read from there:
    max p999 hold across every tracked lock + the inversion count (the
    drill invariant: ZERO — an inversion also raises in-line, so a
    nonzero count here means a worker thread died on it)."""
    from speakingstyle_tpu.obs.locks import checks_enabled
    from speakingstyle_tpu.obs.registry import get_registry

    if not checks_enabled():
        return {"lock_hold_p999_max_s": None, "lock_order_inversions": None}
    reg = get_registry()
    p999s = [
        h.percentile(0.999)
        for h in reg.metrics_named("lock_hold_seconds")
        if h.count
    ]
    return {
        "lock_hold_p999_max_s": (
            round(max(p999s), 6) if p999s else None
        ),
        "lock_order_inversions": int(
            reg.value("lock_order_inversions_total")
        ),
    }


def run_chaos(duration: float = 3.0, clients: int = 16,
              device_ms: float = 20.0):
    """Chaos drill: kill one of two replicas at a deterministic dispatch
    count under steady load and measure what supervision costs.

    Three phases over the same fleet (the run_fleet CPU-proxy setup):
    prefault steady load, a chaos phase that arms ``replica_raise`` on
    the next dispatch (quiesced between phases so the armed counter
    cannot be raced past), and a postfault steady phase once both
    replicas are READY again. A monitor thread polls replica states to
    timestamp the failure and the recovery. Closed-loop clients await
    every request they submit, so the lost-request count is exact:
    anything that neither returned a result nor was intentionally shed
    (Overloaded) counts as lost — the drill's invariant is that this is
    ZERO. CompileMonitor spans the prefault and postfault phases (the
    re-warm recompile between them is the one legitimate compile window).
    """
    import dataclasses

    import numpy as np

    import jax

    from speakingstyle_tpu.configs.config import FleetConfig
    from speakingstyle_tpu.faults import FaultPlan
    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.models.hifigan import Generator
    from speakingstyle_tpu.obs import MetricsRegistry
    from speakingstyle_tpu.serving.batcher import Overloaded
    from speakingstyle_tpu.serving.engine import (
        CompileMonitor,
        SynthesisEngine,
        SynthesisRequest,
    )
    from speakingstyle_tpu.serving.fleet import FAILED, READY, FleetRouter
    from speakingstyle_tpu.serving.style import StyleService

    on_tpu = _is_tpu()
    if on_tpu:
        device_ms = 0.0
    label = "tiny-cpu-proxydev" if device_ms > 0 else (
        "flagship" if on_tpu else "tiny-cpu"
    )
    _mark("building chaos fleet parts")
    cfg = _fleet_proxy_config()
    # generous deadline budgets: the drill measures supervision (requeue
    # + re-warm), so scheduling-induced expiry must not masquerade as
    # loss; a short re-warm backoff keeps the recovery window tight
    cfg = dataclasses.replace(cfg, serve=dataclasses.replace(
        cfg.serve, fleet=FleetConfig(
            stream_window=8, queue_depth=256,
            class_deadline_ms={"interactive": 30_000.0, "batch": 60_000.0},
            rewarm_backoff_s=0.2, rewarm_backoff_max_s=5.0,
        ),
    ))
    serve = cfg.serve
    n_position = max(serve.mel_buckets[-1], serve.src_buckets[-1],
                     cfg.model.max_seq_len) + 1
    model = build_model(cfg, n_position=n_position)
    variables = init_variables(model, cfg, jax.random.PRNGKey(0))
    gen = Generator(
        upsample_rates=(2, 2), upsample_kernel_sizes=(4, 4),
        upsample_initial_channel=16, resblock_kernel_sizes=(3,),
        resblock_dilation_sizes=((1,),),
    )
    n_mels = cfg.preprocess.preprocessing.mel.n_mel_channels
    gparams = gen.init(
        jax.random.PRNGKey(0), np.zeros((1, 8, n_mels), np.float32)
    )["params"]
    rng = np.random.default_rng(0)
    max_len = min(serve.src_buckets[-1],
                  serve.mel_buckets[-1] // serve.frames_per_phoneme)
    max_ref = serve.style.ref_buckets[-1]
    hot_refs = [
        rng.standard_normal(
            (int(rng.integers(8, max_ref + 1)), n_mels)
        ).astype(np.float32)
        for _ in range(8)
    ]

    def make_request(i: int, priority: str) -> SynthesisRequest:
        L = int(rng.integers(max(4, max_len // 2), max_len + 1))
        return SynthesisRequest(
            id=f"chaos{i}",
            sequence=rng.integers(1, 300, L).astype(np.int32),
            ref_mel=hot_refs[i % len(hot_refs)],
            priority=priority,
        )

    registry = MetricsRegistry()
    plan = FaultPlan()
    shared_style = StyleService(cfg, variables, registry=registry)

    def factory(reg):
        return ProxyDeviceEngine(
            SynthesisEngine(
                cfg, variables, vocoder=(gen, gparams), model=model,
                registry=reg, style=shared_style,
            ),
            device_ms,
        )

    _mark("warming 2 chaos replicas")
    router = FleetRouter(factory, cfg, replicas=2, registry=registry,
                         style=shared_style, fault_plan=plan)
    if not router.wait_ready(timeout=600, n=2):
        print(json.dumps({
            "metric": "serve_chaos", "replicas": 2,
            "error": "replicas never became ready", "model": label,
        }))
        router.close()
        return None

    def transfer_warmup(base: int):
        for engine in router.engines():
            for b in engine.lattice.batch_buckets:
                engine.run([make_request(base + b * 100 + j, "batch")
                            for j in range(b)])

    transfer_warmup(10_000_000)

    def load_phase(phase_s: float, seed: int):
        """Closed-loop load; every submitted request is awaited. Returns
        {ok, shed, lost, errors, qps}."""
        stop_at = time.perf_counter() + phase_s
        per = [dict(ok=0, shed=0, lost=0, errors=[])
               for _ in range(clients)]

        def client(cid: int):
            c, i = per[cid], 0
            while time.perf_counter() < stop_at:
                prio = "interactive" if (cid + i) % 2 == 0 else "batch"
                req = make_request(seed + cid * 1_000_000 + i, prio)
                try:
                    router.submit(req).result(timeout=120)
                    c["ok"] += 1
                except Overloaded:
                    c["shed"] += 1
                    time.sleep(0.002)
                except Exception as e:  # structured failure OR stuck: lost
                    c["lost"] += 1
                    c["errors"].append(type(e).__name__)
                i += 1

        threads = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        out = {k: sum(c[k] for c in per) for k in ("ok", "shed", "lost")}
        out["errors"] = sorted({e for c in per for e in c["errors"]})
        out["qps"] = out["ok"] / dt
        return out

    _mark("chaos phase A: prefault steady load")
    with CompileMonitor() as pre_mon:
        prefault = load_phase(duration, 0)

    # quiesced between phases: dispatch_total is stable, so the armed
    # counter value deterministically hits the NEXT dispatch
    plan.arm("replica_raise", router.dispatch_total + 1)
    timeline = {}
    stop_mon = threading.Event()

    def monitor():
        while not stop_mon.is_set():
            states = list(router.states().values())
            now = time.perf_counter()
            if FAILED in states and "t_failed" not in timeline:
                timeline["t_failed"] = now
            if ("t_failed" in timeline and "t_recovered" not in timeline
                    and all(s == READY for s in states)):
                timeline["t_recovered"] = now
                return
            time.sleep(0.002)

    mon_thread = threading.Thread(target=monitor, daemon=True)
    mon_thread.start()
    _mark("chaos phase B: replica kill under load")
    chaos = load_phase(duration, 100_000_000)
    # the re-warm (a fresh engine precompiling the full lattice) may
    # outlast the load phase; wait it out before the postfault measure
    deadline = time.monotonic() + 600
    while time.monotonic() < deadline and "t_recovered" not in timeline:
        time.sleep(0.05)
    stop_mon.set()
    mon_thread.join(timeout=5)
    recovered = "t_recovered" in timeline
    recovery_ms = (
        round(1e3 * (timeline["t_recovered"] - timeline["t_failed"]), 1)
        if recovered and "t_failed" in timeline else None
    )
    postfault = None
    post_compiles = None
    if recovered:
        transfer_warmup(20_000_000)  # the re-warmed engine's first runs
        _mark("chaos phase C: postfault steady load")
        with CompileMonitor() as post_mon:
            postfault = load_phase(duration, 200_000_000)
        post_compiles = post_mon.count
    router.close()

    failures = sum(
        int(registry.value("serve_replica_failures_total",
                           {"replica": str(i)}))
        for i in range(2)
    )
    lost = chaos["lost"] + prefault["lost"] + (
        postfault["lost"] if postfault else 0
    )
    ratio = (
        round(postfault["qps"] / prefault["qps"], 3)
        if postfault and prefault["qps"] else None
    )
    point = {
        "metric": "serve_chaos",
        "replicas": 2,
        "clients": clients,
        "prefault_qps": round(prefault["qps"], 2),
        "chaos_qps": round(chaos["qps"], 2),
        "postfault_qps": round(postfault["qps"], 2) if postfault else None,
        "qps_recovery_ratio": ratio,
        "recovery_ms": recovery_ms,
        "lost_requests": lost,
        "shed": prefault["shed"] + chaos["shed"] + (
            postfault["shed"] if postfault else 0
        ),
        "errors": sorted(set(
            prefault["errors"] + chaos["errors"]
            + (postfault["errors"] if postfault else [])
        )),
        "replica_failures": failures,
        "requeued": int(registry.value("serve_requeued_total")),
        "retries": int(registry.value("serve_retries_total",
                                      {"class": "interactive"})
                       + registry.value("serve_retries_total",
                                        {"class": "batch"})),
        "deadline_exceeded": int(
            registry.value("serve_deadline_exceeded_total",
                           {"class": "interactive"})
            + registry.value("serve_deadline_exceeded_total",
                             {"class": "batch"})
        ),
        "compiles_prefault": pre_mon.count,
        "compiles_postfault": post_compiles,
        "recovered": recovered,
        "proxy_device_ms": device_ms,
        "model": label,
        **_lock_witness_stats(),
    }
    print(json.dumps(point))
    return point


def _cluster_proxy_config(device_ms: float = 20.0):
    """The cluster-drill config: the fleet CPU-proxy lattice with the
    chaos drill's generous deadline budgets (the drill measures
    control-plane supervision, not scheduling-induced expiry) plus the
    cluster control-plane block — a short lease TTL (0.25 s beats, miss
    budget 3 -> 1 s) so expiry-to-requeue is measurable inside a bench
    phase, and a spawn grace wide enough for a child process to build +
    AOT-precompile the tiny model on CPU."""
    import dataclasses

    from speakingstyle_tpu.configs.config import ClusterConfig, FleetConfig

    cfg = _fleet_proxy_config()
    return dataclasses.replace(cfg, serve=dataclasses.replace(
        cfg.serve,
        fleet=FleetConfig(
            stream_window=8, queue_depth=256,
            class_deadline_ms={"interactive": 30_000.0, "batch": 60_000.0},
            rewarm_backoff_s=0.2, rewarm_backoff_max_s=5.0,
        ),
        cluster=ClusterConfig(
            enabled=True,
            heartbeat_interval_s=0.25,
            lease_miss_budget=3,
            connect_timeout_s=5.0,
            spawn_grace_s=600.0,
            quorum=2,
            hedge_quantile=0.95,
            hedge_min_ms=50.0,
            hedge_max_ms=2000.0,
        ),
    ))


def _cluster_replica_child(rid: str, router_addr: str,
                           device_ms: float = 20.0):
    """One replica PROCESS of the cluster drill: build the tiny proxy
    engine, AOT-precompile the full lattice, transfer-warm every batch
    bucket, and only then register + serve — the parent measures
    spawn-to-lease as the warm-up cost, and a registered replica must
    never compile under steady load."""
    import os

    import numpy as np

    import jax

    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.models.hifigan import Generator
    from speakingstyle_tpu.obs import MetricsRegistry
    from speakingstyle_tpu.serving.cluster import ReplicaServer
    from speakingstyle_tpu.serving.engine import (
        SynthesisEngine,
        SynthesisRequest,
    )

    if os.environ.get("BENCH_TRACE_ARM") == "1":
        # run_trace's armed phase: the replica records its own spans so
        # the router can assemble the cross-process trace
        from speakingstyle_tpu.obs.trace import (
            configure_span_ring,
            set_tracing_enabled,
        )
        configure_span_ring(8192, keep_traces=512)
        set_tracing_enabled(True)

    cfg = _cluster_proxy_config(device_ms)
    serve = cfg.serve
    _mark(f"[{rid}] building model parts")
    n_position = max(serve.mel_buckets[-1], serve.src_buckets[-1],
                     cfg.model.max_seq_len) + 1
    model = build_model(cfg, n_position=n_position)
    variables = init_variables(model, cfg, jax.random.PRNGKey(0))
    gen = Generator(
        upsample_rates=(2, 2), upsample_kernel_sizes=(4, 4),
        upsample_initial_channel=16, resblock_kernel_sizes=(3,),
        resblock_dilation_sizes=((1,),),
    )
    n_mels = cfg.preprocess.preprocessing.mel.n_mel_channels
    gparams = gen.init(
        jax.random.PRNGKey(0), np.zeros((1, 8, n_mels), np.float32)
    )["params"]
    registry = MetricsRegistry()
    engine = ProxyDeviceEngine(
        SynthesisEngine(cfg, variables, vocoder=(gen, gparams),
                        model=model, registry=registry),
        device_ms,
    )
    _mark(f"[{rid}] precompiling lattice")
    engine.precompile()
    rng = np.random.default_rng(0)
    max_len = min(serve.src_buckets[-1],
                  serve.mel_buckets[-1] // serve.frames_per_phoneme)
    ref = rng.standard_normal(
        (serve.style.ref_buckets[-1], n_mels)).astype(np.float32)
    for b in engine.lattice.batch_buckets:
        engine.run([
            SynthesisRequest(
                id=f"warm{b}_{j}",
                sequence=rng.integers(1, 300, max_len).astype(np.int32),
                ref_mel=ref, priority="batch",
            )
            for j in range(b)
        ])
    _mark(f"[{rid}] warm; registering with {router_addr}")
    server = ReplicaServer(
        engine, rid, router_addr, serve.cluster,
        registry=registry, pid=os.getpid(),
    )
    server.start()
    server.wait_closed()


def run_cluster(duration: float = 3.0, clients: int = 16,
                device_ms: float = 20.0):
    """Cluster storm: three real replica PROCESSES behind the
    ClusterRouter, a chaos process kill and a router<->replica partition
    fired mid-storm, and an exact closed-loop loss count.

    Four phases over one cluster: steady (per-replica compile counts
    from each replica's own /healthz must not move), a kill storm
    (``replica_proc_kill`` SIGKILLs a replica under load; its lease
    expires, in-flight work requeues, the supervisor respawns a
    process), a partition storm (``net_partition`` deterministically
    drops router<->replica packets; heal re-admits the surviving
    process through the breaker's half-open), and a postfault steady
    phase. Every request is awaited, so lost is exact — the invariant
    is ZERO. Lease-expiry-to-requeue latency is recorded from
    ``serve_lease_requeue_seconds`` (p50/p999). CPU-proxy replicas
    (``tiny-cpu-proxydev``): the numbers measure the control plane,
    never device throughput.
    """
    from speakingstyle_tpu.faults import FaultPlan
    from speakingstyle_tpu.obs import MetricsRegistry
    from speakingstyle_tpu.serving.batcher import Overloaded
    from speakingstyle_tpu.serving.cluster import ClusterRouter
    from speakingstyle_tpu.serving.engine import SynthesisRequest
    from speakingstyle_tpu.serving.fleet import FAILED, READY

    import numpy as np

    label = "tiny-cpu-proxydev"
    cfg = _cluster_proxy_config(device_ms)
    serve = cfg.serve
    here = os.path.dirname(os.path.abspath(__file__))
    rng = np.random.default_rng(0)
    max_len = min(serve.src_buckets[-1],
                  serve.mel_buckets[-1] // serve.frames_per_phoneme)
    n_mels = cfg.preprocess.preprocessing.mel.n_mel_channels
    max_ref = serve.style.ref_buckets[-1]
    hot_refs = [
        rng.standard_normal(
            (int(rng.integers(8, max_ref + 1)), n_mels)
        ).astype(np.float32)
        for _ in range(8)
    ]

    def make_request(i: int, priority: str) -> SynthesisRequest:
        L = int(rng.integers(max(4, max_len // 2), max_len + 1))
        return SynthesisRequest(
            id=f"cluster{i}",
            sequence=rng.integers(1, 300, L).astype(np.int32),
            ref_mel=hot_refs[i % len(hot_refs)],
            priority=priority,
        )

    logs = []

    def spawn(rid, router_addr, extra):
        # children are pinned to CPU regardless of the parent's backend:
        # this drill measures the control plane over a CPU proxy, and
        # three children grabbing one accelerator would fight over it
        log = open(os.path.join(here, f".bench_cluster_{rid}.log"), "w")
        logs.append(log)
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--cluster-replica-inner", "--rid", rid,
             "--router", router_addr, "--device-ms", str(device_ms)],
            stdout=log, stderr=log, cwd=here,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )

    registry = MetricsRegistry()
    plan = FaultPlan()
    _mark("spawning 3 cluster replica processes")
    router = ClusterRouter(spawn, cfg, replicas=3, registry=registry,
                           fault_plan=plan)
    point = {
        "metric": "serve_cluster", "replicas": 3, "clients": clients,
        "proxy_device_ms": device_ms, "model": label,
    }
    try:
        if not router.wait_ready(timeout=600, n=3):
            point["error"] = "replica processes never became ready"
            print(json.dumps(point))
            return point

        def compile_counts():
            """{replica_id: its own /healthz compile counter} for every
            attached remote engine (-1/unreachable rows are dropped)."""
            out = {}
            for rep in router._replicas:
                eng = rep.engine
                rid = getattr(eng, "replica_id", "")
                if rid:
                    c = eng.compile_count
                    if c >= 0:
                        out[rid] = c
            return out

        def load_phase(phase_s: float, seed: int):
            stop_at = time.perf_counter() + phase_s
            per = [dict(ok=0, shed=0, lost=0, errors=[])
                   for _ in range(clients)]

            def client(cid: int):
                c, i = per[cid], 0
                while time.perf_counter() < stop_at:
                    prio = "interactive" if (cid + i) % 2 == 0 else "batch"
                    req = make_request(seed + cid * 1_000_000 + i, prio)
                    try:
                        router.submit(req).result(timeout=120)
                        c["ok"] += 1
                    except Overloaded:
                        c["shed"] += 1
                        time.sleep(0.002)
                    except Exception as e:
                        c["lost"] += 1
                        c["errors"].append(type(e).__name__)
                    i += 1

            threads = [threading.Thread(target=client, args=(c,),
                                        daemon=True)
                       for c in range(clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            out = {k: sum(c[k] for c in per)
                   for k in ("ok", "shed", "lost")}
            out["errors"] = sorted({e for c in per for e in c["errors"]})
            out["qps"] = out["ok"] / dt
            return out

        def drill(kind: str, seed: int):
            """Arm ``kind`` on the next dispatch (quiesced, so the
            counter cannot be raced past), run one storm phase, then
            wait the fleet back to 3 READY.  Returns (phase, recovery
            ms) — for a partition the heal happens after the storm, so
            the recovery window includes the half-open re-admission."""
            plan.arm(kind, router.dispatch_total + 1)
            timeline = {}
            stop_mon = threading.Event()

            def monitor():
                while not stop_mon.is_set():
                    states = list(router.states().values())
                    now = time.perf_counter()
                    if FAILED in states and "t_failed" not in timeline:
                        timeline["t_failed"] = now
                    if ("t_failed" in timeline
                            and "t_recovered" not in timeline
                            and sum(s == READY for s in states) >= 3):
                        timeline["t_recovered"] = now
                        return
                    time.sleep(0.002)

            mon = threading.Thread(target=monitor, daemon=True)
            mon.start()
            phase = load_phase(duration, seed)
            if kind == "net_partition":
                # the storm ran against the partitioned control plane;
                # now heal and let half-open adopt the process back
                deadline = time.monotonic() + 120
                while time.monotonic() < deadline \
                        and not router._partitioned:
                    time.sleep(0.05)
                for rid in sorted(router._partitioned):
                    router.heal(rid)
            deadline = time.monotonic() + 600
            while time.monotonic() < deadline \
                    and "t_recovered" not in timeline:
                time.sleep(0.05)
            stop_mon.set()
            mon.join(timeout=5)
            recovery_ms = (
                round(1e3 * (timeline["t_recovered"]
                             - timeline["t_failed"]), 1)
                if "t_recovered" in timeline and "t_failed" in timeline
                else None
            )
            return phase, recovery_ms

        _mark("cluster phase A: steady load")
        pre_compiles = compile_counts()
        steady = load_phase(duration, 0)
        steady_deltas = {
            rid: c - pre_compiles[rid]
            for rid, c in compile_counts().items() if rid in pre_compiles
        }

        _mark("cluster phase B: replica process kill under load")
        kill, kill_recovery_ms = drill("replica_proc_kill", 100_000_000)

        _mark("cluster phase C: router<->replica partition under load")
        part, part_recovery_ms = drill("net_partition", 200_000_000)

        _mark("cluster phase D: postfault steady load")
        post_pre = compile_counts()
        postfault = load_phase(duration, 300_000_000)
        post_deltas = {
            rid: c - post_pre[rid]
            for rid, c in compile_counts().items() if rid in post_pre
        }

        requeue = registry.histogram("serve_lease_requeue_seconds")

        def pct_ms(hist, q):
            p = hist.percentile(q)
            return round(1e3 * p, 1) if p is not None else None

        lost = (steady["lost"] + kill["lost"] + part["lost"]
                + postfault["lost"])
        hedge_fired = sum(
            registry.value("serve_hedge_fired_total", {"class": k})
            for k in ("interactive", "batch")
        )
        hedge_won = sum(
            registry.value("serve_hedge_won_total", {"class": k})
            for k in ("interactive", "batch")
        )
        point.update({
            "steady_qps": round(steady["qps"], 2),
            "kill_qps": round(kill["qps"], 2),
            "partition_qps": round(part["qps"], 2),
            "postfault_qps": round(postfault["qps"], 2),
            "qps_recovery_ratio": (
                round(postfault["qps"] / steady["qps"], 3)
                if steady["qps"] else None
            ),
            "kill_recovery_ms": kill_recovery_ms,
            "partition_recovery_ms": part_recovery_ms,
            "lost_requests": lost,
            "shed": (steady["shed"] + kill["shed"] + part["shed"]
                     + postfault["shed"]),
            "errors": sorted(set(
                steady["errors"] + kill["errors"] + part["errors"]
                + postfault["errors"]
            )),
            "lease_expired": int(
                registry.value("serve_lease_expired_total")),
            "lease_requeue_p50_ms": pct_ms(requeue, 0.50),
            "lease_requeue_p999_ms": pct_ms(requeue, 0.999),
            "requeued": int(registry.value("serve_requeued_total")),
            "hedge_fired": int(hedge_fired),
            "hedge_won": int(hedge_won),
            # per-replica compile deltas across BOTH steady phases: the
            # acceptance bar is zero on every surviving replica
            "steady_compiles_per_replica": steady_deltas,
            "postfault_compiles_per_replica": post_deltas,
            "steady_compiles": int(
                sum(steady_deltas.values()) + sum(post_deltas.values())
            ),
            **_lock_witness_stats(),
        })
        print(json.dumps(point))
        return point
    finally:
        router.close()
        for log in logs:
            try:
                log.close()
            except OSError:
                pass


def run_trace(duration: float = 3.0, clients: int = 16,
              device_ms: float = 20.0):
    """Tracing drill: the cluster storm run twice — spans disarmed,
    then armed fleet-wide — for an honest overhead ablation plus a
    per-stage critical-path latency breakdown.

    ONE 2-replica process cluster behind the ClusterRouter (same
    CPU-proxy engine as run_cluster) serves a closed-loop storm in
    which every client ALTERNATES traced and untraced requests — a
    paired A/B, because separate clusters (baseline spread from
    process placement) and alternating whole sub-phases (batching
    regime drift) were both tried first and their ±10% p50 noise
    swamped the sub-millisecond signal. Both arms sample the identical
    queue, so the per-arm p50 difference is the marginal cost one
    traced request pays. A traced request is the full plane: the
    ``serve_request`` root span exactly as the HTTP front door creates
    it, the context on the cluster wire (X-Trace-* headers), armed
    replicas recording their side, tail-sample pinning. An untraced
    request carries no context at all, so the delta prices the whole
    feature, propagation included. From the recorded spans the router
    ring + ``fetch_remote_spans`` are assembled per trace and the
    critical path bucketed by stage (serve_queue / remote_dispatch /
    replica_dispatch / ...), p50/p999 each. The overhead on TTFA p50
    and the lost-request count carry hard gates in run_compare:
    tracing that costs >2% or drops work does not ship. CPU-proxy
    replicas: the percentiles measure the control plane + span
    plumbing, never device throughput.
    """
    import collections

    from speakingstyle_tpu.faults import FaultPlan
    from speakingstyle_tpu.obs import MetricsRegistry
    from speakingstyle_tpu.obs import trace as obstrace
    from speakingstyle_tpu.obs.trace import Span, assemble_trace
    from speakingstyle_tpu.serving.batcher import Overloaded
    from speakingstyle_tpu.serving.cluster import ClusterRouter
    from speakingstyle_tpu.serving.engine import SynthesisRequest

    import numpy as np

    label = "tiny-cpu-proxydev"
    cfg = _cluster_proxy_config(device_ms)
    serve = cfg.serve
    here = os.path.dirname(os.path.abspath(__file__))
    rng = np.random.default_rng(0)
    max_len = min(serve.src_buckets[-1],
                  serve.mel_buckets[-1] // serve.frames_per_phoneme)
    n_mels = cfg.preprocess.preprocessing.mel.n_mel_channels
    max_ref = serve.style.ref_buckets[-1]
    hot_refs = [
        rng.standard_normal(
            (int(rng.integers(8, max_ref + 1)), n_mels)
        ).astype(np.float32)
        for _ in range(8)
    ]

    def make_request(i: int, priority: str) -> SynthesisRequest:
        L = int(rng.integers(max(4, max_len // 2), max_len + 1))
        return SynthesisRequest(
            id=f"trace{i}",
            sequence=rng.integers(1, 300, L).astype(np.int32),
            ref_mel=hot_refs[i % len(hot_refs)],
            priority=priority,
        )

    def compile_counts(router):
        out = {}
        for rep in router._replicas:
            eng = rep.engine
            rid = getattr(eng, "replica_id", "")
            if rid:
                c = eng.compile_count
                if c >= 0:
                    out[rid] = c
        return out

    def run_phase(router, phase_s: float, seed: int):
        stop_at = time.perf_counter() + phase_s
        per = [dict(ok=0, shed=0, lost=0, errors=[])
               for _ in range(clients)]
        # per-client (untraced, traced) latency pair — the paired A/B
        lats = [([], []) for _ in range(clients)]

        diffs = [[] for _ in range(clients)]

        def client(cid: int):
            c, i = per[cid], 0
            prev = None  # (index, traced, latency) of last success
            while time.perf_counter() < stop_at:
                # requests 2j and 2j+1 form a pair: same class,
                # adjacent in time, one traced one not (which goes
                # first flips with client parity, cancelling order
                # bias) — the paired diff is the ablation signal
                prio = ("interactive"
                        if ((i // 2) + cid) % 2 == 0 else "batch")
                traced = (cid + i) % 2 == 0
                req = make_request(seed + cid * 1_000_000 + i, prio)
                t0 = time.perf_counter()
                try:
                    if traced:
                        # the root span every served request gets from
                        # the HTTP front door; trace_id == req_id, so
                        # the dumps answer /debug/trace/<req_id>
                        with Span("serve_request", trace_id=req.id,
                                  req_id=req.id, klass=prio) as sp:
                            req.trace = sp.ctx
                            router.submit(req).result(timeout=120)
                    else:
                        router.submit(req).result(timeout=120)
                    c["ok"] += 1
                    lat = time.perf_counter() - t0
                    lats[cid][int(traced)].append(lat)
                    if i % 2 == 1 and prev is not None \
                            and prev[0] == i - 1:
                        d = (lat - prev[2]) if traced else (prev[2] - lat)
                        diffs[cid].append(d)  # traced minus untraced
                    prev = (i, traced, lat)
                except Overloaded:
                    c["shed"] += 1
                    prev = None
                    time.sleep(0.002)
                except Exception as e:
                    c["lost"] += 1
                    c["errors"].append(type(e).__name__)
                    prev = None
                i += 1

        threads = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        out = {k: sum(c[k] for c in per) for k in ("ok", "shed", "lost")}
        out["errors"] = sorted({e for c in per for e in c["errors"]})
        out["qps"] = out["ok"] / dt
        out["lat_off"] = [v for g in lats for v in g[0]]
        out["lat_on"] = [v for g in lats for v in g[1]]
        out["diffs"] = [v for g in diffs for v in g]
        return out

    def pctl_ms(vals, q):
        if not vals:
            return None
        return round(1e3 * float(np.percentile(vals, q)), 3)

    logs = []

    def spawn(rid, router_addr, extra):
        log = open(os.path.join(here, f".bench_trace_{rid}.log"), "w")
        logs.append(log)
        # replicas spawn armed; they record spans only for requests
        # whose wire envelope carries a trace context, which is what
        # the off/on sub-phases toggle
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--cluster-replica-inner", "--rid", rid,
             "--router", router_addr, "--device-ms", str(device_ms)],
            stdout=log, stderr=log, cwd=here,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "BENCH_TRACE_ARM": "1"},
        )

    def blank():
        return dict(ok=0, shed=0, lost=0, errors=[], lat_off=[],
                    lat_on=[], diffs=[], qps_sum=0.0, phases=0)

    def merge(acc, res):
        for k in ("ok", "shed", "lost"):
            acc[k] += res[k]
        acc["errors"] = sorted(set(acc["errors"]) | set(res["errors"]))
        acc["lat_off"].extend(res["lat_off"])
        acc["lat_on"].extend(res["lat_on"])
        acc["diffs"].extend(res["diffs"])
        acc["qps_sum"] += res["qps"]
        acc["phases"] += 1

    def compile_delta(router, pre):
        return sum(c - pre[rid]
                   for rid, c in compile_counts(router).items()
                   if rid in pre)

    point = {
        "metric": "serve_trace", "replicas": 2, "clients": clients,
        "proxy_device_ms": device_ms, "model": label,
        "unit": "ms closed-loop request latency (TTFA proxy on cpu)",
    }
    # one cluster, request-level pairing: machine drift and batching
    # regimes hit both arms alike and cancel out of the ablation
    prev_enabled = obstrace.tracing_enabled()
    obstrace.configure_span_ring(16384, keep_traces=512)
    obstrace.set_tracing_enabled(True)
    res = blank()
    _mark("spawning 2 armed replica processes")
    router = ClusterRouter(spawn, cfg, replicas=2,
                           registry=MetricsRegistry(),
                           fault_plan=FaultPlan())
    try:
        if not router.wait_ready(timeout=600, n=2):
            point["error"] = "replica processes never became ready"
            print(json.dumps(point))
            return point
        # warm the mixed stream so span code is hot for the A/B
        _mark("trace warmup")
        run_phase(router, min(1.0, duration), 777)
        pre = compile_counts(router)
        _mark("trace storm: paired traced/untraced stream")
        for k in range(2):
            merge(res, run_phase(router, duration,
                                 500_000_000 + k * 10_000_000))
        res["compiles"] = compile_delta(router, pre)
        # cross-process span harvest: the local ring (+ tail-kept
        # traces) joined with every replica's dump
        ring = obstrace.get_span_ring()
        span_map = {}
        for s in ring.spans():
            sid = s.get("span_id")
            if sid:
                span_map.setdefault(sid, s)
        for tid in ring.kept_trace_ids():
            for s in ring.spans(tid):
                sid = s.get("span_id")
                if sid:
                    span_map.setdefault(sid, s)
        for s in router.fetch_remote_spans():
            sid = s.get("span_id")
            if sid:
                span_map.setdefault(sid, s)
        res["spans"] = list(span_map.values())
        res["ring_evictions"] = ring.stats()["evictions"]
    finally:
        obstrace.set_tracing_enabled(prev_enabled)
        try:
            router.close()
        except OSError:
            pass
        for log in logs:
            try:
                log.close()
            except OSError:
                pass
    if "spans" not in res:
        point.setdefault("error", "trace storm never completed")
        print(json.dumps(point))
        return point
    res["qps"] = res["qps_sum"] / max(1, res["phases"])

    # per-stage critical-path breakdown: assemble each fully-captured
    # trace and bucket its critical-path spans
    by_trace = collections.defaultdict(list)
    for s in res["spans"]:
        tid = s.get("trace_id")
        if tid:
            by_trace[tid].append(s)
    stage = collections.defaultdict(list)
    chains = collections.Counter()
    assembled = cross_process = 0
    for tid, group in sorted(by_trace.items()):
        if assembled >= 512:
            break
        # a ring-evicted root means a partial trace: skip, the
        # breakdown must only average complete critical paths
        if not any(s.get("name") == "serve_request"
                   and not s.get("parent_span_id") for s in group):
            continue
        view = assemble_trace(group, tid)
        cp = view["critical_path"]
        if not cp:
            continue
        assembled += 1
        if any(s.get("name") == "replica_dispatch" for s in group):
            cross_process += 1
        chains[" > ".join(str(s.get("name")) for s in cp)] += 1
        for s in cp:
            if isinstance(s.get("duration_s"), (int, float)):
                stage[str(s.get("name"))].append(float(s["duration_s"]))

    off_p50 = pctl_ms(res["lat_off"], 50)
    on_p50 = pctl_ms(res["lat_on"], 50)
    off_p999 = pctl_ms(res["lat_off"], 99.9)
    on_p999 = pctl_ms(res["lat_on"], 99.9)
    # the gated statistic: median of the paired (traced - untraced)
    # diffs over the untraced p50 — pooled-percentile deltas sit on
    # the batching plateau edges and swing ±5% run to run, the paired
    # median does not
    med_diff_ms = pctl_ms(res["diffs"], 50)
    point.update({
        "untraced_ttfa_p50_ms": off_p50,
        "untraced_ttfa_p999_ms": off_p999,
        "traced_ttfa_p50_ms": on_p50,
        "traced_ttfa_p999_ms": on_p999,
        "qps": round(res["qps"], 2),
        "paired_diff_p50_ms": med_diff_ms,
        "paired_diffs": len(res["diffs"]),
        "overhead_ttfa_p50_pct": (
            round(100.0 * med_diff_ms / off_p50, 2)
            if off_p50 and med_diff_ms is not None else None
        ),
        "overhead_ttfa_p999_pct": (
            round(100.0 * (on_p999 - off_p999) / off_p999, 2)
            if off_p999 else None
        ),
        "lost_requests": res["lost"],
        "shed": res["shed"],
        "errors": res["errors"],
        "steady_compiles": res["compiles"],
        "spans_recorded": len(res["spans"]),
        "ring_evictions": res["ring_evictions"],
        "traces_assembled": assembled,
        "cross_process_traces": cross_process,
        "critical_path_modal": (
            chains.most_common(1)[0][0] if chains else None
        ),
        "stage_p50_ms": {k: pctl_ms(v, 50)
                         for k, v in sorted(stage.items())},
        "stage_p999_ms": {k: pctl_ms(v, 99.9)
                          for k, v in sorted(stage.items())},
        "stage_n": {k: len(v) for k, v in sorted(stage.items())},
        **_lock_witness_stats(),
    })
    print(json.dumps(point))
    return point


def run_quality(duration: float = 3.0, clients: int = 16,
                device_ms: float = 20.0):
    """Quality-plane drill: price the validators, then prove the plane
    actually pages when a tier starts shipping garbage.

    ONE 2-replica CPU-proxy fleet (the run_chaos setup) runs three
    phases:

      A  paired validator-overhead ablation — every closed-loop client
         alternates ``quality_check`` on/off per adjacent same-class
         pair (the run_trace pairing: which arm goes first flips with
         client parity), so the median paired diff prices exactly what
         the choke point (obs/quality.py) adds to a request. Gated at
         <= 2% of the unchecked p50 in run_compare.
      B  healthy phase — tenant load with validators armed, golden
         anchors pinned (serving/probes.py) and probe rounds + SLO
         steps (synthetic clock) interleaved: the invariant is ZERO
         quality pages while the fleet is healthy (false_pages).
      C  degradation drill — quiesced, ``tier_poison`` armed on the
         next dispatch corrupts ONE replica's param tree in place
         (same shapes/dtypes: zero compiles, no errors, just garbage
         audio). Traced tenant load makes the validators fail and pin
         exemplar traces; probe rounds + SLO steps run until BOTH the
         probe drift edge and the quality burn-rate alert fire. The
         drill records how many probe rounds detection took
         (``probes_to_detection``, budget 16) and the exemplar trace
         id the page carries.

    Closed-loop clients await every submission across all phases, so
    ``lost_requests`` is exact; a CompileMonitor spans A-C (the poison
    is a host-side re-put — steady state must stay at zero compiles).
    ``missed_detection``, ``false_pages``, ``lost_requests``, and the
    overhead budget all carry hard gates in run_compare.
    """
    import dataclasses
    import shutil
    import tempfile

    import numpy as np

    import jax

    from speakingstyle_tpu.configs.config import FleetConfig
    from speakingstyle_tpu.faults import FaultPlan
    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.models.hifigan import Generator
    from speakingstyle_tpu.obs import JsonlEventLog, MetricsRegistry
    from speakingstyle_tpu.obs import trace as obstrace
    from speakingstyle_tpu.obs.events import read_events
    from speakingstyle_tpu.obs.slo import SloEngine
    from speakingstyle_tpu.obs.trace import Span
    from speakingstyle_tpu.serving.batcher import Overloaded
    from speakingstyle_tpu.serving.engine import (
        CompileMonitor,
        SynthesisEngine,
        SynthesisRequest,
    )
    from speakingstyle_tpu.serving.fleet import FleetRouter
    from speakingstyle_tpu.serving.probes import GoldenProber
    from speakingstyle_tpu.serving.style import StyleService

    PROBE_BUDGET = 16  # probe rounds the degradation may take to page

    label = "tiny-cpu-proxydev"
    _mark("building quality fleet parts")
    cfg = _fleet_proxy_config()
    # the chaos drill's generous deadlines: this drill measures the
    # quality plane, so scheduling-induced expiry must not show up as
    # loss or pollute the (latency) SLO stream
    cfg = dataclasses.replace(cfg, serve=dataclasses.replace(
        cfg.serve, fleet=FleetConfig(
            stream_window=8, queue_depth=256,
            class_deadline_ms={"interactive": 30_000.0, "batch": 60_000.0},
            rewarm_backoff_s=0.2, rewarm_backoff_max_s=5.0,
        ),
    ))
    serve = cfg.serve
    scfg = serve.slo
    n_position = max(serve.mel_buckets[-1], serve.src_buckets[-1],
                     cfg.model.max_seq_len) + 1
    model = build_model(cfg, n_position=n_position)
    variables = init_variables(model, cfg, jax.random.PRNGKey(0))
    gen = Generator(
        upsample_rates=(2, 2), upsample_kernel_sizes=(4, 4),
        upsample_initial_channel=16, resblock_kernel_sizes=(3,),
        resblock_dilation_sizes=((1,),),
    )
    n_mels = cfg.preprocess.preprocessing.mel.n_mel_channels
    gparams = gen.init(
        jax.random.PRNGKey(0), np.zeros((1, 8, n_mels), np.float32)
    )["params"]
    rng = np.random.default_rng(0)
    max_len = min(serve.src_buckets[-1],
                  serve.mel_buckets[-1] // serve.frames_per_phoneme)
    max_ref = serve.style.ref_buckets[-1]
    hot_refs = [
        rng.standard_normal(
            (int(rng.integers(8, max_ref + 1)), n_mels)
        ).astype(np.float32)
        for _ in range(8)
    ]

    def make_request(i: int, priority: str,
                     check: bool = True) -> SynthesisRequest:
        L = int(rng.integers(max(4, max_len // 2), max_len + 1))
        return SynthesisRequest(
            id=f"quality{i}",
            sequence=rng.integers(1, 300, L).astype(np.int32),
            ref_mel=hot_refs[i % len(hot_refs)],
            priority=priority,
            quality_check=check,
        )

    tmp = tempfile.mkdtemp(prefix="bench_quality_")
    registry = MetricsRegistry()
    plan = FaultPlan()
    events = JsonlEventLog(tmp)
    shared_style = StyleService(cfg, variables, registry=registry)

    def factory(reg):
        return ProxyDeviceEngine(
            SynthesisEngine(
                cfg, variables, vocoder=(gen, gparams), model=model,
                registry=reg, style=shared_style,
            ),
            device_ms,
        )

    def pctl_ms(vals, q):
        if not vals:
            return None
        return round(1e3 * float(np.percentile(vals, q)), 3)

    point = {
        "metric": "serve_quality", "replicas": 2, "clients": clients,
        "probe_budget": PROBE_BUDGET, "proxy_device_ms": device_ms,
        "model": label,
        "unit": "ms closed-loop request latency (TTFA proxy on cpu)",
    }
    tally = dict(ok=0, shed=0, lost=0, errors=set())

    def load_phase(phase_s: float, seed: int, paired: bool = False,
                   traced: bool = False):
        """Closed-loop load; every submission awaited. ``paired`` runs
        the quality_check on/off A/B (run_trace pairing); ``traced``
        gives every request the front door's root span so a failing
        wav has a trace to pin. Merges into ``tally`` and returns the
        phase summary."""
        stop_at = time.perf_counter() + phase_s
        per = [dict(ok=0, shed=0, lost=0, errors=[])
               for _ in range(clients)]
        lats = [([], []) for _ in range(clients)]  # (unchecked, checked)
        diffs = [[] for _ in range(clients)]

        def client(cid: int):
            c, i = per[cid], 0
            prev = None  # (index, checked, latency) of last success
            while time.perf_counter() < stop_at:
                prio = ("interactive"
                        if ((i // 2) + cid) % 2 == 0 else "batch")
                checked = True if not paired else (cid + i) % 2 == 0
                req = make_request(seed + cid * 1_000_000 + i, prio,
                                   check=checked)
                t0 = time.perf_counter()
                try:
                    if traced:
                        with Span("serve_request", trace_id=req.id,
                                  req_id=req.id, klass=prio) as sp:
                            req.trace = sp.ctx
                            router.submit(req).result(timeout=120)
                    else:
                        router.submit(req).result(timeout=120)
                    c["ok"] += 1
                    lat = time.perf_counter() - t0
                    if paired:
                        lats[cid][int(checked)].append(lat)
                        if i % 2 == 1 and prev is not None \
                                and prev[0] == i - 1:
                            d = (lat - prev[2]) if checked \
                                else (prev[2] - lat)
                            diffs[cid].append(d)  # checked - unchecked
                        prev = (i, checked, lat)
                except Overloaded:
                    c["shed"] += 1
                    prev = None
                    time.sleep(0.002)
                except Exception as e:
                    c["lost"] += 1
                    c["errors"].append(type(e).__name__)
                    prev = None
                i += 1

        threads = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        out = {k: sum(c[k] for c in per) for k in ("ok", "shed", "lost")}
        out["qps"] = out["ok"] / dt
        out["lat_off"] = [v for g in lats for v in g[0]]
        out["lat_on"] = [v for g in lats for v in g[1]]
        out["diffs"] = [v for g in diffs for v in g]
        for k in ("ok", "shed", "lost"):
            tally[k] += out[k]
        tally["errors"] |= {e for c in per for e in c["errors"]}
        return out

    def quality_pages():
        """Cumulative quality-page count: probe drift edges (any tier
        label, 'style' included) + quality burn-rate alerts per class."""
        n = 0.0
        for t in ("default", "style"):
            n += registry.value("serve_probe_drift_alerts_total",
                                {"tier": t})
        for klass in scfg.quality_objectives:
            n += registry.value("serve_slo_quality_alerts_total",
                                {"class": klass})
        return int(n)

    _mark("warming 2 quality replicas")
    # ring BEFORE the router: configure_span_ring REPLACES the process
    # ring, and the fleet binds its gates to whatever ring exists at
    # construction — the SLO engine must read the same one to carry
    # the pinned exemplar trace id on its page
    prev_enabled = obstrace.tracing_enabled()
    obstrace.configure_span_ring(16384, keep_traces=256)
    obstrace.set_tracing_enabled(True)
    router = FleetRouter(factory, cfg, replicas=2, registry=registry,
                         style=shared_style, fault_plan=plan,
                         events=events)
    prober = slo = None
    try:
        if not router.wait_ready(timeout=600, n=2):
            point["error"] = "replicas never became ready"
            print(json.dumps(point))
            return point
        for engine in router.engines():
            for b in engine.lattice.batch_buckets:
                engine.run([make_request(10_000_000 + b * 100 + j, "batch")
                            for j in range(b)])
        _mark("quality warmup load")
        load_phase(min(1.0, duration), 777, paired=True)
        _mark("pinning golden anchors from the healthy fleet")
        prober = GoldenProber(
            router, cfg, style=shared_style, registry=registry,
            events=events, anchor_dir=os.path.join(tmp, "anchors"),
            start=False,
        )
        prober.pin()
        prober.probe_once()  # warm the probe path before monitoring
        # synthetic SLO clock (the slo-engine test idiom): one tick per
        # activity burst, fast-window spaced, so both windows see the
        # drill's counters without waiting wall-clock minutes
        slo = SloEngine(registry, scfg, events=events,
                        trace_ring=obstrace.get_span_ring(), start=False)
        now = 0.0
        slo.step(now=now)

        with CompileMonitor() as qmon:
            _mark("quality phase A: paired validator-overhead ablation")
            overhead = load_phase(duration, 0, paired=True)
            _mark("quality phase B: healthy probes under load")
            healthy = load_phase(duration, 100_000_000, traced=True)
            for _ in range(2):
                prober.probe_once()
                now += scfg.fast_window_s / 2
                slo.step(now=now)
            false_pages = quality_pages()

            # quiesced (every phase-B submission resolved): the armed
            # counter deterministically poisons the NEXT dispatch
            plan.arm("tier_poison", router.dispatch_total + 1)
            _mark("quality phase C: tier_poison degradation drill")
            degraded = load_phase(duration, 200_000_000, traced=True)
            probes_to_detection = None
            for rounds in range(1, PROBE_BUDGET + 1):
                summary = prober.probe_once()
                now += scfg.fast_window_s / 2
                slo.step(now=now)
                if any(prober.alerting().values()) \
                        and any(slo.quality_alerting().values()):
                    probes_to_detection = rounds
                    break
        steady_compiles = qmon.count
    finally:
        obstrace.set_tracing_enabled(prev_enabled)
        router.close()
        if slo is not None:
            slo.close()
        if prober is not None:
            prober.close()

    detected = probes_to_detection is not None
    paged_trace_id = None
    validator_fails = 0
    for rec in read_events(tmp):
        if rec.get("event") == "quality_fail":
            validator_fails += 1
        elif rec.get("event") == "slo_quality_alert" \
                and rec.get("trace_id"):
            paged_trace_id = rec["trace_id"]
    shutil.rmtree(tmp, ignore_errors=True)

    off_p50 = pctl_ms(overhead["lat_off"], 50)
    med_diff_ms = pctl_ms(overhead["diffs"], 50)
    worst_drift = max(
        [0.0] + [s["mel_drift"] for s in summary["tiers"].values()]
    ) if detected else None
    point.update({
        "unchecked_ttfa_p50_ms": off_p50,
        "checked_ttfa_p50_ms": pctl_ms(overhead["lat_on"], 50),
        "paired_diff_p50_ms": med_diff_ms,
        "paired_diffs": len(overhead["diffs"]),
        "overhead_ttfa_p50_pct": (
            round(100.0 * med_diff_ms / off_p50, 2)
            if off_p50 and med_diff_ms is not None else None
        ),
        "qps": round((healthy["qps"] + degraded["qps"]) / 2, 2),
        "false_pages": false_pages,
        "detected": detected,
        "missed_detection": 0 if detected else 1,
        "probes_to_detection": probes_to_detection,
        "detection_mel_drift": (
            worst_drift if worst_drift is None
            or np.isfinite(worst_drift) else "inf"
        ),
        "paged_trace_id": paged_trace_id,
        "validator_fails": validator_fails,
        "lost_requests": tally["lost"],
        "shed": tally["shed"],
        "errors": sorted(tally["errors"]),
        "steady_compiles": steady_compiles,
        **_lock_witness_stats(),
    })
    print(json.dumps(point))
    return point


def run_rollout(duration: float = 3.0, clients: int = 16,
                device_ms: float = 20.0):
    """Live-upgrade drill: a canary-gated rolling rollout under
    closed-loop load, plus a poisoned variant that must abort.

    The run_chaos CPU-proxy fleet (2 replicas) serves checkpoint step 1
    while step 2 — genuinely different weights, saved through the real
    manifest-writing CheckpointManager — rolls out mid-load:

      A  steady load on v1 under a CompileMonitor (must be 0 compiles);
      B  ``RolloutManager.rollout(2)`` concurrent with the same load:
         verify (strict manifest restore) -> canary surge replica ->
         golden-set parity gate -> drain-replace both old replicas;
      C  steady load on v2 under a CompileMonitor (must be 0 again —
         every replacement warmed through the AOT precompile);
      D  quiesced poison drill: ``checkpoint_corrupt`` armed on the
         verify manager's fault plan, rollout(1) must abort in the
         verify phase with the fleet untouched and v2 still serving.

    Closed-loop clients await every submission, so
    ``rollout_lost_requests`` is exact and carries a hard zero gate in
    run_compare — a model upgrade that drops requests is an outage, not
    a regression percentage.
    """
    import dataclasses
    import tempfile

    import numpy as np

    import jax

    from speakingstyle_tpu.configs.config import FleetConfig
    from speakingstyle_tpu.faults import FaultPlan
    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.models.hifigan import Generator
    from speakingstyle_tpu.obs import MetricsRegistry
    from speakingstyle_tpu.serving.batcher import Overloaded
    from speakingstyle_tpu.serving.engine import (
        CompileMonitor,
        SynthesisEngine,
        SynthesisRequest,
    )
    from speakingstyle_tpu.serving.fleet import READY, FleetRouter
    from speakingstyle_tpu.serving.lifecycle import RolloutManager
    from speakingstyle_tpu.serving.style import StyleService
    from speakingstyle_tpu.training.checkpoint import CheckpointManager

    on_tpu = _is_tpu()
    if on_tpu:
        device_ms = 0.0
    label = "tiny-cpu-proxydev" if device_ms > 0 else (
        "flagship" if on_tpu else "tiny-cpu"
    )
    _mark("building rollout fleet parts")
    cfg = _fleet_proxy_config()
    cfg = dataclasses.replace(cfg, serve=dataclasses.replace(
        cfg.serve, fleet=FleetConfig(
            stream_window=8, queue_depth=256,
            class_deadline_ms={"interactive": 30_000.0, "batch": 60_000.0},
            rewarm_backoff_s=0.2, rewarm_backoff_max_s=5.0,
        ),
    ))
    serve = cfg.serve
    n_position = max(serve.mel_buckets[-1], serve.src_buckets[-1],
                     cfg.model.max_seq_len) + 1
    model = build_model(cfg, n_position=n_position)
    variables = init_variables(model, cfg, jax.random.PRNGKey(0))
    gen = Generator(
        upsample_rates=(2, 2), upsample_kernel_sizes=(4, 4),
        upsample_initial_channel=16, resblock_kernel_sizes=(3,),
        resblock_dilation_sizes=((1,),),
    )
    n_mels = cfg.preprocess.preprocessing.mel.n_mel_channels
    gparams = gen.init(
        jax.random.PRNGKey(0), np.zeros((1, 8, n_mels), np.float32)
    )["params"]
    rng = np.random.default_rng(0)
    max_len = min(serve.src_buckets[-1],
                  serve.mel_buckets[-1] // serve.frames_per_phoneme)
    hot_refs = [
        rng.standard_normal(
            (int(rng.integers(8, serve.style.ref_buckets[-1] + 1)), n_mels)
        ).astype(np.float32)
        for _ in range(8)
    ]

    def make_request(i: int, priority: str) -> SynthesisRequest:
        L = int(rng.integers(max(4, max_len // 2), max_len + 1))
        return SynthesisRequest(
            id=f"roll{i}",
            sequence=rng.integers(1, 300, L).astype(np.int32),
            ref_mel=hot_refs[i % len(hot_refs)],
            priority=priority,
        )

    registry = MetricsRegistry()
    ckpt_plan = FaultPlan()  # the verify gate's plan (poison drill)
    shared_style = StyleService(cfg, variables, registry=registry)

    # two REAL checkpoints through the manifest-writing manager: step 1
    # is the live version, step 2 the candidate (genuinely different
    # weights, close enough to pass the parity gate)
    _mark("writing rollout checkpoints (step 1 + 2)")
    ckpt_dir = tempfile.mkdtemp(prefix="bench_rollout_ckpt_")
    writer = CheckpointManager(ckpt_dir)
    writer.save(1, variables, block=True)
    v2_variables = jax.tree_util.tree_map(
        lambda x: x * (1.0 + 1e-3) if np.issubdtype(
            np.asarray(x).dtype, np.floating) else x,
        variables,
    )
    writer.save(2, v2_variables, block=True)
    writer.close()

    def verify_and_build(step: int):
        """The rollout's trust boundary: strict manifest-verified
        restore (CheckpointCorruptError aborts the rollout), then an
        engine factory closed over the restored weights."""
        ckpt = CheckpointManager(ckpt_dir, fault_plan=ckpt_plan,
                                 registry=registry)
        try:
            restored = ckpt.restore(variables, step=step, strict=True)
            info = {"step": ckpt.last_restored_step,
                    "weights_digest": ckpt.last_weights_digest}
        finally:
            ckpt.close()
        version = f"{step}:{(info['weights_digest'] or 'unverified')[:12]}"

        def factory(reg):
            return ProxyDeviceEngine(
                SynthesisEngine(
                    cfg, restored, vocoder=(gen, gparams), model=model,
                    registry=reg, style=shared_style,
                ),
                device_ms,
            )

        return factory, version, info

    _mark("warming 2 rollout replicas on v1")
    factory1, version1, info1 = verify_and_build(1)
    router = FleetRouter(factory1, cfg, replicas=2, registry=registry,
                         style=shared_style)
    router.set_model_version(version1, info1["step"],
                             info1["weights_digest"])
    if not router.wait_ready(timeout=600, n=2):
        print(json.dumps({
            "metric": "serve_rollout", "replicas": 2,
            "error": "replicas never became ready", "model": label,
        }))
        router.close()
        return None
    mgr = RolloutManager(router, verify_and_build, registry=registry)

    def transfer_warmup(base: int):
        for engine in router.engines():
            for b in engine.lattice.batch_buckets:
                engine.run([make_request(base + b * 100 + j, "batch")
                            for j in range(b)])

    transfer_warmup(10_000_000)

    def load_phase(phase_s: float, seed: int):
        """Closed-loop load; every submitted request is awaited."""
        stop_at = time.perf_counter() + phase_s
        per = [dict(ok=0, shed=0, lost=0, errors=[])
               for _ in range(clients)]

        def client(cid: int):
            c, i = per[cid], 0
            while time.perf_counter() < stop_at:
                prio = "interactive" if (cid + i) % 2 == 0 else "batch"
                req = make_request(seed + cid * 1_000_000 + i, prio)
                try:
                    router.submit(req).result(timeout=120)
                    c["ok"] += 1
                except Overloaded:
                    c["shed"] += 1
                    time.sleep(0.002)
                except Exception as e:  # structured failure OR stuck: lost
                    c["lost"] += 1
                    c["errors"].append(type(e).__name__)
                i += 1

        threads = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        out = {k: sum(c[k] for c in per) for k in ("ok", "shed", "lost")}
        out["errors"] = sorted({e for c in per for e in c["errors"]})
        out["qps"] = out["ok"] / dt
        return out

    _mark("rollout phase A: steady load on v1")
    with CompileMonitor() as pre_mon:
        pre = load_phase(duration, 0)

    _mark("rollout phase B: live upgrade under load")
    roll_result = {}

    def do_roll():
        try:
            roll_result.update(mgr.rollout(2))
        except Exception as e:  # surfaced in the JSON point, never lost
            roll_result.update(status="error",
                               reason=f"{type(e).__name__}: {e}")

    roll_thread = threading.Thread(target=do_roll, daemon=True)
    roll_thread.start()
    during = load_phase(duration, 100_000_000)
    roll_thread.join(timeout=600)
    committed = roll_result.get("status") == "committed"
    post = None
    post_compiles = None
    if committed:
        transfer_warmup(20_000_000)  # the new engines' first host paths
        _mark("rollout phase C: steady load on v2")
        with CompileMonitor() as post_mon:
            post = load_phase(duration, 200_000_000)
        post_compiles = post_mon.count

    # -- poisoned variant: the verify gate must refuse a corrupt
    # checkpoint with the fleet untouched and the NEW version serving
    _mark("rollout phase D: poisoned verify (checkpoint_corrupt armed)")
    version_before_poison = router.model_version
    states_before = dict(router.states())
    ckpt_plan.arm("checkpoint_corrupt", 1)  # fresh manager: 1st verify
    try:
        poisoned = mgr.rollout(1)
    except Exception as e:
        poisoned = {"status": "error", "reason": f"{type(e).__name__}: {e}"}
    abort_ok = (
        poisoned.get("status") == "aborted"
        and poisoned.get("phase") == "verify"
        and router.model_version == version_before_poison
        # fleet untouched: identical state map (the rolled-away old
        # replicas legitimately linger as STOPPED entries) with the new
        # version's replicas still READY
        and dict(router.states()) == states_before
        and any(s == READY for s in router.states().values())
    )
    router.close()

    lost = pre["lost"] + during["lost"] + (post["lost"] if post else 0)
    steady_compiles = pre_mon.count + (
        post_compiles if post_compiles is not None else 0
    )
    point = {
        "metric": "serve_rollout",
        "replicas": 2,
        "clients": clients,
        "committed": committed,
        "from_version": version1,
        "to_version": router.model_version,
        "rollout_duration_ms": roll_result.get("duration_ms"),
        "rollout_canary_ms": roll_result.get("canary_ms"),
        "rollout_steady_compiles": steady_compiles,
        "rollout_lost_requests": lost,
        "pre_qps": round(pre["qps"], 2),
        "during_qps": round(during["qps"], 2),
        "post_qps": round(post["qps"], 2) if post else None,
        "shed": pre["shed"] + during["shed"] + (
            post["shed"] if post else 0
        ),
        "errors": sorted(set(
            pre["errors"] + during["errors"]
            + (post["errors"] if post else [])
        )),
        "abort_ok": abort_ok,
        "abort_status": poisoned.get("status"),
        "abort_phase": poisoned.get("phase"),
        "abort_reason": poisoned.get("reason"),
        "rollouts_committed": int(registry.value(
            "serve_rollouts_total", {"outcome": "committed"})),
        "rollouts_aborted": int(registry.value(
            "serve_rollouts_total", {"outcome": "aborted"})),
        "proxy_device_ms": device_ms,
        "model": label,
    }
    print(json.dumps(point))
    return point


def run_traffic(duration: float = 4.0, base_qps: float = 12.0,
                device_ms: float = 40.0, chaos: bool = True, seed: int = 0):
    """Capacity-planning storm: a seeded production-shaped workload
    (serving/traffic.py) replayed open-loop against an AUTOSCALED fleet.

    One schedule, four acts on the same clock: a steady phase at the
    base rate (one replica, right-sized), a 10x flash crowd that builds
    queue until the closed-loop autoscaler grows the fleet — with a
    chaos ``replica_raise`` armed mid-flash so a replica dies inside the
    storm — then a recovery window at base rate while cold replicas
    finish joining, and finally a drain where calm shrinks the fleet
    back to the floor. Every submitted request is tracked to a terminal
    state, so the lost count is exact and its invariant is ZERO: flash
    overload must resolve as shed-with-Retry-After or served-late, never
    as silent loss. CompileMonitor spans the steady phase (scale-up
    warm-ups are the sanctioned compile window, as in run_chaos).

    The emitted record is the capacity artifact: QPS/replica at the
    base rate, shed fraction and scale-up reaction through the flash,
    the measured cost of a replica joining mid-storm, and the policy's
    decision tally by reason.
    """
    import dataclasses

    import numpy as np

    import jax

    from speakingstyle_tpu.configs.config import (
        AutoscaleConfig,
        FleetConfig,
        LongformConfig,
    )
    from speakingstyle_tpu.faults import FaultPlan
    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.models.hifigan import Generator
    from speakingstyle_tpu.obs import MetricsRegistry
    from speakingstyle_tpu.serving.autoscale import Autoscaler
    from speakingstyle_tpu.serving.batcher import Overloaded
    from speakingstyle_tpu.serving.engine import (
        CompileMonitor,
        SynthesisEngine,
        SynthesisRequest,
    )
    from speakingstyle_tpu.serving.fleet import FAILED, FleetRouter
    from speakingstyle_tpu.serving.longform import LongformService
    from speakingstyle_tpu.serving.style import StyleService
    from speakingstyle_tpu.serving.traffic import TrafficModel

    on_tpu = _is_tpu()
    if on_tpu:
        device_ms = 0.0
    label = "tiny-cpu-proxydev" if device_ms > 0 else (
        "flagship" if on_tpu else "tiny-cpu"
    )
    _mark("building traffic fleet parts")
    cfg = _fleet_proxy_config()
    # generous deadlines (the storm deliberately builds multi-second
    # backlog; expiry must not masquerade as loss) + an armed autoscaler
    # sized for the drill: floor 1, ceiling 3, ticks and calm windows in
    # bench seconds
    min_replicas, max_replicas = 1, 3
    cfg = dataclasses.replace(cfg, serve=dataclasses.replace(
        cfg.serve,
        fleet=FleetConfig(
            stream_window=8, queue_depth=256,
            class_deadline_ms={"interactive": 60_000.0, "batch": 120_000.0},
            rewarm_backoff_s=0.2, rewarm_backoff_max_s=5.0,
        ),
        autoscale=AutoscaleConfig(
            enabled=True, min_replicas=min_replicas,
            max_replicas=max_replicas, interval_s=0.05,
            up_queue_fraction=0.25, up_occupancy=0.95,
            up_pressure_rate=50.0, down_queue_fraction=0.05,
            down_occupancy=0.5, down_stable_s=1.0, cooldown_up_s=1.0,
            cooldown_down_s=1.0, max_step=2, assumed_warmup_s=5.0,
            warmup_cost_factor=0.5,
        ),
        # chapter chunk groups share one storm-generous budget: a flash
        # backlog must resolve as served-late, never as a chapter lost
        # to its own per-chunk deadline
        longform=LongformConfig(deadline_ms_per_chunk=30_000.0),
    ))
    serve = cfg.serve
    # the storm: steady (1 phase), flash (1 phase at 10x), recovery
    # (2 phases at base while cold capacity lands and backlog drains)
    flash_start, flash_end = duration, 2.0 * duration
    total_s = 4.0 * duration
    model_traffic = TrafficModel(
        seed=seed, base_qps=base_qps, duration_s=total_s,
        diurnal_floor=0.8, flash_windows=[(flash_start, flash_end)],
        flash_multiplier=10.0, n_styles=32, zipf_s=1.2,
    )
    schedule = model_traffic.schedule()

    n_position = max(serve.mel_buckets[-1], serve.src_buckets[-1],
                     cfg.model.max_seq_len) + 1
    model = build_model(cfg, n_position=n_position)
    variables = init_variables(model, cfg, jax.random.PRNGKey(0))
    gen = Generator(
        upsample_rates=(2, 2), upsample_kernel_sizes=(4, 4),
        upsample_initial_channel=16, resblock_kernel_sizes=(3,),
        resblock_dilation_sizes=((1,),),
    )
    n_mels = cfg.preprocess.preprocessing.mel.n_mel_channels
    gparams = gen.init(
        jax.random.PRNGKey(0), np.zeros((1, 8, n_mels), np.float32)
    )["params"]
    rng = np.random.default_rng(seed)
    max_len = min(serve.src_buckets[-1],
                  serve.mel_buckets[-1] // serve.frames_per_phoneme)
    max_ref = serve.style.ref_buckets[-1]
    # one ref per zipf style rank: the hot ranks hammer the embedding
    # cache exactly as a real catalog's head voices do
    style_refs = [
        rng.standard_normal(
            (int(rng.integers(8, max_ref + 1)), n_mels)
        ).astype(np.float32)
        for _ in range(model_traffic.n_styles)
    ]
    sequences = [rng.integers(1, 300, max_len).astype(np.int32)
                 for _ in range(16)]

    def make_request(i: int, ev) -> SynthesisRequest:
        L = min(max_len, max(4, int(round(ev.length_frac * max_len))))
        return SynthesisRequest(
            id=f"traffic{i}",
            sequence=sequences[i % len(sequences)][:L],
            ref_mel=style_refs[ev.style],
            priority=ev.priority,
        )

    # long_form arrivals (length_frac > 1) are CHAPTERS: they cannot ride
    # the interactive lattice, so they go through the long-form service
    # over the same router — each becomes a deadline-sharing chunk group.
    # The synthetic frontend gives every sentence a fixed phoneme count,
    # so a chapter's chunk plan is exact without G2P cost in the replay.
    sent_ph = max(4, max_len // 2)

    class _SyntheticFrontend:
        def sequence(self, sent: str) -> np.ndarray:
            return sequences[0][:sent_ph]

        def resolve_style(self, payload):
            return None, style_refs[int(payload.get("style_rank", 0))], False

        def speaker(self, spec):
            return 0

    def chapter_payload(ev) -> dict:
        n_sent = max(1, int(round(ev.length_frac * max_len / sent_ph)))
        return {
            "text": " ".join(f"s{j}." for j in range(n_sent)),
            "style_rank": ev.style,
        }

    def run_chapter(i: int, ev) -> int:
        plan_lf = longform_svc.admit(f"chapter{i}", chapter_payload(ev))
        samples = 0
        for piece in longform_svc.stream(plan_lf):
            samples += piece.size
        return samples

    registry = MetricsRegistry()
    plan = FaultPlan()
    shared_style = StyleService(cfg, variables, registry=registry)

    def factory(reg):
        return ProxyDeviceEngine(
            SynthesisEngine(
                cfg, variables, vocoder=(gen, gparams), model=model,
                registry=reg, style=shared_style,
            ),
            device_ms,
        )

    _mark("warming 1 traffic replica")
    router = FleetRouter(factory, cfg, replicas=min_replicas,
                         registry=registry, style=shared_style,
                         fault_plan=plan)
    longform_svc = LongformService(
        cfg, _SyntheticFrontend(), router, registry=registry,
    )
    from concurrent.futures import ThreadPoolExecutor

    lf_pool = ThreadPoolExecutor(
        max_workers=4, thread_name_prefix="bench-longform"
    )
    if not router.wait_ready(timeout=600, n=min_replicas):
        print(json.dumps({
            "metric": "serve_traffic", "error": "replica never became ready",
            "model": label,
        }))
        router.close()
        return None
    for engine in router.engines():
        for b in engine.lattice.batch_buckets:
            engine.run([make_request(10_000_000 + b * 100 + j, schedule[0])
                        for j in range(b)])

    def phase_of(t: float) -> str:
        if t < flash_start:
            return "steady"
        if t < flash_end:
            return "flash"
        return "recovery"

    counts = {p: dict(ok=0, shed=0, lost=0, errors=[])
              for p in ("steady", "flash", "recovery")}
    pending = []  # (future, phase)
    timeline = {}
    peak = [min_replicas]
    stop_mon = threading.Event()
    scaler = Autoscaler(router, serve.autoscale)

    def monitor():
        # bounds witness + reaction/fault timestamps, sampled through
        # the whole storm
        while not stop_mon.wait(0.005):
            live = router.live_replica_count()
            peak[0] = max(peak[0], live)
            now = time.perf_counter()
            if scaler.target > min_replicas and "t_first_up" not in timeline:
                timeline["t_first_up"] = now
            states = list(router.states().values())
            if FAILED in states:
                timeline.setdefault("t_failed", now)
            elif "t_failed" in timeline:
                timeline.setdefault("t_recovered", now)

    mon_thread = threading.Thread(target=monitor, daemon=True)
    mon_thread.start()

    _mark(f"replaying {len(schedule)} arrivals over {total_s:.0f}s "
          f"(flash {flash_start:.0f}-{flash_end:.0f}s)")
    steady_mon = CompileMonitor()
    steady_mon.__enter__()
    steady_done = False
    chaos_armed = False
    t0 = time.perf_counter()
    for i, ev in enumerate(schedule):
        delay = t0 + ev.t - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        if not steady_done and ev.t >= flash_start:
            steady_mon.__exit__(None, None, None)
            steady_done = True
            timeline["t_flash_start"] = t0 + flash_start
        if chaos and not chaos_armed \
                and ev.t >= 0.5 * (flash_start + flash_end):
            # mid-flash chaos: the NEXT dispatch raises in a replica —
            # supervision re-warms it while the autoscaler is growing
            plan.arm("replica_raise", router.dispatch_total + 1)
            chaos_armed = True
        p = phase_of(ev.t)
        try:
            if ev.kind == "long_form":
                # a chapter: admission + chunk-group synthesis on a
                # drain worker; its future resolves when the last
                # stitched piece has been consumed
                pending.append((lf_pool.submit(run_chapter, i, ev), p))
            else:
                pending.append((router.submit(make_request(i, ev)), p))
        except Overloaded:
            counts[p]["shed"] += 1
        except Exception as e:
            counts[p]["lost"] += 1
            counts[p]["errors"].append(type(e).__name__)
    if not steady_done:
        steady_mon.__exit__(None, None, None)
    _mark(f"storm submitted; awaiting {len(pending)} admitted requests")
    for fut, p in pending:
        try:
            fut.result(timeout=300)
            counts[p]["ok"] += 1
        except Overloaded:
            # a chapter's chunk submission hit the shed watermark
            # mid-stream: backpressure, not loss
            counts[p]["shed"] += 1
        except Exception as e:
            counts[p]["lost"] += 1
            counts[p]["errors"].append(type(e).__name__)
    lf_pool.shutdown(wait=True)

    # post-storm: calm should shrink the fleet back to the floor; the
    # wait bound covers the calm window (scaled by the measured warm-up
    # cost) plus the down cooldown
    _mark("draining: waiting for scale-down to the floor")
    shrink_deadline = time.monotonic() + 120
    while time.monotonic() < shrink_deadline:
        if router.live_replica_count() <= min_replicas:
            break
        time.sleep(0.1)
    scaled_down = router.live_replica_count() <= min_replicas
    stop_mon.set()
    mon_thread.join(timeout=5)
    scaler.close()
    warmup_p50 = router.warmup_cost_s()
    router.close()

    # reaction = flash start -> first scale-up decision; meaningful only
    # when the first up actually fired inside the storm
    reaction_ms = None
    if "t_first_up" in timeline and "t_flash_start" in timeline \
            and timeline["t_first_up"] >= timeline["t_flash_start"]:
        reaction_ms = round(
            1e3 * (timeline["t_first_up"] - timeline["t_flash_start"]), 1
        )
    fault_recovery_ms = None
    if "t_failed" in timeline and "t_recovered" in timeline:
        fault_recovery_ms = round(
            1e3 * (timeline["t_recovered"] - timeline["t_failed"]), 1
        )
    decisions = {}
    for key, count in registry.snapshot()["counters"].items():
        if key.startswith("serve_autoscale_decisions_total{"):
            reason = key.split('reason="', 1)[1].split('"', 1)[0]
            decisions[reason] = int(count)
    flash_offered = sum(counts["flash"][k] for k in ("ok", "shed", "lost"))
    flash_shed_fraction = (
        round(counts["flash"]["shed"] / flash_offered, 4)
        if flash_offered else None
    )
    lost = sum(counts[p]["lost"] for p in counts)
    point = {
        "metric": "serve_traffic",
        "workload": model_traffic.describe(),
        "offered": len(schedule),
        "phases": {
            p: {k: counts[p][k] for k in ("ok", "shed", "lost")}
            for p in counts
        },
        "errors": sorted({e for p in counts for e in counts[p]["errors"]}),
        "lost_requests": lost,
        "qps_per_replica_steady": round(
            counts["steady"]["ok"] / duration / min_replicas, 2
        ),
        "qps_per_replica_flash": round(
            counts["flash"]["ok"] / duration / peak[0], 2
        ),
        "flash_shed_fraction": flash_shed_fraction,
        "scaleup_reaction_ms": reaction_ms,
        "replicas_peak": peak[0],
        "replicas_max": max_replicas,
        "scaled_down_to_floor": scaled_down,
        "warmup_cost_s": round(warmup_p50, 3) if warmup_p50 else None,
        "steady_compiles": steady_mon.count,
        "chaos_armed": chaos_armed,
        "chaos_recovery_ms": fault_recovery_ms,
        "replica_failures": sum(
            int(registry.value("serve_replica_failures_total",
                               {"replica": str(i)}))
            for i in range(max_replicas + 2)
        ),
        "requeued": int(registry.value("serve_requeued_total")),
        "autoscale_decisions": decisions,
        "longform_chapters": int(registry.value(
            "serve_longform_requests_total", {"tier": "chunked"})),
        "longform_chunks": int(registry.value("serve_longform_chunks_total")),
        "proxy_device_ms": device_ms,
        "model": label,
        **_lock_witness_stats(),
    }
    print(json.dumps(point))
    return point


def run_ab():
    """A/B the performance knobs (README "Performance knobs"): one process
    per variant so each gets a clean backend; prints one JSON line each."""
    variants = [
        # every variant pins its knobs explicitly against the r5 tuned set
        # (TUNED_OVERRIDES); the rows walk one knob away from it at a time
        # plus the historical conv/attention matrix. Measured results for
        # all of these live in PERF.md.
        dict(TUNED_OVERRIDES),
        dict(TUNED_OVERRIDES, dropout_impl="bernoulli"),
        dict(TUNED_OVERRIDES, fused_optimizer=False),
        dict(TUNED_OVERRIDES, conv_impl="pallas"),
        {"conv_impl": "xla", "attention_kernel": "einsum"},
        {"conv_impl": "unfold", "attention_kernel": "einsum"},
        {"conv_impl": "pallas", "attention_kernel": "einsum"},
        {"conv_impl": "xla", "attention_kernel": "einsum",
         "attention_softmax_dtype": "bfloat16"},
    ]
    for ov in variants:
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--inner",
                 "--overrides", json.dumps(ov)],
                capture_output=True,
                text=True,
                timeout=600,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
        except subprocess.TimeoutExpired:
            print(json.dumps({"error": "timeout after 600s", "overrides": ov}))
            continue
        line = next(
            (ln for ln in reversed(proc.stdout.strip().splitlines())
             if ln.startswith("{")),
            None,
        )
        print(line or json.dumps({"error": proc.stderr[-300:], "overrides": ov}))


# ---------------------------------------------------------------------------
# --multichip: DP scaling sweep on the virtual-device CPU proxy
# ---------------------------------------------------------------------------

MULTICHIP_DEVICE_COUNTS = (1, 2, 4, 8)
# weak scaling: fixed per-chip batch, so frames/s/chip should hold roughly
# flat as the mesh grows; the 1-device point is the normalizer. Tiny model
# (test_parallel.py scale) — the sweep measures the mesh machinery (GSPMD
# partitioning + collectives overhead), not kernel throughput, and the CPU
# proxy could not say anything about kernel speed anyway.
MULTICHIP_B_PER_CHIP, MULTICHIP_L, MULTICHIP_T = 4, 32, 64
MULTICHIP_WARMUP, MULTICHIP_STEPS = 3, 10


def _multichip_child(n_devices: int):
    """One sweep point; runs in a child process whose XLA_FLAGS carry
    --xla_force_host_platform_device_count={n}. Tiny FastSpeech2, DP mesh
    over all n virtual devices, fixed per-chip batch, timed jitted steps
    through the production make_train_step. Emits ONE JSON line."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from speakingstyle_tpu.configs.config import (
        Config,
        ModelConfig,
        ReferenceEncoderConfig,
        TransformerConfig,
        VariancePredictorConfig,
    )
    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.parallel.mesh import make_mesh
    from speakingstyle_tpu.training.optim import make_optimizer
    from speakingstyle_tpu.training.state import TrainState
    from speakingstyle_tpu.training.trainer import make_train_step

    if len(jax.devices()) < n_devices:
        print(json.dumps({
            "metric": "train_multichip", "n_devices": n_devices,
            "frames_per_sec": None,
            "error": f"only {len(jax.devices())} devices visible",
        }))
        return
    cfg = Config(
        model=ModelConfig(
            transformer=TransformerConfig(
                encoder_layer=1, decoder_layer=1,
                encoder_hidden=16, decoder_hidden=16,
                encoder_head=2, decoder_head=2,
                conv_filter_size=32,
            ),
            reference_encoder=ReferenceEncoderConfig(
                encoder_layer=1, conv_layer=1, encoder_hidden=16,
                encoder_head=2, conv_filter_size=16,
            ),
            variance_predictor=VariancePredictorConfig(filter_size=16),
            compute_dtype="float32",
        )
    )
    mesh = (
        make_mesh(data=n_devices, model=1, devices=jax.devices()[:n_devices])
        if n_devices > 1
        else None  # the production 1x1 path: no mesh at all
    )
    Bn, L, T = MULTICHIP_B_PER_CHIP * n_devices, MULTICHIP_L, MULTICHIP_T
    rng_np = np.random.default_rng(0)
    batch = dict(
        speakers=jnp.zeros((Bn,), jnp.int32),
        texts=jnp.asarray(rng_np.integers(1, 300, (Bn, L)), jnp.int32),
        src_lens=jnp.full((Bn,), L, jnp.int32),
        mels=jnp.asarray(rng_np.standard_normal((Bn, T, 80)), jnp.float32),
        mel_lens=jnp.full((Bn,), T, jnp.int32),
        pitches=jnp.asarray(rng_np.standard_normal((Bn, L)), jnp.float32),
        energies=jnp.asarray(rng_np.standard_normal((Bn, L)), jnp.float32),
        durations=jnp.full((Bn, L), T // L, jnp.int32),
    )
    if mesh is not None:
        batch = {
            k: jax.device_put(v, NamedSharding(mesh, P("data")))
            for k, v in batch.items()
        }
    model = build_model(cfg)
    variables = init_variables(model, cfg, jax.random.PRNGKey(0))
    tx = make_optimizer(cfg.train)
    state = TrainState.create(variables, tx)
    if mesh is not None:
        state = jax.device_put(state, NamedSharding(mesh, P()))
    step = make_train_step(model, tx, cfg, mesh=mesh, state_shardings=None)
    rng = jax.random.PRNGKey(1)
    # the step folds in state.step (trainer.py), so one key is correct here
    for _ in range(MULTICHIP_WARMUP):
        state, losses = step(state, batch, rng)  # jaxlint: disable=JL006
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(MULTICHIP_STEPS):
        state, losses = step(state, batch, rng)  # jaxlint: disable=JL006
    jax.block_until_ready((state, losses))
    dt = time.perf_counter() - t0
    fps = Bn * T * MULTICHIP_STEPS / dt
    print(json.dumps({
        "metric": "train_multichip",
        "n_devices": n_devices,
        "mesh": [n_devices, 1],
        "batch": Bn,
        "steps": MULTICHIP_STEPS,
        "frames_per_sec": fps,
        "frames_per_sec_per_chip": fps / n_devices,
        "platform": "cpu-proxy",
    }))


def run_multichip(device_counts=MULTICHIP_DEVICE_COUNTS):
    """The --multichip scaling sweep: one child process per device count,
    each with ``--xla_force_host_platform_device_count={n}`` (the flag only
    takes effect before the backend initializes, hence the re-exec), fixed
    per-chip batch. Prints one JSON line per point; the recorded
    MULTICHIP_r*.json rides `--compare` as multichip_frames_per_s_per_chip_{n}d."""
    import re

    here = os.path.dirname(os.path.abspath(__file__))
    for n in device_counts:
        env = dict(os.environ)
        # CPU proxy on purpose: virtual devices exercise the GSPMD
        # partitioner + collectives exactly like real chips; absolute
        # numbers are meaningless, the per-chip RATIO is the metric
        env["JAX_PLATFORMS"] = "cpu"
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+",
            "",
            env.get("XLA_FLAGS", ""),
        ).strip()
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip()
        )
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--multichip-inner", "--n-devices", str(n)],
                capture_output=True,
                text=True,
                timeout=600,
                env=env,
                cwd=here,
            )
        except subprocess.TimeoutExpired:
            print(json.dumps({
                "metric": "train_multichip", "n_devices": n,
                "frames_per_sec": None, "error": "timeout after 600s",
            }))
            continue
        line = next(
            (ln for ln in reversed(proc.stdout.strip().splitlines())
             if ln.startswith("{")),
            None,
        )
        print(line or json.dumps({
            "metric": "train_multichip", "n_devices": n,
            "frames_per_sec": None,
            "error": f"rc={proc.returncode}: {proc.stderr[-300:]}",
        }))


# ---------------------------------------------------------------------------
# --mesh-serve: weak-scaling sweep over mesh-slice replica geometries
# ---------------------------------------------------------------------------

MESHSERVE_GEOMETRIES = ((1, 1), (2, 1), (2, 2), (1, 4))
MESHSERVE_CLIENTS = 8
# CPU-proxy caveat, same as --multichip: virtual devices exercise the
# GSPMD partitioner + the sharded dispatch path exactly like real chips,
# but collectives are memcpys — the sweep measures mesh-serving MACHINERY
# overhead (resharding hops, per-dispatch device_puts, replicated-weight
# broadcast), never kernel or ICI throughput. The 1x1 point normalizes.


def _mesh_serve_child(dp: int, tp: int, duration: float = 3.0):
    """One weak-scaling point; runs in a child process whose XLA_FLAGS
    force dp*tp host devices. The tiny serve engine becomes a (dp, tp)
    mesh slice (same resolve_mesh path as training), precompiles its
    lattice through the ProgramRegistry, and serves closed-loop clients
    through the ContinuousBatcher. Emits ONE JSON line; steady_compiles
    MUST read zero — the registry invariant on sharded AOT programs."""
    import numpy as np

    import jax

    from speakingstyle_tpu.obs import MetricsRegistry
    from speakingstyle_tpu.serving.batcher import ContinuousBatcher
    from speakingstyle_tpu.serving.engine import (
        CompileMonitor,
        SynthesisRequest,
    )

    geometry = f"{dp}x{tp}"
    if len(jax.devices()) < dp * tp:
        print(json.dumps({
            "metric": "serve_mesh", "geometry": geometry, "qps": None,
            "error": f"only {len(jax.devices())} devices visible",
        }))
        return
    engine, label = _serve_engine(tiny=True, mesh=(dp, tp))
    serve = engine.cfg.serve
    rng = np.random.default_rng(0)
    max_src = serve.src_buckets[-1]
    max_len = min(max_src, serve.mel_buckets[-1] // serve.frames_per_phoneme)
    max_ref = engine.style.lattice.max_ref if engine.style is not None else 8
    hot_refs = [
        rng.standard_normal(
            (int(rng.integers(max(8, max_ref // 2), max_ref + 1)),
             engine.n_mels)
        ).astype(np.float32)
        for _ in range(8)
    ]

    def make_request(i: int) -> SynthesisRequest:
        L = int(rng.integers(max(4, max_len // 2), max_len + 1))
        return SynthesisRequest(
            id=f"mesh{i}",
            sequence=rng.integers(1, 300, L).astype(np.int32),
            ref_mel=hot_refs[i % len(hot_refs)],
        )

    secs = engine.precompile()
    aot_programs = engine.compile_count
    # warmup: one dispatch per batch bucket — first-execution transfers
    # through dispatch_sharding's device_puts, zero further compiles
    for b in engine.lattice.batch_buckets:
        engine.run([make_request(10_000 + b * 100 + j) for j in range(b)])

    point = MetricsRegistry()
    batcher = ContinuousBatcher(engine, registry=point)
    stop_at = time.perf_counter() + duration

    def client(cid: int):
        i = 0
        while time.perf_counter() < stop_at:
            req = make_request(cid * 1_000_000 + i)
            try:
                batcher.submit(req).result(timeout=60)
            except Exception:
                return
            i += 1

    with CompileMonitor() as mon:
        threads = [
            threading.Thread(target=client, args=(c,), daemon=True)
            for c in range(MESHSERVE_CLIENTS)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        batcher.close()
    hist = point.histogram("serve_request_latency_seconds")

    def pct_ms(q):
        p = hist.percentile(q)
        return round(1e3 * p, 1) if p is not None else None

    print(json.dumps({
        "metric": "serve_mesh",
        "geometry": geometry,
        "mesh": [dp, tp],
        "devices": dp * tp,
        "clients": MESHSERVE_CLIENTS,
        "qps": round(hist.count / dt, 2),
        "p50_ms": pct_ms(0.50),
        "p95_ms": pct_ms(0.95),
        "aot_programs": aot_programs,
        "precompile_s": round(secs, 1),
        "steady_compiles": mon.count,
        "model": label,
        "platform": "cpu-proxy",
    }))


def run_mesh_serve(geometries=MESHSERVE_GEOMETRIES, duration: float = 3.0):
    """The --mesh-serve sweep: one child process per (dp, tp) geometry,
    each with ``--xla_force_host_platform_device_count={dp*tp}`` (the
    flag only binds before the backend initializes, hence the re-exec —
    run_multichip's pattern). Weak scaling over replica SHAPE: offered
    load is fixed, the replica's mesh grows; on the CPU proxy the
    meshserve_qps_{geometry} RATIO vs 1x1 is the metric (mesh-serving
    machinery overhead), absolute QPS is not. Rides `--compare`."""
    import re

    here = os.path.dirname(os.path.abspath(__file__))
    for dp, tp in geometries:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+",
            "",
            env.get("XLA_FLAGS", ""),
        ).strip()
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={dp * tp}"
        ).strip()
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--mesh-serve-inner", "--mesh", str(dp), str(tp),
                 "--duration", str(duration)],
                capture_output=True,
                text=True,
                timeout=600,
                env=env,
                cwd=here,
            )
        except subprocess.TimeoutExpired:
            print(json.dumps({
                "metric": "serve_mesh", "geometry": f"{dp}x{tp}",
                "qps": None, "error": "timeout after 600s",
            }))
            continue
        line = next(
            (ln for ln in reversed(proc.stdout.strip().splitlines())
             if ln.startswith("{")),
            None,
        )
        print(line or json.dumps({
            "metric": "serve_mesh", "geometry": f"{dp}x{tp}", "qps": None,
            "error": f"rc={proc.returncode}: {proc.stderr[-300:]}",
        }))


def _longform_child(duration: float = 3.0):
    """Inner body of --longform (re-exec'd with 2 forced host devices so
    the ring tier has a seq mesh to shard over).

    One chapter 10x the largest interactive lattice bucket (160 phonemes
    against src_buckets=[16]) synthesized end-to-end on BOTH tiers:

      * chunked — through the chapter chunker, the deadline-sharing
        group on the continuous batcher, and the equal-power stitcher;
        records chapter TTFA, full-chapter wall time, the per-seam
        click-detector maximum (seam_rms_max), and the CompileMonitor
        count across the measured chapters (must be 0);
      * ring — one ring-attention program at the dedicated long-form
        bucket (1 x 160 x 320 on a seq=2 mesh), streamed through the
        engine's precompiled vocoder windows; records the same TTFA /
        wall / compile numbers plus ring_vs_dense_mel_l2, the RMS
        distance between the ring free-run's mel and the unsharded dense
        model at the identical padded geometry (the sharding-correctness
        parity the acceptance gate tracks).

    CPU-proxy caveat (PERF.md): absolute times here measure scheduling
    and stitching overhead on the tiny model — the honest signals are
    the zero compile counts, the seam bound, and the parity distance,
    not the milliseconds.
    """
    import dataclasses
    import statistics

    import numpy as np

    import jax

    from speakingstyle_tpu.configs.config import LongformConfig
    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.models.hifigan import Generator
    from speakingstyle_tpu.obs import MetricsRegistry
    from speakingstyle_tpu.serving.batcher import ContinuousBatcher
    from speakingstyle_tpu.serving.engine import (
        CompileMonitor,
        SynthesisEngine,
        SynthesisRequest,
    )
    from speakingstyle_tpu.serving.longform import (
        LongformService,
        RingTier,
        plan_chunks,
    )
    from speakingstyle_tpu.serving.server import TextFrontend

    base = _tiny_serve_config()
    serve = dataclasses.replace(
        base.serve, batch_buckets=[1, 2, 4],
        longform=LongformConfig(
            mesh_seq=2, src_buckets=[160], mel_buckets=[320],
            crossfade_frames=2, group_depth=4,
            deadline_ms_per_chunk=30_000.0,
        ),
    )
    cfg = dataclasses.replace(base, serve=serve)
    lf = cfg.serve.longform

    _mark("building long-form model parts")
    reg = MetricsRegistry()
    n_position = max(lf.mel_buckets[-1], lf.src_buckets[-1],
                     cfg.model.max_seq_len) + 1
    model = build_model(cfg, n_position=n_position)
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
    n_mels = cfg.preprocess.preprocessing.mel.n_mel_channels
    gparams = gen.init(
        jax.random.PRNGKey(0), np.zeros((1, 8, n_mels), np.float32)
    )["params"]
    engine = SynthesisEngine(cfg, variables, vocoder=(gen, gparams),
                             model=model, registry=reg)
    _mark(f"precompiling {len(engine.lattice)} interactive lattice points")
    engine.precompile()
    ring = RingTier(cfg, variables, engine, registry=reg)
    _mark(f"precompiling {len(ring.lattice)} ring lattice points (seq=2)")
    ring_precompile_s = ring.precompile()

    rng = np.random.default_rng(0)
    ref = rng.standard_normal((20, n_mels)).astype(np.float32)
    frontend = TextFrontend(cfg, ref)
    # 20 sentences x 8 words -> 160 phonemes under the tiny lexicon:
    # 10x the largest interactive src bucket
    words = ("one two three four five six seven eight."
             " nine ten eleven twelve thirteen fourteen fifteen sixteen.")
    text = " ".join(words for _ in range(10))

    def run_tier(svc, tier, n_id):
        """(ttfa_s, total_s, wav_samples, n_chunks) for one chapter."""
        t0 = time.monotonic()
        plan = svc.admit(f"bench.{tier}.{n_id}", {"text": text,
                                                  "tier": tier})
        assert plan.tier == tier, (plan.tier, tier)
        ttfa, samples = None, 0
        for piece in svc.stream(plan):
            if ttfa is None:
                ttfa = time.monotonic() - t0
            samples += piece.size
        return ttfa, time.monotonic() - t0, samples, len(plan.chunks)

    chunks0 = plan_chunks(text, frontend.sequence,
                          min(cfg.serve.src_buckets[-1],
                              cfg.serve.mel_buckets[-1]
                              // cfg.serve.frames_per_phoneme))
    seq = np.concatenate([c.sequence for c in chunks0])
    point = {
        "metric": "serve_longform",
        "chapter_phonemes": int(seq.size),
        "chunks": len(chunks0),
        "chapter_over_lattice": round(
            seq.size / cfg.serve.src_buckets[-1], 2),
    }
    with ContinuousBatcher(engine) as batcher:
        svc = LongformService(cfg, frontend, batcher, engine=engine,
                              ring=ring, registry=reg)
        for tier in ("chunked", "ring"):
            run_tier(svc, tier, "warm")  # first-execution transfers
            ttfas, totals, n = [], [], 0
            stop_at = time.perf_counter() + duration
            with CompileMonitor() as mon:
                while n == 0 or time.perf_counter() < stop_at:
                    ttfa, total, samples, _ = run_tier(svc, tier, n)
                    ttfas.append(ttfa)
                    totals.append(total)
                    n += 1
            point.update({
                f"{tier}_chapters": n,
                f"{tier}_ttfa_ms": round(
                    1e3 * statistics.median(ttfas), 2),
                f"{tier}_total_ms": round(
                    1e3 * statistics.median(totals), 2),
                f"{tier}_wav_samples": samples,
                f"{tier}_steady_compiles": mon.count,
            })
        point.update({
            "seams": reg.histogram("serve_longform_seam_rms").count,
            "seam_rms_max": round(
                reg.histogram("serve_longform_seam_rms").snapshot()["max"],
                5),
        })

    # sharding-correctness parity: the ring free-run vs the unsharded
    # dense model at the identical padded geometry (outside the compile
    # monitors — the dense reference runs eagerly)
    _mark("ring vs dense parity check")
    sv = engine.style.encode_mels([ref])[0]
    rres = ring.synthesize(
        SynthesisRequest(id="parity", sequence=seq, ref_mel=None, style=sv)
    )
    l_pad, t_pad = lf.src_buckets[-1], lf.mel_buckets[-1]
    texts = np.zeros((1, l_pad), np.int32)
    texts[0, :seq.size] = seq
    out = model.apply(
        variables,
        speakers=np.zeros((1,), np.int32),
        texts=texts,
        src_lens=np.asarray([seq.size], np.int32),
        mels=None, mel_lens=None, max_mel_len=t_pad,
        p_control=np.ones((1, l_pad), np.float32),
        e_control=np.ones((1, l_pad), np.float32),
        d_control=np.ones((1, l_pad), np.float32),
        gammas=sv.gamma.reshape(1, 1, -1),
        betas=sv.beta.reshape(1, 1, -1),
        deterministic=True,
    )
    dense_mel = jax.device_get(out["mel_postnet"])[0, :rres.mel_len]
    diff = rres.mel - dense_mel
    point.update({
        "ring_vs_dense_mel_l2": round(
            float(np.sqrt(np.mean(diff * diff))), 6),
        "ring_mel_len": rres.mel_len,
        "ring_precompile_s": round(ring_precompile_s, 2),
        "model": "tiny-cpu",
        "platform": "cpu-proxy",
    })
    print(json.dumps(point))


def run_longform(duration: float = 3.0):
    """The --longform drill: chunked-vs-ring chapter synthesis in a
    child process re-exec'd with ``--xla_force_host_platform_device_count
    =2`` (the ring tier needs a seq mesh; the flag only binds before the
    backend initializes — run_multichip's pattern). Emits ONE
    {"metric": "serve_longform"} line; rides ``--compare`` as the
    ``longform_*`` keys."""
    import re

    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+",
        "",
        env.get("XLA_FLAGS", ""),
    ).strip()
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count=2"
    ).strip()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--longform-inner", "--duration", str(duration)],
            capture_output=True,
            text=True,
            timeout=600,
            env=env,
            cwd=here,
        )
    except subprocess.TimeoutExpired:
        print(json.dumps({
            "metric": "serve_longform", "error": "timeout after 600s",
        }))
        return
    relayed = False
    for ln in proc.stdout.strip().splitlines():
        if ln.startswith("{"):
            print(ln)
            relayed = True
    if not relayed:
        print(json.dumps({
            "metric": "serve_longform",
            "error": f"rc={proc.returncode}: {proc.stderr[-300:]}",
        }))


# The distilled student is a different function, not a recast of the
# same weights: after the smoke-length in-bench distillation its
# golden-set RMS mel distance sits around 1.2-1.5 (vs ~0.1/~0.3 for the
# bf16/int8 recasts of the teacher). 2.0 gives headroom over run-to-run
# noise while still slamming the door on a broken student — non-finite,
# empty, or unconverged output lands far above it.
STUDENT_TIER_TOLERANCE = 2.0


def _tiers_bench_config(tmp: str):
    """Teacher config for the --tiers frontier: the tiny serve model
    deepened to 2+2 transformer layers with a 64-wide FFN so the
    student's halved depth/width is visible above CPU dispatch overhead,
    but hidden kept at 16 — int8 dequant-on-read cost grows with
    hidden^2 on CPU and at 32 it erases the student's win (measured).
    Train paths point into ``tmp`` and the LR ramp is shortened
    (train.loss.anneal_steps gates the ramp to anneal_lr) so the
    smoke-length distillation actually moves."""
    import dataclasses

    from speakingstyle_tpu.configs.config import TiersConfig

    base = _tiny_serve_config()
    return dataclasses.replace(
        base,
        model=dataclasses.replace(
            base.model,
            transformer=dataclasses.replace(
                base.model.transformer, encoder_layer=2, decoder_layer=2,
                conv_filter_size=64,
            ),
            postnet_layers=4,
        ),
        serve=dataclasses.replace(
            base.serve,
            batch_buckets=[1, 4],
            fleet=dataclasses.replace(
                base.serve.fleet,
                class_deadline_ms={"interactive": 250.0, "batch": 2000.0,
                                   "long_form": 8000.0},
            ),
            tiers=TiersConfig(
                enabled=True,
                precisions=["f32", "bf16", "int8"],
                class_tier={"interactive": "student-int8",
                            "batch": "teacher-bf16",
                            "long_form": "teacher-f32"},
                default_tier="teacher-f32",
                tier_tolerance=0.5,
                golden_set_size=4,
            ),
        ),
        train=dataclasses.replace(
            base.train,
            path=dataclasses.replace(
                base.train.path,
                ckpt_path=os.path.join(tmp, "ckpt"),
                log_path=os.path.join(tmp, "log"),
            ),
            step=dataclasses.replace(
                base.train.step, total_step=80, log_step=40, save_step=80,
            ),
            loss=dataclasses.replace(base.train.loss, anneal_steps=5),
        ),
    )


def run_tiers(duration: float = 3.0, distill_steps: int = 80):
    """The --tiers drill: the quality-vs-speed frontier over the
    precision lattice (teacher at f32/bf16/int8) and the distilled fast
    tier (student at f32/int8), each canary-gated against the
    teacher-f32 anchor before it may ship.

    Per tier it emits one {"metric": "serve_tier"} line — golden-set
    mel_l2 from the quality gate, a MOS proxy derived from it, batch-1
    closed-loop latency p50/p999 (the TTFA proxy on CPU), QPS, and the
    CompileMonitor count (must be zero: every tier serves off the AOT
    lattice). A mixed-tier phase then routes classes through ONE
    TierRouter over per-tier FleetRouters and the closing
    {"metric": "serve_tier_frontier"} line reports the routed fast
    tier's speedup vs the anchor plus per-tier dispatch counts. Rides
    ``--compare`` as the ``tier_*`` keys; any SHIPPED tier whose
    mel_l2 exceeds its tolerance hard-fails the diff there.
    """
    import dataclasses
    import tempfile

    import numpy as np

    import jax

    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.models.hifigan import Generator
    from speakingstyle_tpu.obs import MetricsRegistry
    from speakingstyle_tpu.serving.engine import (
        CompileMonitor,
        SynthesisEngine,
        SynthesisRequest,
    )
    from speakingstyle_tpu.serving.fleet import FleetRouter
    from speakingstyle_tpu.serving.lattice import BucketLattice
    from speakingstyle_tpu.serving.tiers import (
        TierRouter,
        parse_tier,
        tier_gate,
    )
    from speakingstyle_tpu.training.distill import run_distillation

    _mark("building tiers teacher")
    tmp = tempfile.mkdtemp(prefix="bench_tiers_")
    cfg = _tiers_bench_config(tmp)
    lattice = BucketLattice.from_config(cfg.serve)
    n_position = max(lattice.max_mel, lattice.max_src,
                     cfg.model.max_seq_len) + 1
    t_model = build_model(cfg, n_position=n_position)
    t_vars = init_variables(t_model, cfg, jax.random.PRNGKey(0))
    # random weights free-run ~zero durations -> empty gate outputs; the
    # serving tests' duration bias makes the teacher (and, through
    # teacher-forced durations, the distilled student) speak
    dp = t_vars["params"]["variance_adaptor"]["duration_predictor"]
    dp["linear_layer"]["bias"] = dp["linear_layer"]["bias"] + 1.1
    gen = Generator(
        upsample_rates=(2, 2), upsample_kernel_sizes=(4, 4),
        upsample_initial_channel=16, resblock_kernel_sizes=(3,),
        resblock_dilation_sizes=((1,),),
    )
    n_mels = cfg.preprocess.preprocessing.mel.n_mel_channels
    gparams = gen.init(
        jax.random.PRNGKey(0), np.zeros((1, 8, n_mels), np.float32)
    )["params"]
    teacher = SynthesisEngine(
        cfg, t_vars, vocoder=(gen, gparams), lattice=lattice, model=t_model
    )
    t0 = time.perf_counter()
    teacher.precompile()
    teacher_compiles = teacher.compile_count
    _mark(f"teacher precompiled {teacher_compiles} programs in "
          f"{time.perf_counter() - t0:.1f}s "
          f"({len(lattice)} points x {lattice.precisions})")

    _mark(f"distilling student ({distill_steps} steps)")
    t0 = time.perf_counter()
    state, s_cfg = run_distillation(
        cfg, teacher_variables=t_vars, max_steps=distill_steps,
        batch_size=4, log=False,
    )
    distill_s = time.perf_counter() - t0
    s_vars = {"params": state.params, "batch_stats": state.batch_stats}
    s_serve_cfg = dataclasses.replace(s_cfg, serve=dataclasses.replace(
        s_cfg.serve,
        tiers=dataclasses.replace(cfg.serve.tiers,
                                  precisions=["f32", "int8"]),
    ))
    s_lattice = BucketLattice.from_config(s_serve_cfg.serve)
    s_model = build_model(s_serve_cfg, n_position=n_position)
    student = SynthesisEngine(
        s_serve_cfg, s_vars, vocoder=(gen, gparams), lattice=s_lattice,
        model=s_model,
    )
    t0 = time.perf_counter()
    student.precompile()
    student_compiles = student.compile_count
    _mark(f"student precompiled {student_compiles} programs in "
          f"{time.perf_counter() - t0:.1f}s; distill took {distill_s:.1f}s")

    def n_params(variables):
        return int(sum(x.size for x in
                       jax.tree_util.tree_leaves(variables["params"])))

    rng = np.random.default_rng(0)
    max_ref = cfg.serve.style.ref_buckets[-1]
    hot_refs = [
        rng.standard_normal(
            (int(rng.integers(max(8, max_ref // 2), max_ref + 1)), n_mels)
        ).astype(np.float32)
        for _ in range(8)
    ]
    max_len = min(cfg.serve.src_buckets[-1],
                  cfg.serve.mel_buckets[-1] // cfg.serve.frames_per_phoneme)

    def make_request(i: int, precision=None, priority=None):
        L = int(rng.integers(max(4, max_len // 2), max_len + 1))
        return SynthesisRequest(
            id=f"tier{i}",
            sequence=rng.integers(1, 300, L).astype(np.int32),
            ref_mel=hot_refs[i % len(hot_refs)],
            precision=precision,
            priority=priority,
        )

    # (tier name, engine, gate tolerance override); the anchor gates
    # itself by identity and carries the config default tolerance
    tiers = (
        ("teacher-f32", teacher, None),
        ("teacher-bf16", teacher, None),
        ("teacher-int8", teacher, None),
        ("student-f32", student, STUDENT_TIER_TOLERANCE),
        ("student-int8", student, STUDENT_TIER_TOLERANCE),
    )
    p50_by_tier = {}
    qps_by_tier = {}
    gates = {}
    all_zero_compiles = True
    for name, engine, tol in tiers:
        spec = parse_tier(name)
        if name == "teacher-f32":
            gate = None
            mel_l2, tolerance = 0.0, cfg.serve.tiers.tier_tolerance
            shipped, gate_detail, gate_ms = True, "ungated anchor", 0.0
        else:
            gate = tier_gate(engine, teacher, cfg, name, tolerance=tol)
            gates[name] = gate
            mel_l2, tolerance = gate.mel_l2, gate.tolerance
            shipped, gate_detail, gate_ms = (gate.shipped, gate.detail,
                                             gate.gate_ms)
        # first-execution transfer warmup at this precision (compiles
        # already happened in precompile)
        for j in range(5):
            engine.run([make_request(10_000 + j, precision=spec.precision)])
        lat = []
        with CompileMonitor() as mon:
            t0 = time.perf_counter()
            i = 0
            while time.perf_counter() - t0 < duration:
                a = time.perf_counter()
                engine.run([make_request(i, precision=spec.precision)])
                lat.append((time.perf_counter() - a) * 1e3)
                i += 1
            dt = time.perf_counter() - t0
        lat.sort()
        p50 = lat[len(lat) // 2]
        p999 = lat[min(len(lat) - 1, int(len(lat) * 0.999))]
        qps = len(lat) / dt
        p50_by_tier[name] = p50
        qps_by_tier[name] = qps
        all_zero_compiles = all_zero_compiles and mon.count == 0
        print(json.dumps({
            "metric": "serve_tier",
            "tier": name,
            "precision": spec.precision,
            "n_params": n_params(t_vars if spec.model == "teacher"
                                 else s_vars),
            "qps": round(qps, 2),
            "ttfa_p50_ms": round(p50, 3),
            "ttfa_p999_ms": round(p999, 3),
            "steady_compiles": mon.count,
            "mel_l2": round(mel_l2, 4),
            "tolerance": tolerance,
            "mel_l2_over_tolerance": round(mel_l2 / tolerance, 4),
            # a coarse quality stand-in so the frontier has a quality
            # axis in one number; NOT a listening test
            "mos_proxy": round(max(1.0, 5.0 - 1.5 * mel_l2), 2),
            "shipped": shipped,
            "gate_ms": round(gate_ms, 1),
            "gate_detail": gate_detail,
            "unit": "ms batch-1 closed-loop engine dispatch "
                    "(TTFA proxy on cpu)",
            "model": "tiny-cpu",
            "platform": "cpu-proxy",
        }))

    # mixed-tier phase: ONE TierRouter over per-tier fleets (each
    # replicas=1, sharing the precompiled engines), driven by a single
    # closed-loop client cycling the traffic classes — records that
    # class->tier routing + per-tier dispatch counters work end to end
    _mark("mixed-tier routing phase")
    registry = MetricsRegistry()
    router = TierRouter(cfg, registry=registry)
    routed = (
        ("teacher-f32", teacher, cfg, None),
        ("teacher-bf16", teacher, cfg, gates["teacher-bf16"]),
        ("student-int8", student, s_serve_cfg, gates["student-int8"]),
    )
    for name, engine, tier_cfg, gate in routed:
        fleet = FleetRouter(
            lambda reg, e=engine: e, tier_cfg, replicas=1,
            registry=registry, tier=name,
        )
        fleet.wait_ready(timeout=120, n=1)
        router.add_tier(name, fleet, gate=gate)
    classes = ("interactive", "batch", "long_form")
    mixed_done = 0
    with CompileMonitor() as mon:
        stop_at = time.perf_counter() + duration
        i = 0
        while time.perf_counter() < stop_at:
            req = make_request(1_000_000 + i,
                               priority=classes[i % len(classes)])
            router.submit(req).result(timeout=60)
            mixed_done += 1
            i += 1
    dispatch = {
        name: int(registry.counter("serve_tier_dispatch_total",
                                   labels={"tier": name}).value)
        for name in router.tiers()
    }
    routing = router.routing_table()
    fast_tier = routing.get("interactive", router.default_tier)
    router.close()

    anchor_p50 = p50_by_tier["teacher-f32"]
    fast_p50 = p50_by_tier.get(fast_tier)
    print(json.dumps({
        "metric": "serve_tier_frontier",
        "anchor": "teacher-f32",
        "fast_tier": fast_tier,
        "speedup_ttfa_p50": (round(anchor_p50 / fast_p50, 3)
                             if fast_p50 else None),
        "speedup_qps": (round(qps_by_tier[fast_tier]
                              / qps_by_tier["teacher-f32"], 3)
                        if fast_tier in qps_by_tier else None),
        "tiers_shipped": sorted(
            ["teacher-f32"] + [n for n, g in gates.items() if g.shipped]
        ),
        "zero_steady_compiles": all_zero_compiles and mon.count == 0,
        "mixed_requests": mixed_done,
        "mixed_steady_compiles": mon.count,
        "dispatch": dispatch,
        "routing": routing,
        "aot_programs": {"teacher": teacher_compiles,
                         "student": student_compiles},
        "distill_seconds": round(distill_s, 1),
        "model": "tiny-cpu",
        "platform": "cpu-proxy",
        "note": "CPU proxy: batch-1 engine dispatch stands in for TTFA "
                "and int8 pays a dequant-on-read tax CPUs never "
                "amortize; real int8 speedups await the chip campaign "
                "(ROADMAP item 5)",
    }))


REGRESSION_THRESHOLD = 0.10


def _absorb_record(rec, metrics):
    """One emitted bench line -> {key: (value, direction)} entries.
    direction "higher" = more is better (throughput), "lower" = less is
    better (latency percentiles). Null values (guarded failures) skip."""
    if not isinstance(rec, dict):
        return
    m = rec.get("metric")
    if m in ("train_mel_frames_per_sec", "serve_sequential_batch1_qps",
             "synthesis_realtime_factor", "hifigan_realtime_factor",
             "serve_speedup_vs_sequential", "serve_fleet_scaling"):
        if isinstance(rec.get("value"), (int, float)):
            metrics[m] = (float(rec["value"]), "higher")
    elif m == "synthesis_batch1_latency_ms":
        if isinstance(rec.get("value"), (int, float)):
            metrics[m] = (float(rec["value"]), "lower")
    elif m == "serve_offered_load":
        c = rec.get("clients")
        if isinstance(rec.get("qps"), (int, float)):
            metrics[f"serve_qps_{c}c"] = (float(rec["qps"]), "higher")
        for pct in ("p50_ms", "p95_ms", "p99_ms", "p999_ms"):
            if isinstance(rec.get(pct), (int, float)):
                metrics[f"serve_{pct}_{c}c"] = (float(rec[pct]), "lower")
    elif m == "serve_fleet_load":
        r = rec.get("replicas")
        if isinstance(rec.get("qps"), (int, float)):
            metrics[f"fleet_qps_{r}r"] = (float(rec["qps"]), "higher")
        for pct in ("ttfa_p50_ms", "ttfa_p95_ms", "ttfa_p999_ms",
                    "full_p50_ms", "full_p95_ms", "full_p999_ms"):
            if isinstance(rec.get(pct), (int, float)):
                metrics[f"fleet_{pct}_{r}r"] = (float(rec[pct]), "lower")
    elif m == "serve_latency":
        p = rec.get("pipeline")
        for k in ("ttfa_p50_ms", "ttfa_p95_ms", "ttfa_p99_ms",
                  "ttfa_p999_ms", "full_p50_ms", "full_p95_ms",
                  "full_p99_ms", "full_p999_ms"):
            if isinstance(rec.get(k), (int, float)):
                metrics[f"latency_{k}_{p}"] = (float(rec[k]), "lower")
    elif m == "serve_chaos":
        # the drill's SLO numbers ride the regression gate like any other
        # metric; lost_requests additionally carries a hard zero gate in
        # run_compare (any loss fails the diff outright)
        if isinstance(rec.get("recovery_ms"), (int, float)):
            metrics["chaos_recovery_ms"] = (float(rec["recovery_ms"]),
                                            "lower")
        if isinstance(rec.get("qps_recovery_ratio"), (int, float)):
            metrics["chaos_qps_recovery_ratio"] = (
                float(rec["qps_recovery_ratio"]), "higher")
        if isinstance(rec.get("lost_requests"), (int, float)):
            metrics["chaos_lost_requests"] = (float(rec["lost_requests"]),
                                              "lower")
        if isinstance(rec.get("shed"), (int, float)):
            metrics["chaos_shed"] = (float(rec["shed"]), "lower")
        # lock-witness numbers (present when the drill ran with
        # SPEAKINGSTYLE_CHECKS=1): hold p999 bounds critical-section
        # length; inversions carry a hard zero expectation
        if isinstance(rec.get("lock_hold_p999_max_s"), (int, float)):
            metrics["chaos_lock_hold_p999_max_s"] = (
                float(rec["lock_hold_p999_max_s"]), "lower")
        if isinstance(rec.get("lock_order_inversions"), (int, float)):
            metrics["chaos_lock_order_inversions"] = (
                float(rec["lock_order_inversions"]), "lower")
    elif m == "serve_cluster":
        # the multi-process storm (real replica processes behind the
        # ClusterRouter); cluster_lost_requests carries the hard zero
        # gate in run_compare — a control plane that loses requests
        # through a SIGKILL or a partition is broken, not 10% slower
        for src, dst in (
            ("lost_requests", "cluster_lost_requests"),
            ("kill_recovery_ms", "cluster_kill_recovery_ms"),
            ("partition_recovery_ms", "cluster_partition_recovery_ms"),
            ("lease_requeue_p50_ms", "cluster_lease_requeue_p50_ms"),
            ("lease_requeue_p999_ms", "cluster_lease_requeue_p999_ms"),
            ("steady_compiles", "cluster_steady_compiles"),
            ("shed", "cluster_shed"),
            ("lock_hold_p999_max_s", "cluster_lock_hold_p999_max_s"),
            ("lock_order_inversions", "cluster_lock_order_inversions"),
        ):
            if isinstance(rec.get(src), (int, float)):
                metrics[dst] = (float(rec[src]), "lower")
        for src, dst in (
            ("steady_qps", "cluster_steady_qps"),
            ("qps_recovery_ratio", "cluster_qps_recovery_ratio"),
        ):
            if isinstance(rec.get(src), (int, float)):
                metrics[dst] = (float(rec[src]), "higher")
    elif m == "serve_trace":
        # the tracing-overhead ablation; the over-budget overhead and
        # lost_requests carry hard gates in run_compare — tracing that
        # slows the fleet >2% on TTFA p50 or drops a request does not
        # ship at any threshold. The overhead itself hovers around
        # zero where relative diffs are pure noise, so only the budget
        # excess (0 when passing) is stored; the signed value stays in
        # the emitted point
        if isinstance(rec.get("overhead_ttfa_p50_pct"), (int, float)):
            metrics["trace_overhead_over_budget_pct"] = (
                max(0.0, float(rec["overhead_ttfa_p50_pct"]) - 2.0),
                "lower")
        for src, dst in (
            ("traced_ttfa_p50_ms", "trace_on_ttfa_p50_ms"),
            ("untraced_ttfa_p50_ms", "trace_off_ttfa_p50_ms"),
            ("lost_requests", "trace_lost_requests"),
            ("steady_compiles", "trace_steady_compiles"),
        ):
            if isinstance(rec.get(src), (int, float)):
                metrics[dst] = (float(rec[src]), "lower")
        for src, dst in (
            ("qps", "trace_qps"),
            ("cross_process_traces", "trace_cross_process_traces"),
        ):
            if isinstance(rec.get(src), (int, float)):
                metrics[dst] = (float(rec[src]), "higher")
    elif m == "serve_quality":
        # the quality-plane drill; missed_detection, false_pages,
        # lost_requests, and the validator overhead budget all carry
        # hard gates in run_compare — a quality plane that misses a
        # poisoned tier, pages a healthy fleet, drops work, or taxes
        # the hot path >2% does not ship. As with serve_trace, only
        # the budget excess (0 when passing) rides the relative diff;
        # the signed overhead stays in the emitted point
        if isinstance(rec.get("overhead_ttfa_p50_pct"), (int, float)):
            metrics["quality_overhead_over_budget_pct"] = (
                max(0.0, float(rec["overhead_ttfa_p50_pct"]) - 2.0),
                "lower")
        for src, dst in (
            ("missed_detection", "quality_missed_detection"),
            ("false_pages", "quality_false_pages"),
            ("probes_to_detection", "quality_probes_to_detection"),
            ("lost_requests", "quality_lost_requests"),
            ("steady_compiles", "quality_steady_compiles"),
            ("unchecked_ttfa_p50_ms", "quality_off_ttfa_p50_ms"),
            ("checked_ttfa_p50_ms", "quality_on_ttfa_p50_ms"),
        ):
            if isinstance(rec.get(src), (int, float)):
                metrics[dst] = (float(rec[src]), "lower")
        if isinstance(rec.get("qps"), (int, float)):
            metrics["quality_qps"] = (float(rec["qps"]), "higher")
    elif m == "serve_rollout":
        # the live-upgrade drill; rollout_lost_requests carries the same
        # hard zero gate as chaos/traffic in run_compare — an upgrade
        # that drops requests is an outage, not a percentage
        for k in ("rollout_duration_ms", "rollout_canary_ms",
                  "rollout_steady_compiles", "rollout_lost_requests"):
            if isinstance(rec.get(k), (int, float)):
                metrics[k] = (float(rec[k]), "lower")
    elif m == "serve_traffic":
        # the capacity storm's SLO numbers; lost_requests carries the
        # same hard zero gate as the chaos drill in run_compare
        if isinstance(rec.get("qps_per_replica_steady"), (int, float)):
            metrics["traffic_qps_per_replica_steady"] = (
                float(rec["qps_per_replica_steady"]), "higher")
        if isinstance(rec.get("qps_per_replica_flash"), (int, float)):
            metrics["traffic_qps_per_replica_flash"] = (
                float(rec["qps_per_replica_flash"]), "higher")
        if isinstance(rec.get("flash_shed_fraction"), (int, float)):
            metrics["traffic_flash_shed_fraction"] = (
                float(rec["flash_shed_fraction"]), "lower")
        if isinstance(rec.get("scaleup_reaction_ms"), (int, float)):
            metrics["traffic_scaleup_reaction_ms"] = (
                float(rec["scaleup_reaction_ms"]), "lower")
        if isinstance(rec.get("lost_requests"), (int, float)):
            metrics["traffic_lost_requests"] = (
                float(rec["lost_requests"]), "lower")
        if isinstance(rec.get("steady_compiles"), (int, float)):
            metrics["traffic_steady_compiles"] = (
                float(rec["steady_compiles"]), "lower")
        if isinstance(rec.get("lock_hold_p999_max_s"), (int, float)):
            metrics["traffic_lock_hold_p999_max_s"] = (
                float(rec["lock_hold_p999_max_s"]), "lower")
        if isinstance(rec.get("lock_order_inversions"), (int, float)):
            metrics["traffic_lock_order_inversions"] = (
                float(rec["lock_order_inversions"]), "lower")
    elif m == "serve_longform":
        # chapter synthesis on both tiers; the compile counts ride as
        # lower-is-better (floor and expected value: zero), seam_rms_max
        # is the click-detector bound, ring_vs_dense_mel_l2 the
        # sharding-correctness parity distance
        for k in ("chunked_ttfa_ms", "chunked_total_ms", "ring_ttfa_ms",
                  "ring_total_ms", "seam_rms_max", "ring_vs_dense_mel_l2",
                  "chunked_steady_compiles", "ring_steady_compiles"):
            if isinstance(rec.get(k), (int, float)):
                metrics[f"longform_{k}"] = (float(rec[k]), "lower")
    elif m == "train_multichip":
        n = rec.get("n_devices")
        if isinstance(rec.get("frames_per_sec_per_chip"), (int, float)):
            metrics[f"multichip_frames_per_s_per_chip_{n}d"] = (
                float(rec["frames_per_sec_per_chip"]), "higher")
    elif m == "serve_mesh":
        # per-geometry QPS of a mesh-slice replica; steady_compiles rides
        # as lower-is-better (its floor — and expected value — is zero)
        g = rec.get("geometry")
        if isinstance(rec.get("qps"), (int, float)):
            metrics[f"meshserve_qps_{g}"] = (float(rec["qps"]), "higher")
        if isinstance(rec.get("p95_ms"), (int, float)):
            metrics[f"meshserve_p95_ms_{g}"] = (float(rec["p95_ms"]),
                                                "lower")
        if isinstance(rec.get("steady_compiles"), (int, float)):
            metrics[f"meshserve_steady_compiles_{g}"] = (
                float(rec["steady_compiles"]), "lower")
    elif m == "serve_tier":
        # one quality-tier frontier point; mel_l2_over_tolerance rides
        # ONLY for shipped tiers (a gated-out tier was correctly kept
        # off the routing table — its distance is a report, not a
        # regression) and carries a hard >1.0 gate in run_compare
        t = rec.get("tier")
        if isinstance(rec.get("qps"), (int, float)):
            metrics[f"tier_{t}_qps"] = (float(rec["qps"]), "higher")
        for k in ("ttfa_p50_ms", "ttfa_p999_ms", "steady_compiles"):
            if isinstance(rec.get(k), (int, float)):
                metrics[f"tier_{t}_{k}"] = (float(rec[k]), "lower")
        if rec.get("shipped") and isinstance(
                rec.get("mel_l2_over_tolerance"), (int, float)):
            metrics[f"tier_{t}_mel_l2_over_tolerance"] = (
                float(rec["mel_l2_over_tolerance"]), "lower")
    elif m == "serve_tier_frontier":
        if isinstance(rec.get("speedup_ttfa_p50"), (int, float)):
            metrics["tier_frontier_speedup_ttfa_p50"] = (
                float(rec["speedup_ttfa_p50"]), "higher")
        if isinstance(rec.get("mixed_steady_compiles"), (int, float)):
            metrics["tier_mixed_steady_compiles"] = (
                float(rec["mixed_steady_compiles"]), "lower")
    elif m == "serve_style_cache_qps_gain":
        if isinstance(rec.get("value"), (int, float)):
            metrics[m] = (float(rec["value"]), "higher")
    elif m == "serve_style_load":
        h = int(round(100 * rec.get("hit_rate", 0)))
        if isinstance(rec.get("qps"), (int, float)):
            metrics[f"style_qps_h{h}"] = (float(rec["qps"]), "higher")
        for pct in ("hit_p50_ms", "cold_p50_ms", "hit_p95_ms",
                    "cold_p95_ms"):
            if isinstance(rec.get(pct), (int, float)):
                metrics[f"style_{pct}_h{h}"] = (float(rec[pct]), "lower")


def _artifact_metrics(path):
    """Extract comparable metrics from a bench artifact: either a driver
    record ({"parsed": {...}, "tail": "..."} as the BENCH_r*.json
    trajectory stores) or raw bench JSON-lines output."""
    with open(path, encoding="utf-8") as fh:
        text = fh.read()
    metrics = {}

    def absorb_lines(blob):
        for ln in (blob or "").splitlines():
            ln = ln.strip()
            if ln.startswith("{"):
                try:
                    _absorb_record(json.loads(ln), metrics)
                except json.JSONDecodeError:
                    continue

    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        absorb_lines(text)
        return metrics
    if isinstance(doc, list):
        for rec in doc:
            _absorb_record(rec, metrics)
    elif isinstance(doc, dict):
        _absorb_record(doc, metrics)
        _absorb_record(doc.get("parsed"), metrics)
        absorb_lines(doc.get("tail"))
    return metrics


def _latest_artifact(exclude):
    """Newest BENCH_r<N>.json next to this file, excluding ``exclude``."""
    import glob
    import re

    here = os.path.dirname(os.path.abspath(__file__))
    best, best_n = None, -1
    for p in glob.glob(os.path.join(here, "BENCH_r*.json")):
        if os.path.abspath(p) == os.path.abspath(exclude):
            continue
        m = re.search(r"BENCH_r(\d+)\.json$", p)
        if m and int(m.group(1)) > best_n:
            best, best_n = p, int(m.group(1))
    return best


def run_compare(old_path, new_path=None, threshold=REGRESSION_THRESHOLD,
                out=sys.stdout):
    """The regression gate over the BENCH_r*.json trajectory: diff every
    comparable metric between two artifacts and exit non-zero when any
    regresses by more than ``threshold`` (default 10%) — throughput
    falling or latency rising. ``new_path`` defaults to the newest
    recorded BENCH_r*.json other than ``old_path``."""
    if new_path is None:
        new_path = _latest_artifact(old_path)
        if new_path is None:
            print("no newer BENCH_r*.json artifact found to compare "
                  f"{old_path} against", file=out)
            return 2
    old = _artifact_metrics(old_path)
    new = _artifact_metrics(new_path)
    # chaos hard gate, independent of the old artifact: the drill's
    # lost-request count must be ZERO — a supervision bug that drops
    # requests is not a 10%-threshold matter
    lost = new.get("chaos_lost_requests")
    if lost is not None and lost[0] > 0:
        print(f"FAIL: chaos drill lost {int(lost[0])} request(s) in "
              f"{os.path.basename(new_path)}; supervision must requeue "
              "or structurally resolve every in-flight request", file=out)
        return 1
    # same zero gate for the traffic storm: flash overload must resolve
    # as shed (429 + Retry-After) or served-late, never as silent loss
    lost = new.get("traffic_lost_requests")
    if lost is not None and lost[0] > 0:
        print(f"FAIL: traffic storm lost {int(lost[0])} request(s) in "
              f"{os.path.basename(new_path)}; every admitted request "
              "must reach a terminal state through flash + chaos + "
              "scale-down", file=out)
        return 1
    # and for the cluster storm: a replica process SIGKILL or a
    # router<->replica partition must resolve every in-flight request
    # through lease expiry -> requeue (exactly-once via idempotency
    # keys) — any loss is a control-plane bug, not a threshold matter
    lost = new.get("cluster_lost_requests")
    if lost is not None and lost[0] > 0:
        print(f"FAIL: cluster storm lost {int(lost[0])} request(s) in "
              f"{os.path.basename(new_path)}; lease expiry must requeue "
              "every in-flight dispatch and idempotency keys must "
              "dedupe hedged retries", file=out)
        return 1
    # and for the live-upgrade drill: a model rollout is zero-downtime
    # by contract — any request lost through the swap fails the diff
    lost = new.get("rollout_lost_requests")
    if lost is not None and lost[0] > 0:
        print(f"FAIL: rollout drill lost {int(lost[0])} request(s) in "
              f"{os.path.basename(new_path)}; the canary-gated roll "
              "must drain-replace without dropping in-flight work",
              file=out)
        return 1
    # and for the tracing drill: observability must be free-ish and
    # safe — spans that slow the fleet beyond 2% on TTFA p50 or lose a
    # request fail outright, independent of the old artifact
    lost = new.get("trace_lost_requests")
    if lost is not None and lost[0] > 0:
        print(f"FAIL: tracing drill lost {int(lost[0])} request(s) in "
              f"{os.path.basename(new_path)}; the trace plane must "
              "never drop work", file=out)
        return 1
    ov = new.get("trace_overhead_over_budget_pct")
    if ov is not None and ov[0] > 0:
        print(f"FAIL: tracing overhead {ov[0] + 2.0:.2f}% on TTFA p50 "
              f"in {os.path.basename(new_path)} exceeds the 2% budget; "
              "span recording must stay off the request hot path",
              file=out)
        return 1
    # quality-plane hard gates: a missed detection means the validators
    # + golden probes let a poisoned tier ship garbage unpaged; a false
    # page means the plane cries wolf on a healthy fleet; both are
    # correctness bits, not percentages
    miss = new.get("quality_missed_detection")
    if miss is not None and miss[0] > 0:
        print(f"FAIL: quality drill missed the injected tier "
              f"degradation in {os.path.basename(new_path)}; the probe "
              "drift edge and the quality burn-rate alert must both "
              "fire within the probe budget", file=out)
        return 1
    fp = new.get("quality_false_pages")
    if fp is not None and fp[0] > 0:
        print(f"FAIL: quality drill paged {int(fp[0])} time(s) on the "
              f"HEALTHY fleet in {os.path.basename(new_path)}; validator "
              "thresholds and probe tolerances must hold quiet on good "
              "audio", file=out)
        return 1
    lost = new.get("quality_lost_requests")
    if lost is not None and lost[0] > 0:
        print(f"FAIL: quality drill lost {int(lost[0])} request(s) in "
              f"{os.path.basename(new_path)}; validators observe and "
              "account — they must never drop work", file=out)
        return 1
    ov = new.get("quality_overhead_over_budget_pct")
    if ov is not None and ov[0] > 0:
        print(f"FAIL: validator overhead {ov[0] + 2.0:.2f}% on TTFA p50 "
              f"in {os.path.basename(new_path)} exceeds the 2% budget; "
              "the quality choke point must stay cheap enough for every "
              "wav", file=out)
        return 1
    # quality hard gate for the tier frontier: any SHIPPED tier whose
    # golden-set mel_l2 exceeds its tolerance is a quality outage, not
    # a 10%-threshold matter — the canary gate exists to keep such a
    # tier out of the routing table, so seeing one in an artifact means
    # the quality door itself failed
    over = [k for k, v in sorted(new.items())
            if k.startswith("tier_")
            and k.endswith("_mel_l2_over_tolerance") and v[0] > 1.0]
    if over:
        print(f"FAIL: shipped tier(s) beyond quality tolerance in "
              f"{os.path.basename(new_path)}: {', '.join(over)}; every "
              "shipped tier's golden-set mel_l2 must hold under its "
              "serve.tiers tolerance", file=out)
        return 1
    common = sorted(set(old) & set(new))
    if not common:
        print(f"no comparable metrics between {old_path} and {new_path} "
              "(both null/failed rounds?)", file=out)
        return 2
    name_w = max(len(k) for k in common)
    print(f"comparing {os.path.basename(old_path)} (old) -> "
          f"{os.path.basename(new_path)} (new), "
          f"threshold {threshold:.0%}", file=out)
    print(f"{'metric':<{name_w}}  {'old':>12}  {'new':>12}  "
          f"{'delta':>8}  verdict", file=out)
    regressions = []
    for key in common:
        old_v, direction = old[key]
        new_v, _ = new[key]
        delta = (new_v - old_v) / old_v if old_v else 0.0
        worse = delta < -threshold if direction == "higher" \
            else delta > threshold
        better = delta > threshold if direction == "higher" \
            else delta < -threshold
        verdict = "REGRESSION" if worse else ("improved" if better else "ok")
        if worse:
            regressions.append(key)
        print(f"{key:<{name_w}}  {old_v:>12.2f}  {new_v:>12.2f}  "
              f"{delta:>+7.1%}  {verdict}", file=out)
    if regressions:
        print(f"FAIL: {len(regressions)} metric(s) regressed >"
              f"{threshold:.0%}: {', '.join(regressions)}", file=out)
        return 1
    print(f"OK: {len(common)} metric(s) within {threshold:.0%}", file=out)
    return 0


def _run_guarded() -> int:
    """Run the measurement in a timeout-guarded child; returns the exit
    code. This parent never touches JAX, so the chip belongs to the child.

    A result line is printed only for a child that exited 0 with one. A
    missing TPU, a crashed child or a timeout prints the diagnostic line
    ({"value": null, "error": ...}, with the child's last breadcrumbs) and
    returns non-zero: a run that measured nothing must not read as a pass.
    """
    here = os.path.dirname(os.path.abspath(__file__))
    err_path = os.path.join(here, ".bench_stderr.log")
    error = None
    # ONE attempt with the whole budget (a cold compile is deterministic:
    # a retry only halves the time each attempt has). Child stderr streams
    # to a file (not a pipe buffer) so a killed child still leaves its
    # breadcrumbs behind.
    with open(err_path, "w") as err_f:
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--inner",
                 "--overrides", json.dumps(TUNED_OVERRIDES)],
                stdout=subprocess.PIPE,
                stderr=err_f,
                text=True,
                timeout=520.0,
                cwd=here,
            )
        except subprocess.TimeoutExpired:
            proc = None
            error = "timeout after 520s"
    breadcrumbs = ""
    try:
        with open(err_path) as f:
            all_lines = f.read().splitlines()
        marks = [ln for ln in all_lines if "[bench +" in ln]
        # keep the exception text too (a crash's traceback tail), not just
        # the stage markers
        other = [ln for ln in all_lines if "[bench +" not in ln and ln.strip()]
        breadcrumbs = " ; ".join(marks[-6:] + other[-4:])
    except OSError:
        pass
    if proc is not None:
        json_line = next(
            (
                ln
                for ln in reversed(proc.stdout.strip().splitlines())
                if ln.startswith("{")
            ),
            None,
        )
        if proc.returncode == 0 and json_line:
            print(json_line)
            return 0
        error = f"rc={proc.returncode}"
    print(
        json.dumps(
            {
                "metric": "train_mel_frames_per_sec",
                "value": None,
                "unit": "mel-frames/sec/chip",
                "vs_baseline": None,
                "error": f"{error} | last breadcrumbs: {breadcrumbs}"[-1500:],
            }
        )
    )
    return 1


if __name__ == "__main__":
    if "--flops" in sys.argv:
        ov = None
        if "--overrides" in sys.argv:
            ov = json.loads(sys.argv[sys.argv.index("--overrides") + 1])
        main(report_flops=True, overrides=ov)
    elif "--breakdown" in sys.argv:
        run_breakdown()
    elif "--infer" in sys.argv:
        run_infer()
    elif "--serve" in sys.argv:
        dur = (float(sys.argv[sys.argv.index("--duration") + 1])
               if "--duration" in sys.argv else 3.0)
        run_serve(duration=dur)
        run_latency(duration=dur)
        run_fleet(duration=dur)
        run_style(duration=dur)
        run_chaos(duration=dur)
        run_traffic(duration=dur)
        run_rollout(duration=dur)
        run_cluster(duration=dur)
        run_mesh_serve(duration=dur)
        run_longform(duration=dur)
        run_tiers(duration=dur)
        run_trace(duration=dur)
        run_quality(duration=dur)
    elif "--tiers" in sys.argv:
        dur = (float(sys.argv[sys.argv.index("--duration") + 1])
               if "--duration" in sys.argv else 3.0)
        run_tiers(duration=dur)
    elif "--rollout" in sys.argv:
        dur = (float(sys.argv[sys.argv.index("--duration") + 1])
               if "--duration" in sys.argv else 3.0)
        run_rollout(duration=dur)
    elif "--traffic" in sys.argv:
        dur = (float(sys.argv[sys.argv.index("--duration") + 1])
               if "--duration" in sys.argv else 4.0)
        run_traffic(duration=dur)
    elif "--latency" in sys.argv:
        dur = (float(sys.argv[sys.argv.index("--duration") + 1])
               if "--duration" in sys.argv else 3.0)
        run_latency(duration=dur)
    elif "--chaos" in sys.argv:
        dur = (float(sys.argv[sys.argv.index("--duration") + 1])
               if "--duration" in sys.argv else 3.0)
        run_chaos(duration=dur)
    elif "--cluster-replica-inner" in sys.argv:
        _cluster_replica_child(
            sys.argv[sys.argv.index("--rid") + 1],
            sys.argv[sys.argv.index("--router") + 1],
            device_ms=(float(sys.argv[sys.argv.index("--device-ms") + 1])
                       if "--device-ms" in sys.argv else 20.0),
        )
    elif "--cluster" in sys.argv:
        dur = (float(sys.argv[sys.argv.index("--duration") + 1])
               if "--duration" in sys.argv else 3.0)
        run_cluster(duration=dur)
    elif "--trace" in sys.argv:
        dur = (float(sys.argv[sys.argv.index("--duration") + 1])
               if "--duration" in sys.argv else 3.0)
        run_trace(duration=dur)
    elif "--quality" in sys.argv:
        dur = (float(sys.argv[sys.argv.index("--duration") + 1])
               if "--duration" in sys.argv else 3.0)
        run_quality(duration=dur)
    elif "--fleet" in sys.argv:
        dur = (float(sys.argv[sys.argv.index("--duration") + 1])
               if "--duration" in sys.argv else 3.0)
        run_fleet(duration=dur)
    elif "--style" in sys.argv:
        dur = (float(sys.argv[sys.argv.index("--duration") + 1])
               if "--duration" in sys.argv else 3.0)
        run_style(duration=dur)
    elif "--ab" in sys.argv:
        run_ab()
    elif "--multichip-inner" in sys.argv:
        _multichip_child(int(sys.argv[sys.argv.index("--n-devices") + 1]))
    elif "--multichip" in sys.argv:
        run_multichip()
    elif "--mesh-serve-inner" in sys.argv:
        i = sys.argv.index("--mesh")
        dur = (float(sys.argv[sys.argv.index("--duration") + 1])
               if "--duration" in sys.argv else 3.0)
        _mesh_serve_child(int(sys.argv[i + 1]), int(sys.argv[i + 2]),
                          duration=dur)
    elif "--mesh-serve" in sys.argv:
        dur = (float(sys.argv[sys.argv.index("--duration") + 1])
               if "--duration" in sys.argv else 3.0)
        run_mesh_serve(duration=dur)
    elif "--longform-inner" in sys.argv:
        dur = (float(sys.argv[sys.argv.index("--duration") + 1])
               if "--duration" in sys.argv else 3.0)
        _longform_child(duration=dur)
    elif "--longform" in sys.argv:
        dur = (float(sys.argv[sys.argv.index("--duration") + 1])
               if "--duration" in sys.argv else 3.0)
        run_longform(duration=dur)
    elif "--compare" in sys.argv:
        i = sys.argv.index("--compare")
        rest = [a for a in sys.argv[i + 1:] if not a.startswith("--")]
        if not rest:
            print("usage: bench.py --compare OLD.json [NEW.json] "
                  "[--threshold 0.10]", file=sys.stderr)
            sys.exit(2)
        thr = (float(sys.argv[sys.argv.index("--threshold") + 1])
               if "--threshold" in sys.argv else REGRESSION_THRESHOLD)
        sys.exit(run_compare(
            rest[0], rest[1] if len(rest) > 1 else None, threshold=thr
        ))
    elif "--inner" in sys.argv:
        ov = None
        if "--overrides" in sys.argv:
            ov = json.loads(sys.argv[sys.argv.index("--overrides") + 1])
        main(profile="--profile" in sys.argv, overrides=ov)
    else:
        sys.exit(_run_guarded())
