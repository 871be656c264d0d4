"""ProgramRegistry + replicas that ARE mesh slices.

Four layers, mirroring ISSUE 14's acceptance bar:
  1. registry cache-key semantics — (name, shape signature, donation,
     shardings) dedupes; a repeat request returns the SAME Compiled
     without recompiling;
  2. cross-mesh serve parity — ONE set of weights behind a 1x1, 1x2,
     and 2x2 replica serves a single request BIT-identically (every
     single-request dispatch replicates per dispatch_sharding's
     divisibility rule), and a data-sharded coalesced batch agrees to
     float32 ULP;
  3. zero steady-state compiles on a MESH replica, measured on the
     backend monitoring bus (JL008's invariant, now on sharded AOT
     programs), with /debug/programs-shaped card rows recording the
     mesh geometry and sharding specs;
  4. fleet e2e — a 1x1 and a 1x2 replica behind ONE router: the router
     only sees the engine interface, so mesh slices drop in unchanged.

conftest.py forces 8 virtual CPU devices
(``--xla_force_host_platform_device_count=8``), so every geometry here
fits on the CPU proxy.
"""

import dataclasses

import numpy as np
import pytest

from speakingstyle_tpu.configs.config import (
    Config,
    ModelConfig,
    ParallelConfig,
    ReferenceEncoderConfig,
    ServeConfig,
    StyleConfig,
    TransformerConfig,
    VarianceEmbeddingConfig,
    VariancePredictorConfig,
)
from speakingstyle_tpu.obs import MetricsRegistry
from speakingstyle_tpu.serving.engine import CompileMonitor, SynthesisRequest

# ---------------------------------------------------------------------------
# registry cache-key semantics (tiny programs, no model)
# ---------------------------------------------------------------------------


def test_registry_cache_key_dedupes_and_rebuilds():
    import jax
    import jax.numpy as jnp

    from speakingstyle_tpu.parallel import ProgramRegistry

    registry = ProgramRegistry()

    def f(x):
        return x * 2.0

    a4 = (jax.ShapeDtypeStruct((4,), jnp.float32),)
    a8 = (jax.ShapeDtypeStruct((8,), jnp.float32),)

    e1 = registry.compile(f, a4, name="double")
    assert registry.compile_count == 1 and len(registry) == 1
    # identical (name, signature, donation, shardings) -> the SAME
    # Compiled object, no recompile
    assert registry.compile(f, a4, name="double") is e1
    assert registry.compile_count == 1
    # a different shape bucket is a different program
    e2 = registry.compile(f, a8, name="double")
    assert e2 is not e1 and registry.compile_count == 2
    # donation participates in the key
    e3 = registry.compile(f, a4, name="double", donate_argnums=(0,))
    assert e3 is not e1 and registry.compile_count == 3
    # get() resolves the latest program under a name; the card table has
    # one JSON-ready row per program in compile order
    assert registry.get("double") is e3
    rows = registry.programs()
    assert [r["name"] for r in rows] == ["double"] * 3
    assert all("flops" in r and "donate_argnums" in r for r in rows)
    assert rows[2]["donate_argnums"] == [0]
    # single-device programs record no mesh
    assert rows[0]["mesh"] is None and rows[0]["in_shardings"] is None


def test_registry_sharding_specs_are_part_of_the_key():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from speakingstyle_tpu.parallel import ProgramRegistry, make_mesh

    registry = ProgramRegistry()
    mesh = make_mesh(data=2, model=1, devices=jax.devices()[:2])
    bsh = NamedSharding(mesh, P("data"))

    def f(x):
        return x + 1.0

    a4 = (jax.ShapeDtypeStruct((4,), jnp.float32),)
    plain = registry.compile(f, a4, name="inc")
    sharded = registry.compile(
        f, a4, name="inc", in_shardings=(bsh,), out_shardings=bsh
    )
    assert sharded is not plain and registry.compile_count == 2
    # and the repeat sharded request still dedupes
    assert registry.compile(
        f, a4, name="inc", in_shardings=(bsh,), out_shardings=bsh
    ) is sharded
    assert registry.compile_count == 2
    row = registry.programs()[-1]
    assert row["mesh"] == "2x1"
    assert "data" in row["in_shardings"]


def test_registry_counter_lands_in_shared_metrics():
    import jax
    import jax.numpy as jnp

    from speakingstyle_tpu.parallel import ProgramRegistry

    metrics = MetricsRegistry()
    registry = ProgramRegistry(
        metrics, counter_name="serve_compiles_total", prefix="serve"
    )
    registry.compile(
        lambda x: x, (jax.ShapeDtypeStruct((2,), jnp.float32),), name="id"
    )
    assert metrics.value("serve_compiles_total") == 1
    # the card table is queryable by name for the debug endpoints
    assert registry.card("id") is not None


def _cache_entries(path):
    import os

    return [f for f in os.listdir(path) if f.endswith("-cache")]


def test_registry_cache_lands_where_the_env_var_points(tmp_path):
    # tests/conftest.py points JAX_COMPILATION_CACHE_DIR at a per-session
    # directory: every registry reports it, writes there, and ignores the
    # train.obs.compilation_cache_dir override while the variable is set
    import os

    import jax
    import jax.numpy as jnp

    from speakingstyle_tpu.obs.jaxmon import CACHE_DIR_ENV
    from speakingstyle_tpu.parallel import ProgramRegistry

    env_dir = os.environ[CACHE_DIR_ENV]
    registry = ProgramRegistry(cache_dir=str(tmp_path / "ignored"))
    assert registry.cache_dir == env_dir
    before = set(_cache_entries(env_dir))
    registry.compile(
        lambda x: x * 5.0 + 0.125,
        (jax.ShapeDtypeStruct((6,), jnp.float32),),
        name="env_placed",
    )
    assert set(_cache_entries(env_dir)) - before
    assert not (tmp_path / "ignored").exists()


def test_registry_cache_override_applies_late_when_env_unset(
        tmp_path, monkeypatch):
    # jax builds its cache object on the first compile and keeps it. A
    # serve process compiles during checkpoint restore, before the
    # engine's registry (and its config override) exists — the override
    # must still take, or writes keep landing in the old directory while
    # the request counters tick.
    import jax
    import jax.numpy as jnp
    from jax._src import compilation_cache

    from speakingstyle_tpu.obs.jaxmon import CACHE_DIR_ENV
    from speakingstyle_tpu.parallel import ProgramRegistry

    cache_dir = tmp_path / "cc"
    prev_dir = jax.config.jax_compilation_cache_dir
    # the cache object is now built on the session directory
    jax.jit(lambda x: x + 1.0)(jnp.zeros((2,), jnp.float32))
    monkeypatch.delenv(CACHE_DIR_ENV)
    try:
        registry = ProgramRegistry(cache_dir=str(cache_dir))
        assert registry.cache_dir == str(cache_dir)
        registry.compile(
            lambda x: x * 3.0,
            (jax.ShapeDtypeStruct((4,), jnp.float32),),
            name="late",
        )
        assert _cache_entries(cache_dir), (
            "registry compile never reached the overridden cache"
        )
    finally:
        # leave the process-global cache the way we found it
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        compilation_cache.reset_cache()


# ---------------------------------------------------------------------------
# mesh-slice replicas (tiny model, real jax over virtual devices)
# ---------------------------------------------------------------------------


def _tiny_cfg(mesh=(1, 1)):
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
            parallel=ParallelConfig(mesh=list(mesh)),
        ),
    )


@pytest.fixture(scope="module")
def tiny_parts():
    """Model/weights/vocoder built ONCE — the 'one checkpoint' every
    mesh geometry below consumes unchanged."""
    import jax

    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.models.hifigan import Generator

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
    return model, variables, gen, gparams


def _engine_for(mesh, parts, registry=None):
    from speakingstyle_tpu.serving.engine import SynthesisEngine

    model, variables, gen, gparams = parts
    engine = SynthesisEngine(
        _tiny_cfg(mesh), variables, vocoder=(gen, gparams), model=model,
        registry=registry,
    )
    engine.precompile()
    return engine


@pytest.fixture(scope="module")
def engine_1x1(tiny_parts):
    return _engine_for((1, 1), tiny_parts)


@pytest.fixture(scope="module")
def engine_1x2(tiny_parts):
    return _engine_for((1, 2), tiny_parts)


@pytest.fixture(scope="module")
def engine_2x2(tiny_parts):
    return _engine_for((2, 2), tiny_parts)


def _mkreq(i, L=10, T=20):
    rng = np.random.default_rng(i)
    return SynthesisRequest(
        id=f"utt{i}",
        sequence=rng.integers(1, 300, L).astype(np.int32),
        ref_mel=rng.standard_normal((T, 80)).astype(np.float32),
    )


def test_single_request_bit_parity_across_geometries(
        engine_1x1, engine_1x2, engine_2x2):
    """THE portability contract: the same checkpoint behind a 1x2 or
    2x2 replica serves a single request bit-identically to the 1x1
    engine — a b=1 dispatch never divides by dp, so dispatch_sharding
    replicates it and every device runs the identical program."""
    base = engine_1x1.run([_mkreq(0)])[0]
    assert base.mel_len > 0 and base.wav is not None
    for engine in (engine_1x2, engine_2x2):
        res = engine.run([_mkreq(0)])[0]
        assert res.mel_len == base.mel_len
        np.testing.assert_array_equal(res.durations, base.durations)
        np.testing.assert_array_equal(res.mel, base.mel)
        np.testing.assert_array_equal(res.wav, base.wav)


def test_dp1_slice_is_bitwise_even_for_coalesced_batches(
        engine_1x1, engine_1x2):
    """On a dp=1 slice (mesh [1, 2]) NO bucket data-shards, so even the
    b=2 coalesced dispatch is bitwise equal to 1x1."""
    base = engine_1x1.run([_mkreq(1), _mkreq(2)])
    res = engine_1x2.run([_mkreq(1), _mkreq(2)])
    for rb, rr in zip(base, res):
        np.testing.assert_array_equal(rr.mel, rb.mel)
        np.testing.assert_array_equal(rr.wav, rb.wav)


def test_data_sharded_batch_agrees_to_float32_ulp(engine_1x1, engine_2x2):
    """A coalesced b=2 dispatch on dp=2 data-shards (1 row per shard):
    XLA generates a different program for the shard shape, so outputs
    agree to float32 ULP, not bitwise — the same numerics trade DP
    training makes. Durations survive bitwise (argmax-free rounding of
    ULP-close values at these magnitudes)."""
    base = engine_1x1.run([_mkreq(1), _mkreq(2)])
    res = engine_2x2.run([_mkreq(1), _mkreq(2)])
    for rb, rr in zip(base, res):
        assert rr.mel_len == rb.mel_len
        np.testing.assert_array_equal(rr.durations, rb.durations)
        np.testing.assert_allclose(rr.mel, rb.mel, rtol=0, atol=1e-4)
        assert int(np.abs(
            rr.wav.astype(np.int32) - rb.wav.astype(np.int32)
        ).max()) <= 2  # int16 rounding of ULP-close floats


def test_mesh_replica_zero_steady_state_compiles(engine_2x2):
    """JL008's acceptance invariant on a MESH replica: after per-bucket
    warmup the monitoring bus sees ZERO compiles — every sharded AOT
    program came out of precompile, and dispatch_sharding routes each
    batch onto exactly the sharding its program was built for."""
    engine = engine_2x2
    assert engine.mesh is not None and engine.compile_count == 4
    for b in engine.lattice.batch_buckets:
        engine.run([_mkreq(700 + b * 10 + j) for j in range(b)])
    with CompileMonitor() as mon:
        engine.run([_mkreq(10)])                 # replicated b=1
        engine.run([_mkreq(11), _mkreq(12)])     # data-sharded b=2
        engine.run([_mkreq(13)])
    assert mon.count == 0, "the mesh replica compiled after warmup"
    assert engine.compile_count == 4


def test_mesh_replica_cards_record_shardings(engine_2x2):
    """The /debug/programs payload: registry card rows carry the mesh
    geometry and in/out sharding specs of every compiled program."""
    rows = engine_2x2.programs()
    assert len(rows) == engine_2x2.compile_count == 4
    assert all(r["mesh"] == "2x2" for r in rows)
    acoustic_b2 = [r for r in rows if r["name"] == "acoustic:b2.s16.m32"]
    assert len(acoustic_b2) == 1
    # b=2 divides dp=2 -> batch axis over 'data'; weights replicated
    assert "data" in acoustic_b2[0]["in_shardings"]
    assert "data" in acoustic_b2[0]["out_shardings"]
    # b=1 does not divide dp=2 -> fully replicated program
    acoustic_b1 = [r for r in rows if r["name"] == "acoustic:b1.s16.m32"]
    assert "data" not in acoustic_b1[0]["out_shardings"]


def test_fleet_mixed_mesh_replicas_behind_one_router(tiny_parts):
    """A 1x1 replica and a 1x2 mesh-slice replica behind ONE router:
    FleetRouter only touches the engine interface, so a replica being a
    mesh slice is invisible to routing, and steady state stays at zero
    compiles fleet-wide."""
    from speakingstyle_tpu.serving.engine import SynthesisEngine
    from speakingstyle_tpu.serving.fleet import FleetRouter

    model, variables, gen, gparams = tiny_parts
    reg = MetricsRegistry()

    def factory_for(mesh):
        cfg = _tiny_cfg(mesh)

        def factory(registry):
            return SynthesisEngine(cfg, variables, vocoder=(gen, gparams),
                                   model=model, registry=registry)
        return factory

    with FleetRouter(factory_for((1, 1)), _tiny_cfg(), replicas=1,
                     registry=reg) as router:
        assert router.wait_ready(timeout=300, n=1)
        router.start_replica(factory=factory_for((1, 2)))
        assert router.wait_ready(timeout=300, n=2)
        engines = router.engines()
        assert len(engines) == 2
        assert engines[0].mesh is None
        assert engines[1].mesh is not None
        for engine in engines:
            for b in engine.lattice.batch_buckets:
                engine.run([_mkreq(800 + b * 10 + j) for j in range(b)])
        total_before = reg.value("serve_compiles_total")
        with CompileMonitor() as mon:
            futs = [router.submit(_mkreq(i)) for i in range(8)]
            results = [f.result(timeout=120) for f in futs]
        assert mon.count == 0, "the mixed-mesh fleet compiled after warmup"
        assert reg.value("serve_compiles_total") == total_before
        for i, r in enumerate(results):
            assert r.id == f"utt{i}"
            assert r.wav is not None and r.wav.dtype == np.int16
        # the fleet served every request
        snap = reg.snapshot()["counters"]
        served = [v for k, v in snap.items()
                  if k.startswith("serve_replica_requests_total")]
        assert sum(served) >= 8
        # and the two replicas agree bitwise on the same request
        r11 = engines[0].run([_mkreq(99)])[0]
        r12 = engines[1].run([_mkreq(99)])[0]
        np.testing.assert_array_equal(r11.wav, r12.wav)
