"""Multichip training (ISSUE 10), on the 8-virtual-device CPU mesh.

Covers the config->mesh resolution layer (train.parallel.*), the
structured batch-divisibility gate, cross-mesh-shape checkpoint resume
(save on mesh A, restore onto mesh B, bit-identically), the shard-local
nan_grads drill against the dp-reduced NaN sentinel, and per-device
observability gauges during a mesh train smoke.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from speakingstyle_tpu.configs.config import (
    ParallelConfig,
    PathConfig,
    StepConfig,
    TrainPathConfig,
    load_config,
)
from speakingstyle_tpu.parallel import (
    BatchShardingError,
    local_batch_size,
    make_mesh,
    resolve_mesh,
)
from speakingstyle_tpu.parallel.partition import (
    parse_rule_overrides,
    train_state_shardings,
)
from speakingstyle_tpu.training import CheckpointManager, TrainState, run_training
from speakingstyle_tpu.training import faults


# ---------------------------------------------------------------------------
# 1. config -> mesh resolution (train.parallel.*)
# ---------------------------------------------------------------------------


def test_parallel_config_validation():
    ParallelConfig(mesh=[4, 2], seq=1)  # valid
    with pytest.raises(ValueError):
        ParallelConfig(mesh=[8])  # must be [dp, tp]
    with pytest.raises(ValueError):
        ParallelConfig(mesh=[4, 0])  # tp >= 1
    with pytest.raises(ValueError):
        ParallelConfig(mesh=[-2, 1])  # dp >= 1 or -1
    with pytest.raises(ValueError):
        ParallelConfig(partition_rules=[["kernel", "none,ring"]])  # bad axis
    with pytest.raises(ValueError):
        ParallelConfig(partition_rules=[["(unclosed", "none,model"]])


def test_resolve_mesh_single_chip_is_none():
    # [1,1] must leave the single-chip path byte-for-byte intact
    assert resolve_mesh(ParallelConfig()) is None
    assert resolve_mesh(ParallelConfig(mesh=[1, 1])) is None


def test_resolve_mesh_shapes():
    mesh = resolve_mesh(ParallelConfig(mesh=[8, 1]))
    assert mesh.shape["data"] == 8 and mesh.shape["model"] == 1
    # dp=-1: all remaining devices after tp
    mesh = resolve_mesh(ParallelConfig(mesh=[-1, 2]))
    assert mesh.shape["data"] == 4 and mesh.shape["model"] == 2


def test_resolve_mesh_too_many_devices_names_the_fix():
    with pytest.raises(ValueError, match="xla_force_host_platform_device_count"):
        resolve_mesh(ParallelConfig(mesh=[16, 1]))


def test_local_batch_size_structured_error():
    with pytest.raises(BatchShardingError) as exc:
        local_batch_size(12, make_mesh())  # 12 over dp=8
    msg = str(exc.value)
    assert "12" in msg and "dp=8" in msg and "8x1" in msg
    assert "8 or 16" in msg  # the two nearest valid batch sizes


def test_parse_rule_overrides_prepend():
    rules = parse_rule_overrides([["foo/kernel", "none,model"]])
    pat, spec = rules[0]
    assert pat == "foo/kernel" and spec == P(None, "model")


# ---------------------------------------------------------------------------
# 2. cross-mesh-shape resume: save on A, restore onto B, bit-identical
# ---------------------------------------------------------------------------

# (dp, tp); None = the production 1x1 path (no mesh at all)
_MESHES = {"1x1": None, "8x1": (8, 1), "4x2": (4, 2)}
# the toy kernel is named to match this TP override rule (rules are
# re.match-anchored full-path regexes over the flattened param paths)
_TP_RULES = [["dense/kernel", "none,model"]]


def _mk_mesh(spec):
    if spec is None:
        return None
    dp, tp = spec
    return make_mesh(data=dp, model=tp, devices=jax.devices()[: dp * tp])


def _toy_state(tx):
    variables = {
        "params": {
            "dense": {
                "kernel": jnp.arange(128, dtype=jnp.float32).reshape(8, 16),
                "bias": jnp.linspace(0.0, 1.0, 16, dtype=jnp.float32),
            }
        },
        "batch_stats": {},
    }
    return TrainState.create(variables, tx)


def _lay_out(state, mesh):
    """The trainer's layout rule: TP shardings when the model axis is >1,
    replicated on a pure-DP mesh, plain host/single-device state at 1x1."""
    if mesh is None:
        return state, None
    if mesh.shape["model"] > 1:
        sh = train_state_shardings(state, mesh, parse_rule_overrides(_TP_RULES))
        return jax.tree_util.tree_map(jax.device_put, state, sh), sh
    return jax.device_put(state, NamedSharding(mesh, P())), None


def _advance(state, tx):
    """One optimizer step with unit grads (makes opt_state non-trivial)."""
    grads = jax.tree_util.tree_map(jnp.ones_like, state.params)
    updates, new_opt = tx.update(grads, state.opt_state, state.params)
    params = optax.apply_updates(state.params, updates)
    return state.replace(
        step=state.step + 1, params=params, opt_state=new_opt
    )


@pytest.mark.parametrize(
    "src,dst",
    [("8x1", "4x2"), ("8x1", "1x1"), ("4x2", "8x1"), ("1x1", "4x2")],
)
def test_cross_mesh_resume_bit_identical(tmp_path, src, dst):
    tx = optax.adam(1e-3)
    state, _ = _lay_out(_toy_state(tx), _mk_mesh(_MESHES[src]))
    state = _advance(state, tx)  # adam moments become non-trivial
    ckpt = CheckpointManager(str(tmp_path / "ck"))
    ckpt.save(1, state, block=True)

    mesh_b = _mk_mesh(_MESHES[dst])
    target, _ = _lay_out(_toy_state(tx), mesh_b)
    restored = ckpt.restore(target, step=1)
    ckpt.close()

    # every leaf — params AND optimizer state — survives bit-identically
    want = jax.tree_util.tree_leaves(jax.device_get(state))
    got = jax.tree_util.tree_leaves(jax.device_get(restored))
    assert len(want) == len(got)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))

    # the restored state landed in the TARGET layout, not the source's
    if mesh_b is not None and mesh_b.shape["model"] > 1:
        spec = restored.params["dense"]["kernel"].sharding.spec
        assert "model" in str(spec), spec

    # ... and one optimizer step runs in that layout
    stepped = jax.jit(lambda s: _advance(s, tx))(restored)
    assert int(stepped.step) == 2
    assert np.isfinite(np.asarray(jax.device_get(
        stepped.params["dense"]["kernel"]))).all()


def test_restore_via_sharded_abstract(tmp_path):
    """The no-materialization spelling: restore against
    TrainState.sharded_abstract over the target mesh's shardings."""
    tx = optax.adam(1e-3)
    state, _ = _lay_out(_toy_state(tx), _mk_mesh(_MESHES["8x1"]))
    state = _advance(state, tx)
    ckpt = CheckpointManager(str(tmp_path / "ck"))
    ckpt.save(1, state, block=True)

    mesh_b = _mk_mesh(_MESHES["4x2"])
    template = _toy_state(tx)
    sh = train_state_shardings(
        template, mesh_b, parse_rule_overrides(_TP_RULES)
    )
    restored = ckpt.restore(template.sharded_abstract(sh), step=1)
    ckpt.close()
    assert "model" in str(restored.params["dense"]["kernel"].sharding.spec)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(restored.params["dense"]["kernel"])),
        np.asarray(jax.device_get(state.params["dense"]["kernel"])),
    )


# ---------------------------------------------------------------------------
# 3. the shard-local nan_grads drill against the dp-reduced sentinel
# ---------------------------------------------------------------------------


def test_dp_poison_rows_arithmetic():
    assert faults.dp_poison_rows(8, 8) == 1  # one shard's rows
    assert faults.dp_poison_rows(8, 1) == 8  # no mesh: whole batch
    assert faults.dp_poison_rows(16, 4) == 4
    assert faults.dp_poison_rows(4, 8) == 4  # degenerate: keep full batch


def test_shard_local_poison_trips_flag_on_every_device():
    """Inject NaN on ONE dp shard; the all-reduced ``_finite`` flag must
    read False — replicated — on all 8 devices."""
    from tests.test_parallel import _tiny_batch, _tiny_cfg

    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.training import make_optimizer, make_train_step

    mesh = make_mesh()  # 8x1 pure DP
    cfg = _tiny_cfg()
    model = build_model(cfg)
    variables = init_variables(model, cfg, jax.random.PRNGKey(0))
    tx = make_optimizer(cfg.train)
    state = jax.device_put(
        TrainState.create(variables, tx), NamedSharding(mesh, P())
    )
    step = make_train_step(model, tx, cfg, mesh=mesh)

    batch = _tiny_batch(mesh)  # B=8 over dp=8: one row per shard
    poisoned = faults.poison_batch(batch, mesh=mesh)
    # the poison is shard-local: row 0 only, sharding preserved
    host_mels = np.asarray(jax.device_get(poisoned["mels"]))
    assert np.isnan(host_mels[0]).any()
    assert np.isfinite(host_mels[1:]).all()
    assert poisoned["mels"].sharding == batch["mels"].sharding

    # control first (the step donates its input state): clean flag is True
    state, clean_losses = step(state, batch, jax.random.PRNGKey(1))
    assert bool(clean_losses["_finite"])

    _, losses = step(state, poisoned, jax.random.PRNGKey(1))
    flag = losses["_finite"]
    assert not bool(flag)
    assert flag.sharding.is_fully_replicated
    # identical verdict on EVERY device, not just the poisoned shard's
    shard_vals = [bool(s.data) for s in flag.addressable_shards]
    assert shard_vals == [False] * 8


# ---------------------------------------------------------------------------
# 4. run_training on the config mesh: rollback drill + per-device gauges
# ---------------------------------------------------------------------------


def _mesh_train_config(root, tmp_path, mesh=(8, 1), batch_size=8):
    cfg = load_config(preset="LJSpeech")
    tf = dataclasses.replace(
        cfg.model.transformer,
        encoder_layer=1, decoder_layer=1, encoder_hidden=16,
        decoder_hidden=16, encoder_head=2, decoder_head=2,
        conv_filter_size=32,
    )
    ref = dataclasses.replace(
        cfg.model.reference_encoder,
        encoder_layer=1, encoder_hidden=16, conv_layer=1,
        conv_filter_size=32, encoder_head=2,
    )
    vp = dataclasses.replace(cfg.model.variance_predictor, filter_size=16)
    mc = dataclasses.replace(
        cfg.model, transformer=tf, reference_encoder=ref,
        variance_predictor=vp, max_seq_len=128, compute_dtype="float32",
    )
    pp = dataclasses.replace(
        cfg.preprocess, path=PathConfig(preprocessed_path=root)
    )
    opt = dataclasses.replace(cfg.train.optimizer, batch_size=batch_size)
    steps = StepConfig(
        total_step=6, log_step=1, synth_step=10**9, val_step=10**9,
        save_step=2,
    )
    paths = TrainPathConfig(
        ckpt_path=str(tmp_path / "ckpt"),
        log_path=str(tmp_path / "log"),
        result_path=str(tmp_path / "res"),
    )
    tr = dataclasses.replace(
        cfg.train, optimizer=opt, step=steps, path=paths,
        parallel=ParallelConfig(mesh=list(mesh)),
    )
    return dataclasses.replace(cfg, preprocess=pp, model=mc, train=tr)


def test_run_training_rejects_indivisible_batch(synthetic_preprocessed,
                                                tmp_path):
    """The startup gate: batch 10 over dp=8 is a structured config error
    (named batch, mesh shape, nearest valid sizes), not a shard crash."""
    cfg = _mesh_train_config(
        synthetic_preprocessed, tmp_path, mesh=(8, 1), batch_size=10
    )
    with pytest.raises(BatchShardingError, match="8 or 16"):
        run_training(cfg, max_steps=1)


def test_mesh_train_smoke_nan_rollback_and_per_device_gauges(
    synthetic_preprocessed, tmp_path, monkeypatch
):
    """One drill, three acceptance criteria: run_training resolves the
    8x1 mesh from train.parallel alone; the shard-local nan_grads fault
    trips the dp-reduced sentinel into the same rollback as single-chip;
    and the per-device memory gauges land in the registry snapshot, the
    loop's spans in every train_step event."""
    from speakingstyle_tpu.obs import get_registry

    monkeypatch.setenv(faults.ENV_VAR, "nan_grads@3")
    cfg = _mesh_train_config(synthetic_preprocessed, tmp_path, mesh=(8, 1))
    state = run_training(cfg, max_steps=6)  # mesh comes from the config
    assert int(state.step) == 6

    log = (tmp_path / "log" / "log.txt").read_text()
    assert "non-finite losses/grads at step 3" in log
    assert "rollback 1/3 to checkpoint step 2" in log

    from speakingstyle_tpu.obs import read_events
    from tests.test_obs import WINDOW_FIELDS

    steps = list(read_events(str(tmp_path / "log"), event="train_step"))
    assert [e["step"] for e in steps][-1] == 6 and len(steps) >= 6
    for rec in steps:
        assert all(rec[f] >= 0 for f in WINDOW_FIELDS), rec
        assert (rec["data_wait_s"] + rec["dispatch_s"] + rec["sync_s"]
                + rec["log_s"]) <= 1.0 / rec["steps_per_sec"]
    # under a mesh the prefetch worker makes the transfer, and times it
    assert sum(rec["loader_h2d_s"] for rec in steps) > 0
    (start,) = read_events(str(tmp_path / "log"), event="train_start")
    assert start["setup_s"]["restore"] >= 0 and start["setup_s"]["total"] > 0

    snap = get_registry().snapshot()["gauges"]
    mem = [k for k in snap
           if k.startswith('device_memory_watermark_bytes{device="cpu:')]
    assert len(mem) == 8 and all(snap[k] > 0 for k in mem), sorted(snap)
