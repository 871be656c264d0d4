"""Fault-tolerance suite (tier-1): every recovery path exercised end-to-end
on CPU via deterministic fault injection (training/faults.py).

Layers:
  1. unit — FaultPlan grammar, retry_io backoff, Quarantine budget,
     GracefulShutdown signal plumbing, all_finite, RollbackGuard;
  2. components — DevicePrefetcher shutdown/terminal contract, dataset
     loader retry + batcher quarantine, CheckpointManager async saves,
     retention, and corrupt-directory restore fallback;
  3. end-to-end — run_training drills: NaN rollback (with and without a
     checkpoint to roll back to), consecutive-rollback abort, loader
     IOError retry, SIGTERM flush + gapless ``restore_step=-1`` resume,
     and the final-checkpoint-on-tail-steps guarantee.
"""

import dataclasses
import os
import signal
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from speakingstyle_tpu.configs.config import (
    PathConfig,
    ResilienceConfig,
    StepConfig,
    TrainPathConfig,
    load_config,
)
from speakingstyle_tpu.data import BucketedBatcher, DevicePrefetcher, SpeechDataset
from speakingstyle_tpu.training import faults
from speakingstyle_tpu.training.checkpoint import CheckpointManager
from speakingstyle_tpu.training.faults import FaultPlan
from speakingstyle_tpu.training.resilience import (
    BadSampleBudgetError,
    GracefulShutdown,
    Quarantine,
    RollbackGuard,
    TrainingDivergedError,
    all_finite,
    retry_io,
)
from speakingstyle_tpu.training.trainer import run_training


# ---------------------------------------------------------------------------
# 1. units
# ---------------------------------------------------------------------------


def test_fault_plan_grammar_and_fire_once():
    plan = FaultPlan.parse("loader_ioerror@7; nan_grads@12;sigterm@20")
    assert plan and len(plan.pending()) == 3
    assert not plan.fire("nan_grads", 11)
    assert plan.fire("nan_grads", 12)
    assert not plan.fire("nan_grads", 12)  # exactly once
    assert plan.pending() == [("loader_ioerror", 7), ("sigterm", 20)]
    assert not FaultPlan.parse("")
    # duplicates are distinct entries (poisons the post-rollback replay)
    dup = FaultPlan.parse("nan_grads@3;nan_grads@3")
    assert dup.fire("nan_grads", 3) and dup.fire("nan_grads", 3)
    assert not dup.fire("nan_grads", 3)


@pytest.mark.parametrize("bad", ["nan_grads", "nan_grads@x", "typo@3"])
def test_fault_plan_rejects_bad_specs(bad):
    with pytest.raises(ValueError):
        FaultPlan.parse(bad)


def test_fault_plan_from_env(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "sigterm@5")
    assert FaultPlan.from_env().pending() == [("sigterm", 5)]
    monkeypatch.delenv(faults.ENV_VAR)
    assert not FaultPlan.from_env()


def test_retry_io_recovers_with_exponential_backoff():
    calls, sleeps = [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise IOError("transient")
        return "ok"

    assert retry_io(flaky, retries=3, backoff=0.1, sleep=sleeps.append) == "ok"
    assert len(calls) == 3
    assert sleeps == [0.1, 0.2]  # doubles per attempt


def test_retry_io_final_failure_propagates():
    def always():
        raise IOError("permanent")

    with pytest.raises(IOError, match="permanent"):
        retry_io(always, retries=2, backoff=0.0, sleep=lambda _: None)


def test_quarantine_budget():
    q = Quarantine(budget=2)
    q.add("a", ValueError("x"))
    q.add("b", ValueError("y"))
    assert len(q) == 2 and "a" in q and "c" not in q
    with pytest.raises(BadSampleBudgetError):
        q.add("c", ValueError("z"))


def test_graceful_shutdown_catches_and_restores():
    before = signal.getsignal(signal.SIGTERM)
    with GracefulShutdown() as s:
        assert s.installed and not s.requested
        os.kill(os.getpid(), signal.SIGTERM)
        assert s.requested and s.signame == "SIGTERM"
    assert signal.getsignal(signal.SIGTERM) is before


def test_all_finite_reduction():
    ok = {"a": jnp.ones(3), "ints": jnp.arange(4)}  # int leaves ignored
    assert bool(all_finite(ok))
    assert not bool(all_finite(ok, {"b": jnp.array([1.0, jnp.nan])}))
    assert not bool(all_finite({"b": jnp.array([jnp.inf])}))
    # traceable: usable inside the jitted step
    jitted = jax.jit(lambda t: all_finite(t))
    assert not bool(jitted({"x": jnp.array([jnp.nan])}))
    assert bool(jitted({"x": jnp.array([0.5])}))


def test_rollback_guard_consecutive_semantics():
    g = RollbackGuard(max_rollbacks=2)
    assert g.trip(10) == 1
    g.ok()  # a finite window resets the count
    assert g.trip(20) == 1
    assert g.trip(30) == 2
    with pytest.raises(TrainingDivergedError):
        g.trip(40)


def test_poison_batch_nans_only_mels():
    arrays = {"mels": jnp.ones((2, 4, 3)), "texts": jnp.ones((2, 5), jnp.int32)}
    out = faults.poison_batch(arrays)
    assert not bool(jnp.isfinite(out["mels"]).any())
    assert bool(jnp.all(out["texts"] == 1))
    assert bool(jnp.isfinite(arrays["mels"]).all())  # input untouched


# ---------------------------------------------------------------------------
# 2a. DevicePrefetcher shutdown contract
# ---------------------------------------------------------------------------


class _FakeBatch:
    def arrays(self):
        return {"x": np.zeros((2,), np.float32)}


def _infinite_batches():
    while True:
        yield _FakeBatch()


def test_prefetcher_stop_unblocks_blocked_worker():
    """The old worker deadlock: queue full, consumer gone, stop() drains
    once and the worker re-blocks forever on queue.put. The stop-aware
    bounded put must let stop() terminate the thread."""
    pf = DevicePrefetcher(_infinite_batches(), depth=1)
    next(pf)  # worker is now racing to refill the depth-1 queue
    pf.stop()
    assert not pf.thread.is_alive()
    pf.stop()  # idempotent


def test_prefetcher_single_terminal_item_on_error():
    """The old double-enqueue: an exception pushed BOTH the error and the
    None sentinel. Now the error IS the terminal item."""

    def source():
        yield _FakeBatch()
        raise RuntimeError("loader died")

    pf = DevicePrefetcher(source(), depth=4)
    next(pf)
    with pytest.raises(RuntimeError, match="loader died"):
        next(pf)
    with pytest.raises(StopIteration):
        next(pf)  # terminal: nothing queued behind the error
    pf.thread.join(timeout=5.0)
    assert not pf.thread.is_alive()
    assert pf.queue.empty()


def test_prefetcher_clean_end_and_reuse_of_next():
    pf = DevicePrefetcher(iter([_FakeBatch(), _FakeBatch()]), depth=4)
    assert len(list(pf)) == 2
    with pytest.raises(StopIteration):
        next(pf)


def test_prefetcher_context_manager_stops_thread():
    with DevicePrefetcher(_infinite_batches(), depth=1) as pf:
        next(pf)
    assert not pf.thread.is_alive()


# ---------------------------------------------------------------------------
# 2b. dataset retry + quarantine
# ---------------------------------------------------------------------------


def _data_config(root, batch_size=8):
    cfg = load_config(preset="LJSpeech")
    pp = dataclasses.replace(
        cfg.preprocess, path=PathConfig(preprocessed_path=root)
    )
    opt = dataclasses.replace(cfg.train.optimizer, batch_size=batch_size)
    tr = dataclasses.replace(cfg.train, optimizer=opt)
    return dataclasses.replace(cfg, preprocess=pp, train=tr)


def test_loader_retry_recovers_injected_ioerror(synthetic_preprocessed):
    cfg = _data_config(synthetic_preprocessed)
    plan = FaultPlan.parse("loader_ioerror@3")
    ds = SpeechDataset(
        "train.txt", cfg, retries=2, backoff=0.0, fault_plan=plan
    )
    items = [ds[i] for i in range(2)]  # 8 feature loads; #3 faults once
    assert len(items) == 2 and not plan.pending()


def test_loader_without_retries_propagates(synthetic_preprocessed):
    cfg = _data_config(synthetic_preprocessed)
    ds = SpeechDataset(
        "train.txt", cfg, retries=0,
        fault_plan=FaultPlan.parse("loader_ioerror@2"),
    )
    with pytest.raises(OSError):
        [ds[i] for i in range(2)]


def test_batcher_quarantines_corrupt_sample(synthetic_preprocessed):
    root = synthetic_preprocessed
    # permanently corrupt one sample's mel file (retries can't help)
    with open(os.path.join(root, "mel", "LJSpeech-mel-utt003.npy"), "wb") as f:
        f.write(b"not a numpy file")
    cfg = _data_config(synthetic_preprocessed)
    ds = SpeechDataset("train.txt", cfg)
    q = Quarantine(budget=2)
    batcher = BucketedBatcher(ds, max_src=256, max_mel=256, quarantine=q)
    total = sum(b.n_real for b in batcher.epoch(shuffle=False))
    assert total == 9  # 10 train samples, 1 skipped
    assert len(q) == 1 and "utt003" in q
    # a second epoch skips the known-bad sample without re-loading it,
    # and the nine good ones are held in memory: it loads nothing at all
    loads_before = ds._feature_loads
    assert sum(b.n_real for b in batcher.epoch(shuffle=False)) == 9
    assert ds._feature_loads == loads_before
    # zero budget: the first bad sample fails the run
    b0 = BucketedBatcher(
        ds, max_src=256, max_mel=256, quarantine=Quarantine(budget=0)
    )
    with pytest.raises(BadSampleBudgetError):
        list(b0.epoch(shuffle=False))


def test_batcher_without_quarantine_fails_fast(synthetic_preprocessed):
    root = synthetic_preprocessed
    with open(os.path.join(root, "mel", "LJSpeech-mel-utt001.npy"), "wb") as f:
        f.write(b"garbage")
    cfg = _data_config(synthetic_preprocessed)
    batcher = BucketedBatcher(
        SpeechDataset("train.txt", cfg), max_src=256, max_mel=256
    )
    with pytest.raises(Exception):
        list(batcher.epoch(shuffle=False))


# ---------------------------------------------------------------------------
# 2c. checkpoint manager: async, retention, corrupt-dir fallback
# ---------------------------------------------------------------------------


def _toy_state(value: float):
    return {
        "step": jnp.asarray(int(value), jnp.int32),
        "w": jnp.full((4,), value, jnp.float32),
    }


def test_async_save_does_not_block_the_step_loop(tmp_path):
    """Acceptance: the step counter advances while a save is in flight.
    The Orbax write is gated on an event we control, so 'in flight' is a
    deterministic state, not a race."""
    ckpt = CheckpointManager(str(tmp_path / "ck"), async_save=True)
    gate, started = threading.Event(), threading.Event()
    orig_write = ckpt._write

    def gated_write(step, host_state, val_loss):
        started.set()
        assert gate.wait(timeout=10.0)
        orig_write(step, host_state, val_loss)

    ckpt._write = gated_write
    t0 = time.perf_counter()
    ckpt.save(1, _toy_state(1.0))  # returns without waiting for the write
    assert time.perf_counter() - t0 < 5.0
    assert started.wait(timeout=10.0) and ckpt.save_in_flight()

    # ... the "training loop" keeps stepping while the write is gated
    step_fn = jax.jit(lambda s: s + 1)
    counter = jnp.zeros((), jnp.int32)
    for _ in range(3):
        counter = step_fn(counter)
    assert int(jax.device_get(counter)) == 3
    assert ckpt.save_in_flight()  # still mid-save: the loop never blocked

    gate.set()
    ckpt.wait()
    assert not ckpt.save_in_flight() and ckpt.latest_step() == 1
    ckpt.close()


def test_async_save_error_surfaces_on_wait(tmp_path):
    ckpt = CheckpointManager(str(tmp_path / "ck"), async_save=True)

    def boom(step, host_state, val_loss):
        raise RuntimeError("disk full")

    ckpt._write = boom
    ckpt.save(1, _toy_state(1.0))
    with pytest.raises(RuntimeError, match="disk full"):
        ckpt.wait()
    ckpt.close()


def test_retention_prunes_but_keeps_best(tmp_path):
    ckpt = CheckpointManager(
        str(tmp_path / "ck"), max_to_keep=2, keep_best=True
    )
    val = {1: 0.5, 2: 0.1, 3: 0.9, 4: 0.8, 5: 0.7}  # best at step 2
    for s in range(1, 6):
        ckpt.save(s, _toy_state(float(s)), val_loss=val[s], block=True)
    assert ckpt.all_steps() == [2, 4, 5]  # newest 2 + pinned best
    assert ckpt.best_step() == 2
    restored = ckpt.restore(_toy_state(0.0), step=2)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.full(4, 2.0))
    ckpt.close()


def test_retention_without_keep_best(tmp_path):
    ckpt = CheckpointManager(str(tmp_path / "ck"), max_to_keep=3)
    for s in range(1, 6):
        ckpt.save(s, _toy_state(float(s)), val_loss=float(-s), block=True)
    assert ckpt.all_steps() == [3, 4, 5]
    ckpt.close()


def _corrupt_step_dir(root: str, step: int):
    """Simulate a crash mid-write: gut the step's files, keep the dir."""
    import shutil

    step_dir = None
    for name in os.listdir(root):
        if name == str(step) or name.startswith(f"{step}."):
            step_dir = os.path.join(root, name)
    assert step_dir is not None, os.listdir(root)
    for sub in os.listdir(step_dir):
        p = os.path.join(step_dir, sub)
        shutil.rmtree(p) if os.path.isdir(p) else os.unlink(p)


def test_restore_falls_back_past_corrupt_latest(tmp_path):
    root = str(tmp_path / "ck")
    ckpt = CheckpointManager(root)
    ckpt.save(2, _toy_state(2.0), block=True)
    ckpt.save(4, _toy_state(4.0), block=True)
    ckpt.close()
    _corrupt_step_dir(root, 4)

    ckpt = CheckpointManager(root)
    # latest-step resolution (restore_step=-1) survives the corrupt dir
    restored = ckpt.restore(_toy_state(0.0), step=None)
    assert int(restored["step"]) == 2
    # an explicitly requested corrupt step still fails loudly
    if 4 in ckpt.all_steps():
        with pytest.raises(Exception):
            ckpt.restore(_toy_state(0.0), step=4)
    ckpt.close()


def test_restore_empty_dir_raises(tmp_path):
    ckpt = CheckpointManager(str(tmp_path / "ck"))
    with pytest.raises(FileNotFoundError):
        ckpt.restore(_toy_state(0.0))
    ckpt.close()


# ---------------------------------------------------------------------------
# 3. end-to-end drills through run_training
# ---------------------------------------------------------------------------


def _train_config(root, tmp_path, total=6, save=2, log=1, **res_overrides):
    """Supertiny geometry: compile-bound, so keep one bucket + tiny dims."""
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
    opt = dataclasses.replace(cfg.train.optimizer, batch_size=8)
    steps = StepConfig(
        total_step=total, log_step=log, synth_step=10**9,
        val_step=10**9, save_step=save,
    )
    paths = TrainPathConfig(
        ckpt_path=str(tmp_path / "ckpt"),
        log_path=str(tmp_path / "log"),
        result_path=str(tmp_path / "res"),
    )
    res = ResilienceConfig(**res_overrides)
    tr = dataclasses.replace(
        cfg.train, optimizer=opt, step=steps, path=paths, resilience=res
    )
    return dataclasses.replace(cfg, preprocess=pp, model=mc, train=tr)


def _logged_losses(tmp_path):
    log = (tmp_path / "log" / "log.txt").read_text().splitlines()
    out = {}
    for ln in log:
        if ln.startswith("[train] Step ") and "total_loss:" in ln:
            s = int(ln.split("Step ")[1].split(",")[0])
            out[s] = float(ln.split("total_loss: ")[1].split(",")[0])
    return out


def test_nan_rollback_recovers_and_completes(synthetic_preprocessed, tmp_path,
                                             monkeypatch):
    """Acceptance: nan_grads@k rolls back to the last good checkpoint and
    the run completes with a finite final loss."""
    monkeypatch.setenv(faults.ENV_VAR, "nan_grads@3")
    cfg = _train_config(synthetic_preprocessed, tmp_path, total=6, save=2)
    state = run_training(cfg, max_steps=6)
    assert int(state.step) == 6

    log = (tmp_path / "log" / "log.txt").read_text()
    assert "non-finite losses/grads at step 3" in log
    assert "rollback 1/3 to checkpoint step 2" in log
    losses = _logged_losses(tmp_path)
    # steps resumed 3..6 after the rollback; every logged loss is finite
    assert {3, 4, 5, 6} <= set(losses)
    assert all(np.isfinite(v) for v in losses.values())
    ckpt = CheckpointManager(cfg.train.path.ckpt_path)
    assert ckpt.latest_step() == 6
    ckpt.close()


def test_nan_rollback_without_checkpoint_reinitializes(
    synthetic_preprocessed, tmp_path, monkeypatch
):
    monkeypatch.setenv(faults.ENV_VAR, "nan_grads@1")
    cfg = _train_config(synthetic_preprocessed, tmp_path, total=3, save=100)
    state = run_training(cfg, max_steps=3)
    assert int(state.step) == 3
    log = (tmp_path / "log" / "log.txt").read_text()
    assert "fresh init (no checkpoint yet)" in log
    assert all(np.isfinite(v) for v in _logged_losses(tmp_path).values())


def test_consecutive_rollbacks_abort(synthetic_preprocessed, tmp_path,
                                     monkeypatch):
    """The same poison on every post-rollback replay => diverged run."""
    monkeypatch.setenv(
        faults.ENV_VAR, "nan_grads@3;nan_grads@3;nan_grads@3"
    )
    cfg = _train_config(
        synthetic_preprocessed, tmp_path, total=6, save=2, max_rollbacks=2
    )
    with pytest.raises(TrainingDivergedError):
        run_training(cfg, max_steps=6)


def test_loader_ioerror_drill_completes(synthetic_preprocessed, tmp_path,
                                        monkeypatch):
    """Acceptance: loader_ioerror@k retries/quarantines and completes."""
    monkeypatch.setenv(faults.ENV_VAR, "loader_ioerror@7")
    cfg = _train_config(synthetic_preprocessed, tmp_path, total=4, save=4)
    state = run_training(cfg, max_steps=4)
    assert int(state.step) == 4
    assert all(np.isfinite(v) for v in _logged_losses(tmp_path).values())


def test_sigterm_flush_and_gapless_resume(synthetic_preprocessed, tmp_path,
                                          monkeypatch):
    """Acceptance: a SIGTERM'd run leaves a checkpoint from which
    --restore_step -1 resumes to completion with no step gap."""
    monkeypatch.setenv(faults.ENV_VAR, "sigterm@3")
    cfg = _train_config(synthetic_preprocessed, tmp_path, total=6, save=100)
    state = run_training(cfg, max_steps=6)
    assert int(state.step) == 3  # preempted after step 3...
    ckpt = CheckpointManager(cfg.train.path.ckpt_path)
    assert ckpt.latest_step() == 3  # ...but the flush landed
    ckpt.close()
    log = (tmp_path / "log" / "log.txt").read_text()
    assert "SIGTERM: checkpoint flushed at step 3" in log

    monkeypatch.delenv(faults.ENV_VAR)
    state = run_training(cfg, restore_step=-1, max_steps=6)
    assert int(state.step) == 6
    losses = _logged_losses(tmp_path)
    assert set(losses) == {1, 2, 3, 4, 5, 6}  # no gap, no repeat
    ckpt = CheckpointManager(cfg.train.path.ckpt_path)
    assert ckpt.latest_step() == 6
    ckpt.close()


def test_final_checkpoint_covers_tail_steps(synthetic_preprocessed, tmp_path):
    """total_step not divisible by save_step: the tail must not be lost."""
    cfg = _train_config(synthetic_preprocessed, tmp_path, total=5, save=2)
    state = run_training(cfg, max_steps=5)
    assert int(state.step) == 5
    ckpt = CheckpointManager(cfg.train.path.ckpt_path)
    assert ckpt.latest_step() == 5  # 2, 4 periodic + 5 flushed at loop end
    assert set(ckpt.all_steps()) >= {4, 5}
    ckpt.close()
