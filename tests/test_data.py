"""Data pipeline tests: loading, sort-group collate, bucketing, prefetch."""

import dataclasses

import numpy as np
import pytest

from speakingstyle_tpu.configs.config import PathConfig, load_config
from speakingstyle_tpu.data import (
    BucketedBatcher,
    DevicePrefetcher,
    SpeechDataset,
    TextBatcher,
    bucket_length,
)


def _config(root, batch_size=4):
    cfg = load_config(preset="LJSpeech")
    pp = dataclasses.replace(cfg.preprocess, path=PathConfig(preprocessed_path=root))
    opt = dataclasses.replace(cfg.train.optimizer, batch_size=batch_size)
    tr = dataclasses.replace(cfg.train, optimizer=opt)
    return dataclasses.replace(cfg, preprocess=pp, train=tr)


def test_bucket_length():
    assert bucket_length(1, 32) == 32
    assert bucket_length(32, 32) == 32
    assert bucket_length(33, 32) == 64
    assert bucket_length(999, 128, max_len=1000) == 1000


def test_dataset_items(synthetic_preprocessed):
    ds = SpeechDataset("train.txt", _config(synthetic_preprocessed))
    assert len(ds) == 10
    item = ds[0]
    assert item["mel"].shape[1] == 80
    assert item["duration"].sum() == item["mel"].shape[0]
    assert len(item["pitch"]) == len(item["text"]) == len(item["duration"])
    assert item["text"].dtype == np.int32 and (item["text"] > 0).all()


def test_batcher_static_shapes_and_sort(synthetic_preprocessed):
    cfg = _config(synthetic_preprocessed)
    ds = SpeechDataset("train.txt", cfg, sort=True, drop_last=False)
    batcher = BucketedBatcher(ds, src_bucket=32, mel_bucket=128)
    batches = list(batcher.epoch(shuffle=False))
    assert sum(len(b.ids) for b in batches) == 10
    for b in batches:
        B, L_src = b.texts.shape
        assert L_src % 32 == 0
        assert b.mels.shape[1] % 128 == 0
        assert b.mels.shape[2] == 80
        # sorted descending within each batch
        assert (np.diff(b.src_lens) <= 0).all()
        # durations sum to mel length per item
        for i in range(B):
            assert b.durations[i].sum() == b.mel_lens[i]
            # padding is zero beyond src_len
            assert (b.texts[i, b.src_lens[i]:] == 0).all()


def test_batcher_truncation_keeps_duration_sum(synthetic_preprocessed):
    cfg = _config(synthetic_preprocessed)
    ds = SpeechDataset("train.txt", cfg)
    batcher = BucketedBatcher(ds, src_bucket=16, mel_bucket=32, max_mel=32)
    for b in batcher.epoch(shuffle=False):
        assert b.mels.shape[1] <= 32
        for i in range(len(b.ids)):
            assert b.durations[i].sum() == b.mel_lens[i] <= 32


def test_src_truncation_shrinks_mel_len(synthetic_preprocessed):
    """When max_src drops phonemes, mel_len must shrink to the frames still
    covered so sum(duration) == mel_len holds for every item."""
    cfg = _config(synthetic_preprocessed)
    ds = SpeechDataset("train.txt", cfg)
    batcher = BucketedBatcher(ds, src_bucket=4, mel_bucket=16, max_src=4)
    for b in batcher.epoch(shuffle=False):
        for i in range(len(b.ids)):
            assert b.durations[i].sum() == b.mel_lens[i]
            assert b.src_lens[i] <= 4


def test_infinite_iter_reshuffles(synthetic_preprocessed):
    cfg = _config(synthetic_preprocessed)
    ds = SpeechDataset("train.txt", cfg)
    batcher = BucketedBatcher(ds, seed=7)
    it = iter(batcher)
    seen = [next(it).ids for _ in range(8)]  # > 1 epoch of 3 batches
    assert len(seen) == 8  # stream does not exhaust


def test_device_prefetcher(synthetic_preprocessed):
    cfg = _config(synthetic_preprocessed)
    ds = SpeechDataset("train.txt", cfg)
    batcher = BucketedBatcher(ds)
    pf = DevicePrefetcher(batcher.epoch(shuffle=False), mesh=None)
    batch, arrays = next(pf)
    assert set(arrays) >= {"texts", "mels", "durations"}
    assert arrays["mels"].shape[0] == len(batch.ids)
    pf.stop()


@pytest.fixture
def closed_spans(monkeypatch):
    """Every span the loader closes, in order: a span's fields reach no
    registry (they ride on the profiler's event), so the tests read them
    off the span itself."""
    from speakingstyle_tpu.data import dataset, prefetch
    from speakingstyle_tpu.obs import Span

    seen = []

    class Recording(Span):
        def __exit__(self, *exc):
            seen.append(self)
            return super().__exit__(*exc)

    monkeypatch.setattr(dataset, "Span", Recording)
    monkeypatch.setattr(prefetch, "Span", Recording)
    return seen


def test_loader_read_counts_the_files_read(synthetic_preprocessed,
                                           closed_spans):
    """``loader_read``: one epoch reads four files a sample; the dataset's
    accumulators and the ``loader_fetch`` span's fields say how many files
    and bytes ``np.load`` returned, the counter how long it took."""
    import os

    from speakingstyle_tpu.obs import MetricsRegistry

    cfg = _config(synthetic_preprocessed)
    ds = SpeechDataset("train.txt", cfg)
    reg = MetricsRegistry()
    batches = list(BucketedBatcher(ds, registry=reg).epoch(shuffle=False))
    n = sum(b.n_real for b in batches)
    assert n == len(ds) == 10
    on_disk = 0
    for basename, speaker, _, _ in ds.entries:
        for kind in ("mel", "pitch", "energy", "duration"):
            arr = np.load(os.path.join(synthetic_preprocessed, kind,
                                       f"{speaker}-{kind}-{basename}.npy"))
            on_disk += arr.nbytes
    assert (ds.read_files, ds.read_bytes) == (4 * n, on_disk)
    fetches = [sp for sp in closed_spans if sp.name == "loader_fetch"]
    assert [sp.fields for sp in fetches] == [  # ten samples: one super-batch
        {"samples": n, "files": 4 * n, "bytes": on_disk}]
    fetch = reg.histogram("loader_fetch_seconds")
    assert fetch.count == 1
    assert 0 < reg.value("loader_read_seconds_total") <= fetch.sum
    assert reg.value("loader_read_seconds_total") == pytest.approx(
        ds.read_seconds)
    # what nothing reads is not published
    assert not [k for k in reg.snapshot()["counters"]
                if k.startswith("loader_read_") and "seconds" not in k]
    # one collate span for the sort, one per batch emitted
    collates = [sp for sp in closed_spans if sp.name == "loader_collate"]
    assert reg.histogram("loader_collate_seconds").count == 1 + len(batches)
    assert [sp.fields["real_frames"] for sp in collates[1:]] == [
        int(b.mel_lens.sum()) for b in batches]


@pytest.mark.parametrize("with_mesh", [False, True])
def test_loader_h2d_times_a_transfer_only_where_the_worker_makes_one(
    synthetic_preprocessed, closed_spans, with_mesh
):
    """Without a mesh the worker hands host arrays on and the jitted call
    moves them (``train_dispatch``): no ``loader_h2d`` span, no seconds.
    With one, every batch's ``device_put`` is under a span that carries
    the batch's bytes."""
    import jax

    from speakingstyle_tpu.obs import MetricsRegistry
    from speakingstyle_tpu.parallel.mesh import make_mesh

    cfg = _config(synthetic_preprocessed, batch_size=2)
    ds = SpeechDataset("train.txt", cfg)
    batches = list(BucketedBatcher(ds).epoch(shuffle=False))
    mesh = make_mesh(data=2, model=1, devices=jax.devices()[:2]) \
        if with_mesh else None
    reg = MetricsRegistry()
    with DevicePrefetcher(iter(batches), mesh=mesh, registry=reg) as pf:
        got = list(pf)
    assert len(got) == len(batches) == 5
    h2d = [sp for sp in closed_spans if sp.name == "loader_h2d"]
    if not with_mesh:
        assert not h2d and reg.histogram("loader_h2d_seconds").count == 0
        assert all(isinstance(a, np.ndarray) for a in got[0][1].values())
        return
    assert reg.histogram("loader_h2d_seconds").count == 5
    assert [sp.fields["bytes"] for sp in h2d] == [
        sum(a.nbytes for a in b.arrays().values()) for b in batches]
    assert all(isinstance(a, jax.Array) for a in got[0][1].values())


def _slow(items, seconds):
    import time

    for it in items:
        time.sleep(seconds)
        yield it


def test_loader_blocked_tells_a_slow_consumer_from_a_slow_producer(
    synthetic_preprocessed
):
    """``loader_blocked`` is the worker waiting on a full queue: a consumer
    slower than the loader accumulates it, a loader slower than the
    consumer does not (there the consumer waits, ``train_data_wait``)."""
    import time

    from speakingstyle_tpu.obs import MetricsRegistry

    cfg = _config(synthetic_preprocessed, batch_size=2)
    ds = SpeechDataset("train.txt", cfg)
    batches = list(BucketedBatcher(ds).epoch(shuffle=False))
    assert len(batches) == 5

    slow_consumer = MetricsRegistry()
    with DevicePrefetcher(iter(batches), depth=1,
                          registry=slow_consumer) as pf:
        for _ in pf:
            time.sleep(0.05)
    blocked = slow_consumer.histogram("loader_blocked_seconds")
    assert blocked.count >= 2 and blocked.sum > 0.1

    slow_producer = MetricsRegistry()
    with DevicePrefetcher(_slow(batches, 0.05), depth=1,
                          registry=slow_producer) as pf:
        assert len(list(pf)) == 5
    # (the end-of-stream marker may find the last batch still queued)
    assert slow_producer.histogram("loader_blocked_seconds").sum < 0.02


def test_text_batcher(synthetic_preprocessed, tmp_path):
    cfg = _config(synthetic_preprocessed)
    src = tmp_path / "source.txt"
    src.write_text("utt000|LJSpeech|{AH0 K T}|hello\n")
    tb = TextBatcher(str(src), cfg)
    item = tb[0]
    assert item["text"].shape == (3,)
    assert item["mel"] is not None  # found the preprocessed mel for style
