"""Data pipeline tests: loading, sort-group collate, bucketing, prefetch."""

import dataclasses

import numpy as np
import pytest

from speakingstyle_tpu.configs.config import PathConfig, load_config
from speakingstyle_tpu.data import (
    BucketedBatcher,
    CacheBudget,
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
        {"samples": n, "hits": 0, "files": 4 * n, "bytes": on_disk}]
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


# ---------------------------------------------------------------------------
# the sample cache: finished samples kept in host memory after a first read
# ---------------------------------------------------------------------------


def _same_batches(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert (x.n_real, x.ids, x.raw_texts) == (y.n_real, y.ids, y.raw_texts)
        for k, v in x.arrays().items():
            w = y.arrays()[k]
            assert v.dtype == w.dtype and np.array_equal(v, w), k


def _sample_bytes(root, n=None):
    """Bytes of each finished train sample, by a dataset that keeps none."""
    ds = SpeechDataset("train.txt", _config(root), cache=CacheBudget(0))
    return [sum(v.nbytes for v in ds[i].values() if isinstance(v, np.ndarray))
            for i in range(len(ds) if n is None else n)]


def test_second_epoch_reads_no_file_and_yields_the_same_batches(
    synthetic_preprocessed, closed_spans
):
    """Everything fits: the first epoch reads four files a sample and keeps
    each finished sample, the second opens none and is bit-identical."""
    from speakingstyle_tpu.obs import MetricsRegistry

    ds = SpeechDataset("train.txt", _config(synthetic_preprocessed))
    reg = MetricsRegistry()
    batcher = BucketedBatcher(ds, registry=reg)
    first = list(batcher.epoch(shuffle=False))
    n = len(ds)
    files, nbytes = ds.read_files, ds.read_bytes
    assert (files, ds.cache_hits, ds.cache_misses) == (4 * n, 0, n)
    assert ds.cache.held == sum(_sample_bytes(synthetic_preprocessed))
    second = list(batcher.epoch(shuffle=False))
    assert (ds.read_files, ds.read_bytes) == (files, nbytes)
    assert (ds.cache_hits, ds.cache_misses) == (n, n)
    _same_batches(first, second)
    fetches = [sp.fields for sp in closed_spans if sp.name == "loader_fetch"]
    assert fetches == [
        {"samples": n, "hits": 0, "files": 4 * n, "bytes": nbytes},
        {"samples": n, "hits": n, "files": 0, "bytes": 0}]
    assert reg.value("loader_cache_hits_total") == n
    assert reg.value("loader_cache_misses_total") == n
    assert reg.value("loader_cache_bytes") == ds.cache.held
    # a shuffled epoch finds every sample too, whatever the order
    list(batcher.epoch(shuffle=True))
    assert (ds.read_files, ds.cache_hits) == (files, 2 * n)


def test_budget_smaller_than_the_corpus_holds_a_prefix(synthetic_preprocessed):
    """Admit until full, never evict: the samples that found room are
    served from memory in every later epoch, the rest are read from their
    files every time, and the batches are what an uncached loader yields."""
    sizes = _sample_bytes(synthetic_preprocessed)
    n, fit = len(sizes), 4
    limit = sum(sizes[:fit]) + sizes[fit] - 1  # the fifth just does not fit
    later = [i for i in range(fit + 1, n)
             if sum(sizes[:fit]) + sizes[i] <= limit]  # a smaller one may
    cfg = _config(synthetic_preprocessed)
    plain = list(BucketedBatcher(
        SpeechDataset("train.txt", cfg, cache=CacheBudget(0))
    ).epoch(shuffle=False))
    ds = SpeechDataset("train.txt", cfg, cache=CacheBudget(limit))
    batcher = BucketedBatcher(ds)
    _same_batches(plain, list(batcher.epoch(shuffle=False)))
    held = sorted(ds._held)
    assert held[:fit] == list(range(fit)) and fit not in held
    assert set(held[fit:]) <= set(later)
    assert ds.cache.held == sum(sizes[i] for i in held) <= limit
    for epoch in (2, 3):
        files = ds.read_files
        _same_batches(plain, list(batcher.epoch(shuffle=False)))
        assert ds.read_files == files + 4 * (n - len(held))
        assert sorted(ds._held) == held  # nothing evicted, nothing added
        assert ds.cache_hits == (epoch - 1) * len(held)


def test_budget_of_zero_is_the_uncached_loader(synthetic_preprocessed):
    ds = SpeechDataset("train.txt", _config(synthetic_preprocessed),
                       cache=CacheBudget(0))
    batcher = BucketedBatcher(ds)
    first = list(batcher.epoch(shuffle=False))
    second = list(batcher.epoch(shuffle=False))
    _same_batches(first, second)
    assert (ds.read_files, ds._feature_loads) == (8 * len(ds), 8 * len(ds))
    assert (ds.cache_hits, ds.cache_misses) == (0, 2 * len(ds))
    assert not ds._held and ds.cache.held == 0
    assert all(v.flags.writeable for v in ds[0].values()
               if isinstance(v, np.ndarray))


def test_host_budget_is_a_quarter_of_what_the_host_reports(monkeypatch):
    from speakingstyle_tpu.data import dataset

    assert 0 < CacheBudget().limit <= dataset.host_available_bytes()
    monkeypatch.setattr(dataset, "host_available_bytes", lambda: 4000)
    budget = CacheBudget()
    assert budget.limit == 1000
    assert budget.admit(600) and not budget.admit(401) and budget.admit(400)
    assert budget.held == 1000 and not budget.admit(1)


def test_kept_samples_are_read_only_and_batches_are_copies(
    synthetic_preprocessed
):
    """What the dataset keeps cannot be written through, and ``_pad_batch``
    copies all it takes: scribbling over an emitted batch (as a donated or
    poisoned buffer might) leaves the next epoch's batches as they were."""
    ds = SpeechDataset("train.txt", _config(synthetic_preprocessed))
    batcher = BucketedBatcher(ds)
    first = list(batcher.epoch(shuffle=False))
    again = list(batcher.epoch(shuffle=False))  # all from memory
    for b in again:
        for v in b.arrays().values():
            assert v.flags.writeable and v.flags.owndata
            v[...] = -7
        b.ids.clear()
    _same_batches(first, list(batcher.epoch(shuffle=False)))
    sample = ds[0]
    for k in ("text", "mel", "pitch", "energy", "duration"):
        assert not sample[k].flags.writeable, k
        with pytest.raises(ValueError):
            sample[k][...] = 0
    sample["mel"] = None  # the dict handed out is the caller's own
    assert ds[0]["mel"] is not None


def test_a_sample_that_failed_is_not_kept(synthetic_preprocessed):
    """A load that raised keeps nothing: not the failed sample, not its
    bytes; and a quarantined sample is never asked for again."""
    import os

    from speakingstyle_tpu.training.faults import FaultPlan
    from speakingstyle_tpu.training.resilience import Quarantine

    cfg = _config(synthetic_preprocessed)
    ds = SpeechDataset("train.txt", cfg,
                       fault_plan=FaultPlan.parse("loader_ioerror@6"))
    ds[0]
    held = ds.cache.held
    with pytest.raises(OSError):
        ds[1]  # its second file
    assert list(ds._held) == [0] and ds.cache.held == held
    assert (ds.cache_hits, ds.cache_misses) == (0, 1)
    ds[1]  # the fault fired once: now it loads, and is kept
    assert sorted(ds._held) == [0, 1] and ds.cache_misses == 2

    with open(os.path.join(synthetic_preprocessed, "mel",
                           "LJSpeech-mel-utt003.npy"), "wb") as f:
        f.write(b"not a numpy file")
    ds = SpeechDataset("train.txt", cfg)
    q = Quarantine(budget=2)
    batcher = BucketedBatcher(ds, quarantine=q)
    for _ in range(2):
        assert sum(b.n_real for b in batcher.epoch(shuffle=False)) == 9
    assert "utt003" in q and 3 not in ds._held and len(ds._held) == 9


@pytest.mark.parametrize("limit", [0, None], ids=["uncached", "cached"])
def test_loader_ioerror_fires_at_the_same_call_within_a_first_epoch(
    synthetic_preprocessed, limit
):
    """``loader_ioerror@N`` counts calls of ``_feature``; through a first
    epoch those are what they were, four a sample in order, so the Nth is
    the same file with the cache as without."""
    from speakingstyle_tpu.training.faults import FaultPlan

    plan = FaultPlan.parse("loader_ioerror@23")
    ds = SpeechDataset(
        "train.txt", _config(synthetic_preprocessed), fault_plan=plan,
        cache=None if limit is None else CacheBudget(limit))
    batcher = BucketedBatcher(ds)
    with pytest.raises(OSError, match=r"loader_ioerror@23 .*energy-utt005"):
        list(batcher.epoch(shuffle=False))
    assert ds._feature_loads == 23 and not plan.pending()


def test_run_training_reports_the_cache_on_its_events(
    synthetic_preprocessed, tmp_path
):
    """A toy ``run_training``: the budget on ``train_start``, the window's
    hits and misses (per step, as every window field) on every
    ``train_step`` event; ten samples at batch 8 are one batch an epoch,
    so the first step's samples are read and the later steps' are held."""
    from speakingstyle_tpu.data import dataset
    from speakingstyle_tpu.obs import MetricsRegistry, read_events
    from speakingstyle_tpu.training.trainer import run_training
    from tests.test_resilience import _train_config

    cfg = _train_config(synthetic_preprocessed, tmp_path, total=4, save=10,
                        log=1)
    reg = MetricsRegistry()
    run_training(cfg, max_steps=4, registry=reg)
    log_dir = cfg.train.path.log_path
    (start,) = read_events(log_dir, event="train_start")
    assert 0 < start["loader_cache_budget_bytes"] <= int(
        CacheBudget.HOST_SHARE * dataset.host_available_bytes() * 1.5)
    steps = list(read_events(log_dir, event="train_step"))
    assert len(steps) == 4
    assert all(e["loader_cache_hits"] >= 0 and e["loader_cache_misses"] >= 0
               for e in steps)
    hits = sum(e["loader_cache_hits"] for e in steps)
    misses = sum(e["loader_cache_misses"] for e in steps)
    # the worker runs beside the loop: the first epoch, fetched while the
    # model was built, may lie before the first window, and what was
    # fetched after the last boundary is in the registry and in no event
    assert misses in (0, 10) and reg.value("loader_cache_misses_total") == 10
    assert 0 < hits <= reg.value("loader_cache_hits_total")
    assert hits % 10 == 0
    assert reg.value("loader_cache_bytes") == sum(
        _sample_bytes(synthetic_preprocessed))


def test_text_batcher(synthetic_preprocessed, tmp_path):
    cfg = _config(synthetic_preprocessed)
    src = tmp_path / "source.txt"
    src.write_text("utt000|LJSpeech|{AH0 K T}|hello\n")
    tb = TextBatcher(str(src), cfg)
    item = tb[0]
    assert item["text"].shape == (3,)
    assert item["mel"] is not None  # found the preprocessed mel for style
