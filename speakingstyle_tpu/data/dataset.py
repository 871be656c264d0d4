"""Training/inference datasets over preprocessed features.

On-disk contract matches the reference exactly (reference: dataset.py:12-146):
metadata lines ``basename|speaker|{phones}|raw_text``; features at
``<root>/{mel,pitch,energy,duration}/{speaker}-{kind}-{basename}.npy``;
collate sorts a ``group_size × batch_size`` super-batch by text length and
splits it into ``group_size`` real batches.

TPU-side redesign (SURVEY.md §7 step 5): every emitted batch is padded to a
shape from a small static bucket grid — (src rounded up to ``src_bucket``,
mel rounded up to ``mel_bucket``) — so XLA compiles a handful of programs
instead of one per batch shape. The reference's dynamic per-batch max-length
padding (utils/tools.py:285-316) would trigger a recompile every step.

A run reads the same utterances once an epoch, thousands of times over, so
``SpeechDataset`` keeps each finished sample in host memory after its first
read (``CacheBudget``): from the second epoch on, whatever fits is served
without opening a file.
"""

import json
import os
import threading
import time
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence

import numpy as np

from speakingstyle_tpu.configs.config import Config
from speakingstyle_tpu.obs import MetricsRegistry, Span, get_registry
from speakingstyle_tpu.text import text_to_sequence


def parse_metadata(path: str):
    """metadata file -> list of (basename, speaker, phones_text, raw_text)."""
    entries = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip("\n")
            if not line:
                continue
            basename, speaker, text, raw = line.split("|", 3)
            entries.append((basename, speaker, text, raw))
    return entries


def bucket_length(n: int, step: int, max_len: Optional[int] = None) -> int:
    """Round n up to the next bucket edge (multiple of `step`)."""
    b = ((max(n, 1) + step - 1) // step) * step
    return min(b, max_len) if max_len is not None else b


@dataclass
class Batch:
    """One padded, static-shape training batch (all numpy, host-side).

    The batch dimension may include all-padding dummy items (src_len =
    mel_len = 0) so B divides the mesh's data axis; ``n_real`` counts the
    genuine items. Dummy items contribute nothing to masked losses.
    """

    n_real: int
    ids: List[str]
    raw_texts: List[str]
    speakers: np.ndarray     # [B] int32
    texts: np.ndarray        # [B, L_src] int32
    src_lens: np.ndarray     # [B] int32
    mels: np.ndarray         # [B, L_mel, n_mels] float32
    mel_lens: np.ndarray     # [B] int32
    pitches: np.ndarray      # [B, L_src or L_mel] float32
    energies: np.ndarray     # [B, L_src or L_mel] float32
    durations: np.ndarray    # [B, L_src] int32

    @property
    def frames_real(self) -> int:
        """Real (unpadded) positions of the sequence the decoder runs over."""
        return int(self.mel_lens.sum())

    @property
    def frames_padded(self) -> int:
        return self.mels.shape[0] * self.mels.shape[1]

    @property
    def shape(self) -> tuple:
        """What the step compiles once for: (rows, mel bucket, src bucket)."""
        return self.mels.shape[:2] + self.texts.shape[1:]

    def arrays(self) -> Dict[str, np.ndarray]:
        return {
            "speakers": self.speakers,
            "texts": self.texts,
            "src_lens": self.src_lens,
            "mels": self.mels,
            "mel_lens": self.mel_lens,
            "pitches": self.pitches,
            "energies": self.energies,
            "durations": self.durations,
        }


def host_available_bytes() -> int:
    """What the host says a process may still take: ``MemAvailable`` of
    ``/proc/meminfo``, the physical memory where there is no such line."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")


class CacheBudget:
    """Bytes of finished samples that the datasets given this budget may
    hold between them. Observed, not set: with no ``limit`` it is a quarter
    of what the host reports as available when the budget is made (each
    process of a multi-host run makes its own). A limit of 0 holds nothing.

    Admission only, never a release: every epoch is a fresh permutation, so
    of a corpus larger than the budget any eviction rule would hit the
    share it holds and no more.
    """

    HOST_SHARE = 0.25

    def __init__(self, limit: Optional[int] = None):
        self.limit = (int(self.HOST_SHARE * host_available_bytes())
                      if limit is None else int(limit))
        self.held = 0
        self._lock = threading.Lock()  # train and validation loaders share it

    def admit(self, nbytes: int) -> bool:
        """Take ``nbytes`` of the budget if that much is left."""
        with self._lock:
            if self.held + nbytes > self.limit:
                return False
            self.held += nbytes
            return True


class CachedSamples:
    """What every dataset of ``.npy`` samples shares: a sample is built once
    (``_build(idx)``, the subclass's) and then kept, finished (its arrays
    after their casts, marked read-only), for as long as ``cache`` has room:
    ``__getitem__`` serves it from memory from then on and reads nothing. A
    sample that does not fit is built from its files every time, as is one
    whose load raised. ``cache=None`` makes a budget of this dataset's own
    from the host's memory; a run's datasets share one; one-pass callers
    pass ``CacheBudget(0)``. ``cache_hits``/``cache_misses`` count the
    samples served either way.

    ``retries``/``backoff`` engage retry-with-exponential-backoff on
    transient OSErrors in the file loads (flaky network filesystems on
    preemptible slices); ``fault_plan`` (training/faults.py) injects a
    ``loader_ioerror`` exactly once at the named load count so the
    retry path is exercised deterministically in tests.

    ``read_seconds``/``read_files``/``read_bytes`` accumulate what
    ``np.load`` alone cost (``loader_read``): plain sums and no span; the
    batcher that drives the dataset reports their growth once per
    super-batch (the seconds as a counter, files and bytes on its
    ``loader_fetch`` span).
    """

    group_size = 4  # super-batch factor (reference: train.py:31)

    def __init__(self, config: Config, sort: bool, drop_last: bool,
                 retries: int, backoff: float, fault_plan,
                 cache: Optional[CacheBudget]):
        self.root = config.preprocess.path.preprocessed_path
        self.batch_size = config.train.optimizer.batch_size
        self.sort = sort
        self.drop_last = drop_last
        self.retries = retries
        self.backoff = backoff
        self.fault_plan = fault_plan
        self._feature_loads = 0  # loader_ioerror@N counter (1-based)
        self.cache = cache if cache is not None else CacheBudget()
        self._held: Dict[int, Dict] = {}
        self.cache_hits, self.cache_misses = 0, 0
        self.read_seconds, self.read_files, self.read_bytes = 0.0, 0, 0
        self.entries: List[tuple] = []

    def __len__(self):
        return len(self.entries)

    def _load(self, path: str) -> np.ndarray:
        from speakingstyle_tpu.training.resilience import retry_io

        self._feature_loads += 1
        n = self._feature_loads

        def load():
            if self.fault_plan is not None and self.fault_plan.fire(
                "loader_ioerror", n
            ):
                raise IOError(f"injected loader_ioerror@{n} ({path})")
            t0 = time.monotonic()
            arr = np.load(path)
            self.read_seconds += time.monotonic() - t0
            self.read_files += 1
            self.read_bytes += arr.nbytes
            return arr

        if not self.retries:
            return load()
        return retry_io(
            load, retries=self.retries, backoff=self.backoff,
            exceptions=(OSError,), describe=path,
        )

    def _build(self, idx: int) -> Dict:
        raise NotImplementedError

    def __getitem__(self, idx: int) -> Dict:
        sample = self._held.get(idx)
        if sample is not None:
            self.cache_hits += 1
            return dict(sample)
        sample = self._build(idx)  # a load that raises keeps nothing
        self.cache_misses += 1
        arrays = [v for v in sample.values() if isinstance(v, np.ndarray)]
        if self.cache.admit(sum(a.nbytes for a in arrays)):
            for a in arrays:
                a.flags.writeable = False
            self._held[idx] = sample
        return dict(sample)


class SpeechDataset(CachedSamples):
    """Feature-loading dataset (reference: dataset.py:12-146): four feature
    files a sample (mel, pitch, energy, duration), kept after their first
    read as ``CachedSamples`` says."""

    def __init__(
        self,
        filename: str,
        config: Config,
        sort: bool = True,
        drop_last: bool = False,
        retries: int = 0,
        backoff: float = 0.05,
        fault_plan=None,
        cache: Optional[CacheBudget] = None,
    ):
        super().__init__(config, sort, drop_last, retries, backoff, fault_plan,
                         cache)
        pp = config.preprocess
        self.cleaners = pp.preprocessing.text.text_cleaners
        self.pitch_level = pp.preprocessing.pitch.feature
        self.energy_level = pp.preprocessing.energy.feature
        self.entries = parse_metadata(os.path.join(self.root, filename))
        with open(os.path.join(self.root, "speakers.json")) as f:
            self.speaker_map = json.load(f)

    def _feature(self, kind: str, speaker: str, basename: str) -> np.ndarray:
        return self._load(
            os.path.join(self.root, kind, f"{speaker}-{kind}-{basename}.npy"))

    def _build(self, idx: int) -> Dict:
        basename, speaker, text, raw = self.entries[idx]
        phones = np.asarray(text_to_sequence(text, self.cleaners), np.int32)
        return {
            "id": basename,
            "speaker": self.speaker_map[speaker],
            "raw_text": raw,
            "text": phones,
            "mel": self._feature("mel", speaker, basename).astype(np.float32),
            "pitch": self._feature("pitch", speaker, basename).astype(np.float32),
            "energy": self._feature("energy", speaker, basename).astype(np.float32),
            "duration": self._feature("duration", speaker, basename).astype(np.int32),
        }


class BucketedBatcher:
    """Sort-group collate + static-shape bucket padding.

    ``src_bucket``/``mel_bucket`` control the bucket grid granularity;
    ``max_src``/``max_mel`` cap the padded shapes (features beyond the cap
    are truncated, mirroring the reference Decoder's max_seq_len truncation,
    transformer/Models.py:154-162).

    ``quarantine`` (training/resilience.Quarantine) makes sample loading
    fault-tolerant: a sample that still fails after the dataset's own
    retries is quarantined (logged + skipped) instead of killing the
    prefetch worker, and the run fails only past the quarantine's
    bad-sample budget. Without it, the first loader error propagates
    (the pre-resilience behavior).

    Spans (obs/trace.py, into ``registry``), on whichever thread drives
    the iterator (the prefetch worker): ``loader_fetch`` around a
    super-batch's sample loads (fields: samples, of them ``hits`` served
    from the dataset's memory, and the files and bytes still read), with
    the seconds of the dataset's ``loader_read`` sum published beside it as
    ``loader_read_seconds_total``, the samples served from memory and from
    files as ``loader_cache_hits_total`` / ``loader_cache_misses_total``,
    the bytes held against the budget as ``loader_cache_bytes``; and
    ``loader_collate`` around the length sort and each ``_pad_batch``.
    """

    def __init__(
        self,
        dataset: SpeechDataset,
        src_bucket: int = 32,
        mel_bucket: int = 128,
        max_src: Optional[int] = None,
        max_mel: Optional[int] = None,
        batch_pad_multiple: int = 1,
        seed: int = 1234,
        quarantine=None,
        registry: Optional[MetricsRegistry] = None,
    ):
        self.ds = dataset
        self.src_bucket = src_bucket
        self.mel_bucket = mel_bucket
        self.max_src = max_src
        self.max_mel = max_mel
        self.batch_pad_multiple = batch_pad_multiple
        self.quarantine = quarantine
        self.rng = np.random.default_rng(seed)
        self.registry = registry if registry is not None else get_registry()

    def _fetch(self, idx: int) -> Optional[Dict]:
        """Load one sample; quarantine-and-skip (returns None) on failure
        when a quarantine is attached."""
        sample_id = self.ds.entries[idx][0]
        if self.quarantine is not None and sample_id in self.quarantine:
            return None  # known-bad: don't pay the retries again
        try:
            return self.ds[idx]
        except Exception as e:
            if self.quarantine is None:
                raise
            self.quarantine.add(sample_id, e)  # raises past the budget
            return None

    def _fetch_all(self, chunk) -> List[Dict]:
        """One super-batch's samples under a ``loader_fetch`` span (how
        many came from memory, and the files and bytes still read, as
        fields); the seconds ``np.load`` took of it and the samples served
        either way into the registry's counters."""
        ds, reg = self.ds, self.registry

        def sums():
            return (ds.read_seconds, ds.read_files, ds.read_bytes,
                    ds.cache_hits, ds.cache_misses)

        before = sums()
        with Span("loader_fetch", registry=reg) as sp:
            items = [it for i in chunk
                     if (it := self._fetch(int(i))) is not None]
            seconds, files, nbytes, hits, misses = (
                now - was for now, was in zip(sums(), before))
            sp.note(samples=len(items), hits=hits, files=files, bytes=nbytes)
        reg.counter("loader_read_seconds_total",
                    help="seconds inside np.load of feature files").inc(seconds)
        reg.counter("loader_cache_hits_total",
                    help="samples served from host memory").inc(hits)
        reg.counter("loader_cache_misses_total",
                    help="samples built from their feature files").inc(misses)
        reg.gauge("loader_cache_bytes",
                  help="bytes of finished samples held in host memory"
                  ).set(ds.cache.held)
        return items

    def _pad_batch(self, items: Sequence[Dict]) -> Batch:
        n_real = len(items)
        m = self.batch_pad_multiple
        B = ((n_real + m - 1) // m) * m
        src_lens = np.zeros((B,), np.int32)
        mel_lens = np.zeros((B,), np.int32)
        src_lens[:n_real] = [len(d["text"]) for d in items]
        mel_lens[:n_real] = [d["mel"].shape[0] for d in items]
        if self.max_src is not None:
            src_lens = np.minimum(src_lens, self.max_src)
        if self.max_mel is not None:
            mel_lens = np.minimum(mel_lens, self.max_mel)
        L_src = bucket_length(int(src_lens.max()), self.src_bucket, self.max_src)
        L_mel = bucket_length(int(mel_lens.max()), self.mel_bucket, self.max_mel)
        n_mels = items[0]["mel"].shape[1]

        texts = np.zeros((B, L_src), np.int32)
        durations = np.zeros((B, L_src), np.int32)
        mels = np.zeros((B, L_mel, n_mels), np.float32)
        p_len = L_src if self.ds.pitch_level == "phoneme_level" else L_mel
        e_len = L_src if self.ds.energy_level == "phoneme_level" else L_mel
        pitches = np.zeros((B, p_len), np.float32)
        energies = np.zeros((B, e_len), np.float32)

        for i, d in enumerate(items):
            ls, lm = src_lens[i], mel_lens[i]
            texts[i, :ls] = d["text"][:ls]
            dur = d["duration"][:ls].copy()
            # keep sum(duration) == mel_len after any truncation: trim excess
            # frames from the tail phones, and if src truncation dropped
            # duration mass, shrink mel_len to the frames still covered
            excess = int(dur.sum()) - int(lm)
            j = len(dur) - 1
            while excess > 0 and j >= 0:
                take = min(excess, int(dur[j]))
                dur[j] -= take
                excess -= take
                j -= 1
            lm = int(dur.sum())
            mel_lens[i] = lm
            durations[i, :ls] = dur
            mels[i, :lm] = d["mel"][:lm]
            pitches[i, : min(len(d["pitch"]), p_len)] = d["pitch"][:p_len]
            energies[i, : min(len(d["energy"]), e_len)] = d["energy"][:e_len]

        speakers = np.zeros((B,), np.int32)
        speakers[:n_real] = [d["speaker"] for d in items]
        return Batch(
            n_real=n_real,
            ids=[d["id"] for d in items],
            raw_texts=[d["raw_text"] for d in items],
            speakers=speakers,
            texts=texts,
            src_lens=src_lens,
            mels=mels,
            mel_lens=mel_lens,
            pitches=pitches,
            energies=energies,
            durations=durations,
        )

    def epoch(self, shuffle: bool = True) -> Iterator[Batch]:
        """One pass: super-batch grouping then per-group length sort."""
        ds = self.ds
        order = np.arange(len(ds))
        if shuffle:
            self.rng.shuffle(order)
        super_size = ds.batch_size * ds.group_size
        for s in range(0, len(order), super_size):
            chunk = order[s : s + super_size]
            items = self._fetch_all(chunk)
            if not items:
                continue
            if ds.sort:
                with Span("loader_collate", registry=self.registry,
                          rows=len(items)):
                    idx = np.argsort([-len(d["text"]) for d in items],
                                     kind="stable")
                    items = [items[int(i)] for i in idx]
            for b in range(0, len(items), ds.batch_size):
                sub = items[b : b + ds.batch_size]
                if len(sub) < ds.batch_size and ds.drop_last:
                    continue
                with Span("loader_collate", registry=self.registry,
                          rows=len(sub)) as sp:
                    batch = self._pad_batch(sub)
                    sp.note(padded_frames=batch.mels.shape[0] * batch.mels.shape[1],
                            real_frames=int(batch.mel_lens.sum()))
                yield batch

    def __iter__(self) -> Iterator[Batch]:
        """Infinite stream of batches (the reference's while-True epoch loop)."""
        while True:
            yield from self.epoch()


class TextBatcher:
    """Inference-time dataset: metadata without targets (reference:
    dataset.py:149-218) + the reference mel for the style encoder."""

    def __init__(self, filename: str, config: Config, ref_mels: Optional[Dict] = None):
        pp = config.preprocess
        self.root = pp.path.preprocessed_path
        self.cleaners = pp.preprocessing.text.text_cleaners
        self.entries = parse_metadata(filename)
        with open(os.path.join(self.root, "speakers.json")) as f:
            self.speaker_map = json.load(f)
        self.ref_mels = ref_mels or {}

    def __len__(self):
        return len(self.entries)

    def __getitem__(self, idx):
        basename, speaker, text, raw = self.entries[idx]
        item = {
            "id": basename,
            "speaker": self.speaker_map.get(speaker, 0),
            "raw_text": raw,
            "text": np.asarray(text_to_sequence(text, self.cleaners), np.int32),
        }
        mel = self.ref_mels.get(basename)
        if mel is None:
            path = os.path.join(self.root, "mel", f"{speaker}-mel-{basename}.npy")
            if os.path.exists(path):
                mel = np.load(path).astype(np.float32)
        item["mel"] = mel
        return item

    def epoch(
        self,
        batch_size: int = 8,
        src_bucket: int = 32,
        mel_bucket: int = 128,
    ) -> Iterator[Batch]:
        """Padded inference batches (reference: synthesize.py:255-262 uses a
        bs-8 DataLoader). Target arrays are zeros — free-running mode only
        reads texts + the style-reference mel."""
        for s in range(0, len(self), batch_size):
            items = [self[i] for i in range(s, min(s + batch_size, len(self)))]
            B = len(items)
            for d in items:
                if d["mel"] is None:
                    raise ValueError(
                        f"no reference mel for {d['id']!r}: the style encoder "
                        "requires one (reference: synthesize.py --ref_audio)"
                    )
            src_lens = np.asarray([len(d["text"]) for d in items], np.int32)
            mel_lens = np.asarray([d["mel"].shape[0] for d in items], np.int32)
            L_src = bucket_length(int(src_lens.max()), src_bucket)
            L_mel = bucket_length(int(mel_lens.max()), mel_bucket)
            n_mels = items[0]["mel"].shape[1]
            texts = np.zeros((B, L_src), np.int32)
            mels = np.zeros((B, L_mel, n_mels), np.float32)
            for i, d in enumerate(items):
                texts[i, : src_lens[i]] = d["text"]
                mels[i, : mel_lens[i]] = d["mel"]
            yield Batch(
                n_real=B,
                ids=[d["id"] for d in items],
                raw_texts=[d["raw_text"] for d in items],
                speakers=np.asarray([d["speaker"] for d in items], np.int32),
                texts=texts,
                src_lens=src_lens,
                mels=mels,
                mel_lens=mel_lens,
                pitches=np.zeros((B, L_src), np.float32),
                energies=np.zeros((B, L_src), np.float32),
                durations=np.zeros((B, L_src), np.int32),
            )
