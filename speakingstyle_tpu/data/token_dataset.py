"""A token corpus packed into rows, for the ``decoder_lm`` family.

On disk, under ``preprocess.path.preprocessed_path``: ``train.txt`` and
``val.txt``, one ``basename|n_tokens`` a line, and a document's ids at
``tokens/<basename>.npy`` (int32). Documents are of any length; the model
trains on full rows of ``seq_len`` positions. ``PackedBatcher`` makes them
the usual way (concatenate and chunk): an epoch's documents in a fresh
order, each closed by ``eod_id``, laid end to end and cut every ``seq_len``
ids. No padding, no mask between documents (attention runs across their
boundaries), and what is left of the stream after the last full super-batch
is carried into the next, so no id is ever dropped.

For the block-diffusion objective the packer also draws the noise
(``block_noise``), on the host and from the data seed, so that a run and
whoever follows it see the same rows: each block of ``block_length`` tokens
of a row draws ``t ~ U[0.001, 1]`` and each of its positions is masked with
probability ``t``; ``noised`` holds ``mask_id`` where a position is masked
and ``weight`` ``1 / t`` there, 0 elsewhere. A row's draw is a function of
(the batcher's seed, the epoch, the row's index in the epoch) and of
nothing drawn before it.

The reading is ``data/dataset.py``'s: ``TokenDataset`` is a
``CachedSamples`` (a document is read once and then served from host
memory, inside the run's ``CacheBudget``), ``PackedBatcher`` a
``BucketedBatcher`` (``loader_fetch`` spans, cache counters, quarantine)
whose collate is the packer, and ``DevicePrefetcher`` drives it.
"""

import os
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from speakingstyle_tpu.configs.config import Config
from speakingstyle_tpu.data.dataset import BucketedBatcher, CachedSamples, CacheBudget
from speakingstyle_tpu.obs import MetricsRegistry, Span


# documents read ahead of the packer at a time: a few megabytes of ids, and a
# corpus no larger than this is held whole after its first fetch
FETCH_DOCS = 1024


# the least masking probability a block draws: its weight is at most 1000
T_MIN = 0.001


def block_noise(tokens: np.ndarray, block_length: int, mask_id: int,
                seed: int, epoch: int, first_row: int):
    """(noised ``[R, L]`` int32, weight ``[R, L]`` float32, masked positions)
    for rows ``first_row ..`` of epoch ``epoch``: a generator a row, seeded
    by (``seed``, ``epoch``, the row's index), draws the blocks' ``t`` and
    then the positions' coins."""
    rows, length = tokens.shape
    masked = np.empty(tokens.shape, bool)
    weight = np.zeros(tokens.shape, np.float32)
    for r in range(rows):
        rng = np.random.default_rng([int(seed), int(epoch), first_row + r])
        t = np.repeat(rng.uniform(T_MIN, 1.0, length // block_length),
                      block_length)
        masked[r] = rng.random(length) < t
        weight[r, masked[r]] = (1.0 / t[masked[r]]).astype(np.float32)
    noised = np.where(masked, np.int32(mask_id), tokens).astype(np.int32)
    return noised, weight, int(masked.sum())


@dataclass
class TokenBatch:
    """``batch_size`` full rows of ``seq_len`` ids (numpy, host-side); under
    the block-diffusion objective with their noised copy and loss weights."""

    ids: List[str]           # the documents that begin in these rows
    tokens: np.ndarray       # [B, seq_len] int32
    noised: Optional[np.ndarray] = None   # [B, seq_len] int32
    weight: Optional[np.ndarray] = None   # [B, seq_len] float32

    @property
    def n_real(self) -> int:
        return self.tokens.shape[0]

    @property
    def frames_real(self) -> int:
        """Corpus tokens trained on: a packed row has no padding. (Under
        block diffusion the layers run over twice as many positions, the
        noised and the clean copy: the method's cost, not more tokens.)"""
        return self.tokens.size

    frames_padded = frames_real

    @property
    def shape(self) -> tuple:
        return self.tokens.shape

    def arrays(self) -> Dict[str, np.ndarray]:
        out = {"tokens": self.tokens}
        if self.noised is not None:
            out.update(noised=self.noised, weight=self.weight)
        return out


class TokenDataset(CachedSamples):
    """Documents of token ids, one ``.npy`` each."""

    def __init__(self, filename: str, config: Config, sort: bool = False,
                 drop_last: bool = True, retries: int = 0,
                 backoff: float = 0.05, fault_plan=None,
                 cache: Optional[CacheBudget] = None):
        super().__init__(config, sort, drop_last, retries, backoff, fault_plan,
                         cache)
        with open(os.path.join(self.root, filename), encoding="utf-8") as f:
            self.entries = [(name, int(n)) for name, n in
                            (line.strip().split("|") for line in f if line.strip())]

    def _build(self, idx: int) -> Dict:
        name = self.entries[idx][0]
        ids = self._load(os.path.join(self.root, "tokens", f"{name}.npy"))
        return {"id": name, "tokens": ids.astype(np.int32)}


class PackedBatcher(BucketedBatcher):
    """Documents in, full rows out. A super-batch is ``group_size`` batches
    of ``batch_size`` rows, as in the parent; documents are fetched
    ``FETCH_DOCS`` at a time (a ``loader_fetch`` span each) until the stream
    holds a super-batch, then packed under ``loader_collate``, inside which
    a ``loader_pack`` ring span (under ``trace_parent``, where one is given)
    says how many documents and ids went into how many rows and what was
    carried on. ``noise`` (block length, mask id) makes it draw each row's
    ``block_noise`` too, under a ``loader_noise`` ring span of its own
    (rows, blocks, masked)."""

    def __init__(self, dataset: TokenDataset, seq_len: int, eod_id: int,
                 seed: int = 1234, quarantine=None,
                 registry: Optional[MetricsRegistry] = None,
                 trace_parent=None, noise: Optional[Tuple[int, int]] = None):
        super().__init__(dataset, seed=seed, quarantine=quarantine,
                         registry=registry)
        self.seq_len, self.eod_id = seq_len, eod_id
        self.trace_parent = trace_parent
        self.noise, self.seed = noise, seed
        self._epoch = -1                      # the epoch being packed
        self._row = 0                         # rows of it packed so far
        self._stream: List[np.ndarray] = []   # ids not yet in a row
        self._stream_len = 0
        self._names: List[str] = []

    def _take(self, items: List[Dict]):
        for d in items:
            self._stream += [d["tokens"], np.asarray([self.eod_id], np.int32)]
            self._stream_len += len(d["tokens"]) + 1
            self._names.append(d["id"])

    def _pack(self, rows: int) -> np.ndarray:
        """The stream's first ``rows * seq_len`` ids as ``[rows, seq_len]``;
        the rest stays."""
        with Span("loader_pack", registry=self.registry,
                  parent=self.trace_parent) as sp:
            flat = np.concatenate(self._stream)
            n = rows * self.seq_len
            out = flat[:n].reshape(rows, self.seq_len).copy()
            rest = flat[n:]
            sp.note(documents=len(self._names), rows=rows, ids=n,
                    carried=int(rest.size))
            self._stream = [rest] if rest.size else []
            self._stream_len = int(rest.size)
        return out

    def _noise(self, rows: np.ndarray):
        """The next rows' noise: (noised, weight), or (None, None)."""
        if self.noise is None:
            return None, None
        block_length, mask_id = self.noise
        with Span("loader_noise", registry=self.registry,
                  parent=self.trace_parent) as sp:
            noised, weight, masked = block_noise(
                rows, block_length, mask_id, self.seed, self._epoch, self._row)
            sp.note(rows=len(rows), blocks=rows.size // block_length,
                    masked=masked)
        self._row += len(rows)
        return noised, weight

    def epoch(self, shuffle: bool = True) -> Iterator[TokenBatch]:
        ds = self.ds
        self._epoch, self._row = self._epoch + 1, 0
        order = np.arange(len(ds))
        if shuffle:
            self.rng.shuffle(order)
        per_batch = ds.batch_size * self.seq_len
        want = per_batch * ds.group_size
        for s in range(0, len(order), FETCH_DOCS):
            self._take(self._fetch_all(order[s: s + FETCH_DOCS]))
            last = s + FETCH_DOCS >= len(order)
            while self._stream_len >= want or (
                    last and self._stream_len >= per_batch):
                n_batches = min(self._stream_len // per_batch, ds.group_size)
                with Span("loader_collate", registry=self.registry,
                          rows=n_batches * ds.batch_size) as sp:
                    rows = self._pack(n_batches * ds.batch_size)
                    noised, weight = self._noise(rows)
                    names, self._names = self._names, []
                    sp.note(padded_frames=rows.size, real_frames=rows.size)
                for b in range(n_batches):
                    at = slice(b * ds.batch_size, (b + 1) * ds.batch_size)
                    yield TokenBatch(
                        ids=names if b == 0 else [], tokens=rows[at],
                        noised=None if noised is None else noised[at],
                        weight=None if weight is None else weight[at])
