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

The reading is ``data/dataset.py``'s: ``TokenDataset`` is a
``CachedSamples`` (a document is read once and then served from host
memory, inside the run's ``CacheBudget``), ``PackedBatcher`` a
``BucketedBatcher`` (``loader_fetch`` spans, cache counters, quarantine)
whose collate is the packer, and ``DevicePrefetcher`` drives it.
"""

import os
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional

import numpy as np

from speakingstyle_tpu.configs.config import Config
from speakingstyle_tpu.data.dataset import BucketedBatcher, CachedSamples, CacheBudget
from speakingstyle_tpu.obs import MetricsRegistry, Span


# documents read ahead of the packer at a time: a few megabytes of ids, and a
# corpus no larger than this is held whole after its first fetch
FETCH_DOCS = 1024


@dataclass
class TokenBatch:
    """``batch_size`` full rows of ``seq_len`` ids (numpy, host-side)."""

    ids: List[str]           # the documents that begin in these rows
    tokens: np.ndarray       # [B, seq_len] int32

    @property
    def n_real(self) -> int:
        return self.tokens.shape[0]

    @property
    def frames_real(self) -> int:
        """Positions the decoder runs over: a packed row has no padding."""
        return self.tokens.size

    frames_padded = frames_real

    @property
    def shape(self) -> tuple:
        return self.tokens.shape

    def arrays(self) -> Dict[str, np.ndarray]:
        return {"tokens": self.tokens}


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
    carried on."""

    def __init__(self, dataset: TokenDataset, seq_len: int, eod_id: int,
                 seed: int = 1234, quarantine=None,
                 registry: Optional[MetricsRegistry] = None,
                 trace_parent=None):
        super().__init__(dataset, seed=seed, quarantine=quarantine,
                         registry=registry)
        self.seq_len, self.eod_id = seq_len, eod_id
        self.trace_parent = trace_parent
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

    def epoch(self, shuffle: bool = True) -> Iterator[TokenBatch]:
        ds = self.ds
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
                    names, self._names = self._names, []
                    sp.note(padded_frames=rows.size, real_frames=rows.size)
                for b in range(n_batches):
                    yield TokenBatch(
                        ids=names if b == 0 else [],
                        tokens=rows[b * ds.batch_size:(b + 1) * ds.batch_size])
