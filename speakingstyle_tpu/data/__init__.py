"""Data pipeline: preprocessed-feature datasets, bucketed batching, prefetch."""

from speakingstyle_tpu.data.dataset import (
    Batch,
    BucketedBatcher,
    CacheBudget,
    SpeechDataset,
    TextBatcher,
    bucket_length,
    parse_metadata,
)
from speakingstyle_tpu.data.prefetch import DevicePrefetcher
from speakingstyle_tpu.data.token_dataset import (
    PackedBatcher,
    TokenBatch,
    TokenDataset,
)

__all__ = [
    "Batch",
    "BucketedBatcher",
    "CacheBudget",
    "SpeechDataset",
    "TextBatcher",
    "bucket_length",
    "parse_metadata",
    "DevicePrefetcher",
    "PackedBatcher",
    "TokenBatch",
    "TokenDataset",
]
