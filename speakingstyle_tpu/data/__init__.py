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

__all__ = [
    "Batch",
    "BucketedBatcher",
    "CacheBudget",
    "SpeechDataset",
    "TextBatcher",
    "bucket_length",
    "parse_metadata",
    "DevicePrefetcher",
]
