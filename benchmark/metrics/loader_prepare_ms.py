"""Milliseconds a step's share of the loader spent preparing samples apart
from ``np.load``: ``loader_fetch`` less ``loader_read`` (text to ids,
``astype``, the retry wrapper) plus ``loader_collate`` (sort and padding)."""
from benchmark.harness import spans


def read(ctx):
    whole = spans.window_mean_ms(ctx, "loader_fetch_s", "loader_collate_s")
    load = spans.window_mean_ms(ctx, "loader_read_s")
    return None if whole is None or load is None else whole - load
