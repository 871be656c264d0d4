"""Milliseconds a step's share of the log boundary's wait for the device
(``train_sync``)."""
from benchmark.harness import spans


def read(ctx):
    return spans.window_mean_ms(ctx, "sync_s")
