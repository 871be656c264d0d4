"""Milliseconds the host took to enqueue a step (``train_dispatch``)."""
from benchmark.harness import spans


def read(ctx):
    return spans.window_mean_ms(ctx, "dispatch_s")
