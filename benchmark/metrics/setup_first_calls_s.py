"""Seconds of every batch shape's first ``train_dispatch`` together: the
step's compile, or its load from the persistent cache (they hold the
seconds ``compile_s`` counts for the step)."""
from benchmark.harness import spans


def read(ctx):
    return spans.run_span_s("train_dispatch")
