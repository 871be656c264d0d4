"""Seconds of the run's ``setup_restore`` span: the checkpoint manager and
the restore of the seeded weights."""
from benchmark.harness import spans


def read(ctx):
    return spans.run_span_s("setup_restore")
