"""Seconds of the run's ``run_start`` ring span: from the end of set-up's
datasets to the loop's first data wait (the logger, the build identity, the
step's key, the rollback template)."""
from benchmark.harness import spans


def read(ctx):
    return spans.run_span_s("run_start")
