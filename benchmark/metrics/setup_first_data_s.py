"""Seconds of the run's first ``train_data_wait``, the one the ring holds:
the first super-batch's loads, before any step. 0 where the run records
``run_start`` and no wait; None where it records neither (an earlier
program)."""
from benchmark.harness import spans


def read(ctx):
    if spans.run_span_s("run_start") is None:
        return None
    return spans.run_span_s("train_data_wait") or 0.0
