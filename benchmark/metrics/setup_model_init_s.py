"""Seconds of the run's ``setup_model_init`` span: the model built, its
variables initialised, the optimizer state made."""
from benchmark.harness import spans


def read(ctx):
    return spans.run_span_s("setup_model_init")
