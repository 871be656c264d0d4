"""Share of the device's idle time in the traced window during which the
prefetch worker was loading a super-batch's samples (``loader_fetch``)."""
from benchmark.harness import spans


def read(ctx):
    return spans.idle_share_pct(ctx["trace"], ["loader_fetch"])
