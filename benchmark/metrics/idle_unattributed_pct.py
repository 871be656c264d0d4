"""Share of the device's idle time in the traced window under none of the
step loop's four spans: host work that has no span yet."""
from benchmark.harness import spans


def read(ctx):
    covered = spans.idle_share_pct(ctx["trace"], spans.MAIN_THREAD_SPANS)
    return None if covered is None else 100.0 - covered
