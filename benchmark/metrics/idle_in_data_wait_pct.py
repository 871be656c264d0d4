"""Share of the device's idle time in the traced window during which the
step loop sat in ``train_data_wait``."""
from benchmark.harness import spans


def read(ctx):
    return spans.idle_share_pct(ctx["trace"], ["train_data_wait"])
