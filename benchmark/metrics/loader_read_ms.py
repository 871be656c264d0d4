"""Milliseconds a step's share of the loader spent inside ``np.load``: the
program's ``loader_read`` accumulator, from the window's ``train_step``
events."""
from benchmark.harness import spans


def read(ctx):
    return spans.window_mean_ms(ctx, "loader_read_s")
