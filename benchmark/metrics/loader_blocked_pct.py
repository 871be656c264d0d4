"""Share of a step's wall time the loader spent waiting on a full queue
(``loader_blocked``): near 0 the loader sets the pace, near what is left of
its cycle the device does."""
from benchmark.harness import spans


def read(ctx):
    blocked = spans.window_mean_ms(ctx, "loader_blocked_s")
    if blocked is None:
        return None
    step_ms = 1e3 * ctx["window_s"] / (len(ctx["events"]) * ctx["log_step"])
    return 100.0 * blocked / step_ms
