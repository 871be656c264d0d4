"""Mean wall time of a step over the window: time between the first and
the last step boundary over the steps between them."""


def read(ctx):
    return 1e3 * ctx["window_s"] / (len(ctx["events"]) * ctx["log_step"])
