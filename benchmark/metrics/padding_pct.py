"""Share of the mel frames the device was handed that were padding: one
cycle's batch shapes against the deck's real frames."""


def read(ctx):
    padded = sum(b * t for b, t, _ in ctx["shapes"][: ctx["log_step"]])
    return 100.0 * (1.0 - ctx["frames_per_cycle"] / padded)
