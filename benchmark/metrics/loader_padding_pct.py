"""Share of the mel frames handed to the step that were padding, by the
program's own counters (``train_frames_real_total`` over
``train_frames_padded_total``) in the window's events."""


def read(ctx):
    ev = ctx["events"]
    if not ev or any("frames_padded" not in e for e in ev):
        return None
    return 100.0 * (1.0 - sum(e["frames_real"] for e in ev)
                    / sum(e["frames_padded"] for e in ev))
