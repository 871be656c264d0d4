"""Longest cycle of the window minus the median cycle."""
import statistics


def read(ctx):
    c = ctx["cycles_s"]
    if not c:
        return None
    return 1e3 * (max(c) - statistics.median(c))
