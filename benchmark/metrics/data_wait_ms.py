"""Mean time a step blocked on the prefetcher, from the window's
``train_step`` events."""


def read(ctx):
    ev = ctx["events"]
    return 1e3 * sum(e["data_wait_s"] for e in ev) / len(ev)
