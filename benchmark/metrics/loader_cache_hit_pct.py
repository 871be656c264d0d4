"""Share of the window's samples that the loader served from host memory
and not from their feature files: the program's ``loader_cache_hits`` over
hits + misses in the window's ``train_step`` events. 100 once the corpus is
held; where it outgrows the budget, the share of it that found room."""


def read(ctx):
    ev = ctx["events"]
    fields = ("loader_cache_hits", "loader_cache_misses")
    if not ev or any(f not in e for e in ev for f in fields):
        return None
    hits, misses = (sum(e[f] for e in ev) for f in fields)
    return 100.0 * hits / (hits + misses) if hits + misses else None
