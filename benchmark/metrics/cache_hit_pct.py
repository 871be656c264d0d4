"""Share of set-up's compile requests the persistent cache served."""


def read(ctx):
    c = ctx["compiles_open"]
    if not c["cache_requests"]:
        return None
    return 100.0 * c["cache_hits"] / c["cache_requests"]
