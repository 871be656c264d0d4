"""Milliseconds set-up needed to ``np.load`` one batch's feature files from
the working directory: what the file system charges the loader."""


def read(ctx):
    return ctx.get("corpus_read_ms")
