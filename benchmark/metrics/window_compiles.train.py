"""Backend compilations between the window's open and close."""


def read(ctx):
    return ctx["compiles_close"]["compiles"] - ctx["compiles_open"]["compiles"]
