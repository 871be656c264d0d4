"""Seconds the backend spent compiling before the window opened."""


def read(ctx):
    return ctx["compiles_open"]["compile_seconds"]
