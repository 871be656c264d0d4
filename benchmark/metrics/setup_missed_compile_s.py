"""Backend-compile seconds before the window opened that the persistent
cache did not serve (the program's
``jax_backend_compile_missed_seconds_total``): 0 on a warm start, a
program's whole compile where it compiled again. None where the program
keeps no such counter."""


def read(ctx):
    return ctx["compiles_open"]["missed_compile_seconds"]
