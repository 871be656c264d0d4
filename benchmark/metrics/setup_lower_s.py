"""Seconds jax spent lowering jaxprs to MLIR modules before the window
opened (the program's ``jax_lower_seconds_total``). None where the program
keeps no such counter."""


def read(ctx):
    return ctx["compiles_open"]["lower_seconds"]
