"""Seconds jax spent tracing functions to jaxprs before the window opened
(the program's ``jax_trace_seconds_total``: the outermost trace of a thread
only). None where the program keeps no such counter."""


def read(ctx):
    return ctx["compiles_open"]["trace_seconds"]
