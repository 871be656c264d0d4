"""Operator lowerings (XLA compositions and Pallas TPU kernels)."""


def on_tpu() -> bool:
    """Whether programs compile for a TPU: the Pallas kernels lower through
    Mosaic there and take their jnp reference / interpreter elsewhere. A
    backend that cannot initialise raises here rather than being read as
    "not a TPU"."""
    import jax

    return jax.default_backend() == "tpu"


def live_equations(closed_jaxpr):
    """(scope, equation) for every equation of a program that its results
    need, each once however often its loop runs: the equations inside a
    loop body, a remat or call region stand in place of the equation that
    holds them (a kernel's body is not walked), ``scope`` the names of the
    scopes they stand in, outermost first, joined by ``/``. What is dead
    goes first: a rematerialised region lists its whole forward until what
    its backward never reads is taken out."""
    from jax.interpreters import partial_eval as pe

    jaxpr = closed_jaxpr.jaxpr
    live = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))[0]

    def walk(j, outer):
        for eqn in j.eqns:
            scope = "/".join(filter(None, (outer, str(eqn.source_info.name_stack))))
            yield scope, eqn
            if eqn.primitive.name == "pallas_call":
                continue
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) else [value]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from walk(sub, scope)

    return walk(live, "")


def launches(closed_jaxpr, names) -> dict:
    """Launches of named Pallas kernels in a program (``live_equations``):
    ``names`` maps a variant to its kernels' names, the result a variant to
    how many ``pallas_call``s carry one of them."""
    found = dict.fromkeys(names, 0)
    for _, eqn in live_equations(closed_jaxpr):
        if eqn.primitive.name == "pallas_call":
            name = eqn.params.get("name")
            for variant, kernels in names.items():
                found[variant] += name in kernels
    return found
