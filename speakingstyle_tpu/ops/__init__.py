"""Operator lowerings (XLA compositions and Pallas TPU kernels)."""


def on_tpu() -> bool:
    """Whether programs compile for a TPU: the Pallas kernels lower through
    Mosaic there and take their jnp reference / interpreter elsewhere. A
    backend that cannot initialise raises here rather than being read as
    "not a TPU"."""
    import jax

    return jax.default_backend() == "tpu"


def launches(closed_jaxpr, names) -> dict:
    """Launches of named Pallas kernels in a program: ``names`` maps a
    variant to its kernels' names, the result a variant to how many
    ``pallas_call``s carry one of them. What is dead goes first: a
    rematerialised region lists its whole forward until what its backward
    never reads is taken out."""
    from jax.interpreters import partial_eval as pe

    jaxpr = closed_jaxpr.jaxpr
    live = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))[0]
    found = dict.fromkeys(names, 0)

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params.get("name")
                for variant, kernels in names.items():
                    found[variant] += name in kernels
                continue
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) else [value]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(live)
    return found
