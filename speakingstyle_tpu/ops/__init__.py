"""Operator lowerings (XLA compositions and Pallas TPU kernels)."""


def on_tpu() -> bool:
    """Whether programs compile for a TPU: the Pallas kernels lower through
    Mosaic there and take their jnp reference / interpreter elsewhere. A
    backend that cannot initialise raises here rather than being read as
    "not a TPU"."""
    import jax

    return jax.default_backend() == "tpu"
