"""The plain reference of the ``ljspeech`` configuration: what ``correct``
compares the timed path with. Straightforward float32 ``jax.numpy`` at
``highest`` precision; weights from the seed by its own generator.

``train_steps`` follows the first optimizer steps (loss, gradients in blocks
of rows, clip, Adam). The equations live in ``benchmark/reference/`` and are
shared by the configurations that share the model's code; this file binds
them to this configuration, and the harness finds it by the ``reference``
key of ``ljspeech.json``.
"""

from benchmark.reference import fs2

hyper = fs2.hyper
init_params = fs2.init_params
init_batch_stats = fs2.init_batch_stats
train_steps = fs2.train_steps
