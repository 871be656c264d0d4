"""What the comparison needs of the program's own ``decoder_lm`` step that
the recorder around it (``train_cell.StepRecorder``) does not keep: the
router's choices of the first timed-path step. (A configuration's bindings
import nothing of the program; the harness stands beside it, as
``train_cell`` does.)

``keep_choices`` is called before ``train_cell`` puts its recorder around
``trainer.make_train_step`` and stands under it: the step the loop drives,
its kernels and its compute dtype, and nothing built beside it."""

_kept = []
_original = []


class _Keeper:
    """Stands where ``make_train_step``'s jitted function stands and keeps
    the first call's ``losses["_choices"]`` (still on the device)."""

    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def __call__(self, state, arrays, rng):
        new_state, losses = self.inner(state, arrays, rng)
        if not _kept and "_choices" in losses:
            _kept.append(losses["_choices"])
        return new_state, losses


def keep_choices():
    """From now on, the first step of every jitted train step that
    ``trainer.make_train_step`` makes leaves its choices for ``choices``."""
    from speakingstyle_tpu.training import trainer

    if _original:
        return
    made = trainer.make_train_step
    _original.append(made)

    def make(*a, **k):
        _kept.clear()
        return _Keeper(made(*a, **k))

    trainer.make_train_step = make


def choices() -> list:
    """Each layer's choices ``[B, T, k]`` (numpy) as the first step of the
    timed path made them, fetched before the device is cleared; the
    trainer gets its own ``make_train_step`` back."""
    import jax
    import numpy as np

    from speakingstyle_tpu.training import trainer

    if _original:
        trainer.make_train_step = _original.pop()
    if not _kept:
        raise LookupError("no step ran under keep_choices")
    if not isinstance(_kept[0], np.ndarray):
        _kept[0] = np.asarray(jax.device_get(_kept[0]))
    return list(_kept[0])
