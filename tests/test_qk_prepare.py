"""``ops/qk_prepare`` in the Pallas interpreter on the CPU against the passes
it fuses, as the model runs them by parts (``mellum.rms_norm`` ->
``mellum.apply_rope`` -> transpose): forward and every gradient in float32
and bfloat16, the ``scale`` gradient's sum over a reduction long enough for
a narrow accumulator to show, and the shapes that go to the parts instead."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from speakingstyle_tpu.models import mellum
from speakingstyle_tpu.ops.qk_prepare import launches, qk_prepare

EPS = 1e-6


def by_parts(x, cos, sin, scale, heads):
    """The parent's three passes on ``x`` widened to float32."""
    B, T, _ = x.shape
    y = x.astype(jnp.float32).reshape(B, T, heads, -1)
    if scale is not None:
        y = mellum.rms_norm(y, scale, EPS)
    return mellum.apply_rope(y, cos, sin).transpose(0, 2, 1, 3)


def operands(B, T, H, D, dtype, seed=0):
    rng = np.random.default_rng(seed)
    angles = rng.uniform(-np.pi, np.pi, (T, D // 2))
    angles = np.concatenate([angles, angles], axis=1)
    return (jnp.asarray(rng.standard_normal((B, T, H * D)), dtype),
            jnp.asarray(np.cos(angles), jnp.float32),
            jnp.asarray(np.sin(angles), jnp.float32),
            jnp.asarray(1 + 0.2 * rng.standard_normal(D), jnp.float32),
            jnp.asarray(rng.standard_normal((B, H, T, D)), dtype))


def both(x, cos, sin, scale, w, heads, dtype=None):
    """(value, gradients) of one weighted sum through the interpreted kernels
    (their output in ``dtype``) and through the parts in float32 (of ``x``
    widened first, so that its gradient comes back unrounded); gradients of
    ``x`` and, if any, ``scale``."""
    wrt = (0,) if scale is None else (0, 1)

    def through(fn, x):
        def loss(x, scale):
            return jnp.sum(w.astype(jnp.float32) * fn(x, scale).astype(jnp.float32))
        return jax.value_and_grad(loss, wrt)(x, scale)

    return (through(lambda x, s: qk_prepare(x, cos, sin, s, heads=heads, eps=EPS,
                                            dtype=dtype, interpret=True), x),
            through(lambda x, s: by_parts(x, cos, sin, s, heads),
                    x.astype(jnp.float32)))


@pytest.mark.parametrize("normed", [True, False], ids=["norm", "plain"])
@pytest.mark.parametrize("heads", [4, 1], ids=["heads_4", "gqa_1"])
def test_kernels_are_the_parts_in_float32(heads, normed):
    x, cos, sin, scale, w = operands(2, 256, heads, 128, jnp.float32)
    scale = scale if normed else None
    (a, ga), (b, gb) = both(x, cos, sin, scale, w, heads)
    out = qk_prepare(x, cos, sin, scale, heads=heads, eps=EPS, interpret=True)
    assert out.shape == (2, heads, 256, 128) and out.dtype == x.dtype
    np.testing.assert_allclose(out, by_parts(x, cos, sin, scale, heads),
                               rtol=1e-5, atol=1e-5)
    assert abs(float(a - b)) <= 1e-5 * abs(float(b)) + 1e-3
    assert len(ga) == 1 + normed
    for g, h in zip(ga, gb):
        assert g.shape == h.shape and g.dtype == h.dtype == jnp.float32
        np.testing.assert_allclose(g, h, rtol=1e-5,
                                   atol=1e-5 * float(jnp.max(jnp.abs(h))))


@pytest.mark.parametrize("wide", [True, False], ids=["from_float32", "from_bfloat16"])
@pytest.mark.parametrize("normed", [True, False], ids=["norm", "plain"])
@pytest.mark.parametrize("heads", [4, 1], ids=["heads_4", "gqa_1"])
def test_kernels_to_bfloat16_are_one_rounding_from_the_float32_parts(heads, normed, wide):
    """A bfloat16 output from float32 inside, one rounding at each output:
    every element within half a bfloat16 step (2^-8 of its size, with a
    hundredth more for float32's own order of operations) of the parts
    computed in float32 on the same operands, be ``x`` the projection's
    float32 accumulators (as the model hands them) or bfloat16 itself.
    ``x``'s gradient comes back in ``x``'s dtype holding bfloat16 values;
    ``scale``'s gradient is float32 and no rounding from it."""
    x, cos, sin, scale, w = operands(2, 256, heads, 128,
                                     jnp.float32 if wide else jnp.bfloat16)
    scale, w = scale if normed else None, w.astype(jnp.bfloat16)
    (_, ga), (_, gb) = both(x, cos, sin, scale, w, heads, jnp.bfloat16)
    out = qk_prepare(x, cos, sin, scale, heads=heads, eps=EPS, dtype=jnp.bfloat16,
                     interpret=True)
    want = by_parts(x, cos, sin, scale, heads)
    assert out.dtype == jnp.bfloat16 and ga[0].dtype == x.dtype
    assert (ga[0] == ga[0].astype(jnp.bfloat16).astype(x.dtype)).all()

    def one_rounding(got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want)
        assert want.dtype == np.float32
        assert np.all(np.abs(got - want) <= 1.01 * 2.0 ** -8 * np.abs(want) + 1e-6)

    one_rounding(out, want)
    one_rounding(ga[0], gb[0])
    if normed:
        assert ga[1].dtype == jnp.float32
        np.testing.assert_allclose(ga[1], gb[1], rtol=1e-5,
                                   atol=1e-5 * float(jnp.max(jnp.abs(gb[1]))))


def test_scale_gradient_holds_over_a_long_reduction():
    """65,536 rows of 128 lanes in bfloat16, every term of ``scale``'s
    gradient of one sign (the cotangent is the output itself, so lane ``j``
    sums ``y_j^2``-like terms): a sum carried in bfloat16 stalls near 256
    terms, and partial sums rounded to bfloat16 before they are added, or a
    running sum rounded so a step, move single lanes by 0.025% and 0.038%
    (both tried). Norm within 0.3% of the float32 parts, every lane within
    0.003% (float32 sums read 0.00008%)."""
    B, T, H, D = 2, 4096, 8, 128
    x, cos, sin, scale, _ = operands(B, T, H, D, jnp.bfloat16, seed=3)
    assert B * T * H >= 65536

    def grad(fn):
        def loss(scale):
            y = fn(scale).astype(jnp.float32)
            return jnp.sum(jax.lax.stop_gradient(y).astype(jnp.bfloat16)
                           .astype(jnp.float32) * y)
        return jax.grad(loss)(scale)

    got = grad(lambda s: qk_prepare(x, cos, sin, s, heads=H, eps=EPS, interpret=True))
    want = grad(lambda s: by_parts(x, cos, sin, s, H))
    assert got.dtype == jnp.float32
    assert float(jnp.min(jnp.abs(want))) > 100.0     # 65,536 terms of one sign
    norms = float(jnp.linalg.norm(got)), float(jnp.linalg.norm(want))
    assert abs(norms[0] - norms[1]) < 3e-3 * norms[1]
    assert float(jnp.max(jnp.abs(got - want) / jnp.abs(want))) < 3e-5


@pytest.mark.parametrize("interpret", [None, True], ids=["jnp", "interpreted"])
@pytest.mark.parametrize("T,D", [(32, 64), (33, 128)], ids=["head_64", "odd_length"])
def test_shapes_the_tiles_do_not_divide_trace_to_the_parents_operations(T, D, interpret):
    """A head of 64 lanes, a length of 33: no kernel whatever ``interpret``
    says, and the model's parts trace to the very equations the parent's
    ``SelfAttention`` ran (norm, rotation, transpose, in its dtype)."""
    x, cos, sin, scale, _ = operands(2, T, 2, D, jnp.float32)

    def parent(x, scale):
        y = x.astype(jnp.bfloat16).reshape(2, T, 2, D)   # the projection's own rounding
        y = mellum.rms_norm(y, scale, EPS)
        return mellum.apply_rope(y, cos, sin).transpose(0, 2, 1, 3)

    def program(x, scale):
        return qk_prepare(x, cos, sin, scale, heads=2, eps=EPS, interpret=interpret,
                          dtype=jnp.bfloat16, otherwise=mellum.heads_by_parts)

    got, want = jax.make_jaxpr(program)(x, scale), jax.make_jaxpr(parent)(x, scale)
    assert "pallas_call" not in str(got)
    assert str(got) == str(want)
    assert launches(jax.make_jaxpr(jax.grad(
        lambda x, s: jnp.sum(program(x, s).astype(jnp.float32))))(x, scale)) \
        == {"norm": 0, "plain": 0}


def test_operands_that_do_not_belong_together_are_refused():
    x, cos, sin, _, _ = operands(2, 32, 2, 128, jnp.float32)
    with pytest.raises(ValueError, match="heads"):
        qk_prepare(x, cos, sin, heads=4, interpret=True)
    with pytest.raises(ValueError, match="tables"):
        qk_prepare(x, cos[:16], sin[:16], heads=2, interpret=True)
    with pytest.raises(ValueError, match="by parts"):    # off a TPU, nothing to run instead
        qk_prepare(x, cos, sin, heads=2)
