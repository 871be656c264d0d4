"""Unit tests for core ops: masking, PE, length regulation, bucketize."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from speakingstyle_tpu.ops.length_regulator import length_regulate, predicted_durations
from speakingstyle_tpu.ops.masking import length_to_mask, masked_mean
from speakingstyle_tpu.ops.positional import sinusoid_position_table
from speakingstyle_tpu.ops.quantize import bucketize, make_bins


def test_length_to_mask():
    m = length_to_mask(jnp.array([3, 1]), 4)
    assert m.tolist() == [[False, False, False, True], [False, True, True, True]]


def test_masked_mean_matches_select_mean():
    v = jnp.array([1.0, 2.0, 3.0, 100.0])
    keep = jnp.array([True, True, True, False])
    assert float(masked_mean(v, keep)) == pytest.approx(2.0)


def test_sinusoid_table_reference_formula():
    # reference: transformer/Models.py:10-30
    t = sinusoid_position_table(8, 6)
    pos, j = 3, 4
    expected_sin = np.sin(pos / np.power(10000, 2 * (j // 2) / 6))
    assert t[pos, j] == pytest.approx(expected_sin, abs=1e-6)
    expected_cos = np.cos(pos / np.power(10000, 2 * (5 // 2) / 6))
    assert t[pos, 5] == pytest.approx(expected_cos, abs=1e-6)
    assert np.all(t[0, 0::2] == 0.0) and np.all(t[0, 1::2] == 1.0)


def test_length_regulate_expands_per_duration():
    # phoneme i repeated durations[i] times, like the reference Python loop
    # (reference: model/modules.py:174-197)
    x = jnp.arange(1, 4, dtype=jnp.float32)[None, :, None]  # [1,3,1] values 1,2,3
    d = jnp.array([[2, 0, 3]])
    frames, mel_lens, pad = length_regulate(x, d, 7)
    assert mel_lens.tolist() == [5]
    assert frames[0, :, 0].tolist() == [1, 1, 3, 3, 3, 0, 0]
    assert pad[0].tolist() == [False] * 5 + [True] * 2


def test_length_regulate_truncates_to_budget():
    x = jnp.ones((1, 2, 4))
    d = jnp.array([[5, 5]])
    frames, mel_lens, pad = length_regulate(x, d, 6)
    assert mel_lens.tolist() == [6]
    assert not bool(pad.any())


def test_length_regulate_jits():
    f = jax.jit(length_regulate, static_argnums=2)
    x = jnp.ones((2, 3, 4))
    d = jnp.array([[1, 2, 3], [0, 0, 1]])
    frames, mel_lens, pad = f(x, d, 8)
    assert frames.shape == (2, 8, 4)
    assert mel_lens.tolist() == [6, 1]


def test_predicted_durations_round_then_scale():
    # round(exp(logd)-1) * control, clamped at 0 (reference: modules.py:137-144)
    logd = jnp.log(jnp.array([[4.0, 1.0, 0.1]]))  # exp-1 = 3, 0, -0.9
    mask = jnp.array([[False, False, False]])
    assert predicted_durations(logd, mask, 1.0).tolist() == [[3, 0, 0]]
    assert predicted_durations(logd, mask, 2.0).tolist() == [[6, 0, 0]]
    mask2 = jnp.array([[False, False, True]])
    assert predicted_durations(logd, mask2, 1.0)[0, 2] == 0


def test_bucketize_matches_torch_semantics():
    # torch.bucketize(v, [0,1,2]) == [0,0,1,1,2,3] for v=[-1,0,.5,1,2,3]
    bins = np.array([0.0, 1.0, 2.0], np.float32)
    v = jnp.array([-1.0, 0.0, 0.5, 1.0, 2.0, 3.0])
    assert bucketize(v, bins).tolist() == [0, 0, 1, 1, 2, 3]


def test_make_bins():
    lin = make_bins(0.0, 10.0, 6, "linear")
    assert lin.shape == (5,) and lin[0] == 0.0 and lin[-1] == 10.0
    log = make_bins(1.0, 100.0, 5, "log")
    assert log[0] == pytest.approx(1.0) and log[-1] == pytest.approx(100.0)


def test_grad_reverse():
    """Identity forward; -alpha * g backward (reference: model/blocks.py:7-40)."""
    import jax
    import jax.numpy as jnp

    from speakingstyle_tpu.ops.grad_reverse import grad_reverse

    x = jnp.asarray([1.0, -2.0, 3.0])
    np.testing.assert_array_equal(np.asarray(grad_reverse(x, 0.7)), np.asarray(x))

    g = jax.grad(lambda x: (grad_reverse(x, 0.7) ** 2).sum())(x)
    np.testing.assert_allclose(np.asarray(g), -0.7 * 2 * np.asarray(x), rtol=1e-6)
    # jits and composes with other grads
    g2 = jax.jit(jax.grad(lambda x: grad_reverse(x, 2.0).sum() + x.sum()))(x)
    np.testing.assert_allclose(np.asarray(g2), np.full(3, -2.0 + 1.0), rtol=1e-6)


# ---------------------------------------------------------------------------
# dropout impls (ops/dropout.py)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["bernoulli", "bits16", "hash"])
def test_dropout_impls(impl):
    """Every mask impl: correct keep rate, inverted scaling, determinism
    per key, decorrelation across keys, and exact zeros at drops."""
    import jax

    from speakingstyle_tpu.ops.dropout import dropout, keep_mask

    rate = 0.2
    shape = (65, 97, 33)  # odd element count: exercises the bits16 tail slice
    k1, k2 = jax.random.PRNGKey(3), jax.random.PRNGKey(4)
    m1 = np.asarray(keep_mask(k1, rate, shape, impl))
    m1b = np.asarray(keep_mask(k1, rate, shape, impl))
    m2 = np.asarray(keep_mask(k2, rate, shape, impl))
    assert m1.shape == shape and m1.dtype == bool
    np.testing.assert_array_equal(m1, m1b)  # deterministic per key
    assert m1.mean() == pytest.approx(1 - rate, abs=0.01)
    assert (m1 != m2).mean() > 0.2  # different keys -> different masks

    x = jnp.asarray(np.random.default_rng(0).standard_normal(shape),
                    jnp.float32)
    y = np.asarray(dropout(x, rate, k1, impl=impl))
    np.testing.assert_allclose(
        y[m1], np.asarray(x)[m1] / (1 - rate), rtol=1e-6
    )
    assert (y[~m1] == 0).all()

    # grad flows only through kept elements, scaled
    g = jax.grad(lambda x_: jnp.sum(dropout(x_, rate, k1, impl=impl)))(x)
    np.testing.assert_allclose(
        np.asarray(g), m1.astype(np.float32) / (1 - rate), rtol=1e-6
    )


def test_dropout_hash_no_spatial_structure():
    """The counter-hash mask must not correlate along any axis (the risk
    of an iota-based stream): neighboring elements' keep decisions are
    statistically independent."""
    import jax

    from speakingstyle_tpu.ops.dropout import keep_mask

    m = np.asarray(
        keep_mask(jax.random.PRNGKey(0), 0.5, (256, 256), "hash")
    ).astype(np.int8)
    # lag-1 agreement along each axis ~ 0.5 for independent bits
    for ax in (0, 1):
        a = np.take(m, range(0, m.shape[ax] - 1), axis=ax)
        b = np.take(m, range(1, m.shape[ax]), axis=ax)
        assert abs((a == b).mean() - 0.5) < 0.02
    # and across keys
    m2 = np.asarray(
        keep_mask(jax.random.PRNGKey(1), 0.5, (256, 256), "hash")
    ).astype(np.int8)
    assert abs((m == m2).mean() - 0.5) < 0.02


# ---------------------------------------------------------------------------
# conv1d lowerings (ops/conv.py, ops/pallas_conv.py) — fast parity gate
# ---------------------------------------------------------------------------

def _conv_ref(x, w, b, dilation=1):
    import jax

    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(1,), padding="SAME",
        rhs_dilation=(dilation,), dimension_numbers=("NWC", "WIO", "NWC"),
    )
    return y + b


@pytest.mark.parametrize("k,dilation", [(1, 1), (3, 1), (9, 1), (3, 2), (5, 3)])
def test_conv1d_impl_parity(k, dilation):
    """unfold and pallas lowerings match lax.conv exactly (fwd + grad)."""
    import jax

    from speakingstyle_tpu.ops.conv import conv1d_unfold
    from speakingstyle_tpu.ops.pallas_conv import fused_conv1d

    rng = np.random.default_rng(k * 10 + dilation)
    x = jnp.asarray(rng.standard_normal((2, 23, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, 8, 12)) * 0.2, jnp.float32)
    b = jnp.asarray(rng.standard_normal(12) * 0.1, jnp.float32)

    ref = _conv_ref(x, w, b, dilation)
    np.testing.assert_allclose(
        np.asarray(conv1d_unfold(x, w, b, dilation=dilation)), np.asarray(ref),
        atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(fused_conv1d(x, w, b, dilation=dilation, interpret=True)),
        np.asarray(ref), atol=1e-5,
    )

    g_ref = jax.grad(lambda x_: jnp.sum(_conv_ref(x_, w, b, dilation) ** 2))(x)
    g_unf = jax.grad(
        lambda x_: jnp.sum(conv1d_unfold(x_, w, b, dilation=dilation) ** 2)
    )(x)
    g_pal = jax.grad(
        lambda x_: jnp.sum(
            fused_conv1d(x_, w, b, dilation=dilation, interpret=True) ** 2
        )
    )(x)
    np.testing.assert_allclose(np.asarray(g_unf), np.asarray(g_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref), atol=1e-4)


def test_fused_conv_relu_ln_matches_composed():
    """The fully fused pallas path == conv -> relu -> LayerNorm, fwd + grads
    wrt every operand."""
    import jax

    from speakingstyle_tpu.ops.pallas_conv import (
        _reference_fused,
        fused_conv_relu_ln,
    )

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((2, 19, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 8, 16)) * 0.2, jnp.float32)
    b = jnp.asarray(rng.standard_normal(16) * 0.1, jnp.float32)
    s = jnp.asarray(rng.standard_normal(16), jnp.float32)
    sb = jnp.asarray(rng.standard_normal(16), jnp.float32)

    got = fused_conv_relu_ln(x, w, b, s, sb, interpret=True)
    want = _reference_fused(x, w, b, s, sb, 1, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    g_got = jax.grad(
        lambda a: jnp.sum(
            fused_conv_relu_ln(a[0], a[1], a[2], a[3], a[4], interpret=True) ** 2
        )
    )((x, w, b, s, sb))
    g_want = jax.grad(
        lambda a: jnp.sum(_reference_fused(a[0], a[1], a[2], a[3], a[4], 1, True) ** 2)
    )((x, w, b, s, sb))
    for gg, gw in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(gw), atol=1e-4)


def test_fused_conv_bwd_modes_agree():
    """Both backward modes (analytic default, recompute A/B path) produce
    the same gradients through the explicit ``bwd_mode`` argument."""
    import jax

    from speakingstyle_tpu.ops.pallas_conv import fused_conv1d

    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((2, 16, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 8, 12)) * 0.1, jnp.float32)
    grads = [
        np.asarray(
            jax.grad(
                lambda x_: jnp.sum(
                    fused_conv1d(
                        x_, w, None, interpret=True, bwd_mode=m
                    ) ** 2
                )
            )(x)
        )
        for m in ("analytic", "recompute")
    ]
    np.testing.assert_allclose(grads[0], grads[1], atol=1e-5)


def test_fused_conv_bwd_modes_agree_bf16():
    """Analytic-vs-recompute gradient parity with bf16 storage and ReLU.

    Tolerance note: the analytic backward rebuilds the ReLU mask from the
    activation residual *as stored in bf16* with a strictly-positive
    threshold (finfo(bf16).tiny), while recompute mode re-derives it from
    an f32 recompute. The two masks can only disagree on elements whose
    pre-activation magnitude is below bf16's smallest normal (~1.2e-38) —
    probability ~0 for these inputs — so the remaining difference is pure
    bf16 rounding noise on the matching elements, bounded by the loose
    tolerances here (bf16 has ~8 mantissa bits => ~0.4% relative)."""
    import jax

    from speakingstyle_tpu.ops.pallas_conv import fused_conv1d

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((2, 16, 8)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((3, 8, 12)) * 0.1, jnp.bfloat16)
    grads = [
        np.asarray(
            jax.grad(
                lambda x_: jnp.sum(
                    fused_conv1d(
                        x_, w, None, relu=True, interpret=True, bwd_mode=m
                    ).astype(jnp.float32) ** 2
                )
            )(x),
            np.float32,
        )
        for m in ("analytic", "recompute")
    ]
    np.testing.assert_allclose(grads[0], grads[1], rtol=2e-2, atol=5e-2)
    # the fix this guards: gradients flow wherever the STORED activation
    # is a normal positive — analytic mode must not zero more elements
    # than a strictly-positive stored value implies
    y = np.asarray(
        fused_conv1d(x, w, None, relu=True, interpret=True), np.float32
    )
    dy_analytic = np.asarray(
        jax.grad(
            lambda x_: jnp.sum(
                fused_conv1d(
                    x_, w, None, relu=True, interpret=True,
                    bwd_mode="analytic",
                ).astype(jnp.float32).sum(axis=(0, 1))[0]
            )
        )(x),
        np.float32,
    )
    assert np.any(y > 0) and np.any(dy_analytic != 0)


def test_fused_conv_relu_ln_grads_lane_aligned():
    """Gradient parity at a lane-aligned (cout=128) width: this is the
    config where the REAL kernel path runs (the cout=16 test above trips
    the lane-alignment fallback to the jnp reference), so it exercises the
    want_act second pallas output + analytic backward wiring in CI."""
    import jax

    from speakingstyle_tpu.ops.pallas_conv import (
        _reference_fused,
        fused_conv_relu_ln,
    )

    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((2, 24, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 128, 128)) * 0.05, jnp.float32)
    b = jnp.asarray(rng.standard_normal(128) * 0.1, jnp.float32)
    s = jnp.asarray(rng.standard_normal(128), jnp.float32)
    sb = jnp.asarray(rng.standard_normal(128), jnp.float32)

    got = fused_conv_relu_ln(x, w, b, s, sb, interpret=True)
    want = _reference_fused(x, w, b, s, sb, 1, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    g_got = jax.grad(
        lambda a: jnp.sum(
            fused_conv_relu_ln(a[0], a[1], a[2], a[3], a[4], interpret=True)
            ** 2
        )
    )((x, w, b, s, sb))
    g_want = jax.grad(
        lambda a: jnp.sum(
            _reference_fused(a[0], a[1], a[2], a[3], a[4], 1, True) ** 2
        )
    )((x, w, b, s, sb))
    for gg, gw in zip(g_got, g_want):
        np.testing.assert_allclose(
            np.asarray(gg), np.asarray(gw), rtol=2e-4, atol=2e-4
        )


def test_conv1d_module_tree_matches_nn_conv():
    """Conv1d's param entry is nn.Conv-identical for every impl."""
    import flax.linen as nn
    import jax

    from speakingstyle_tpu.ops.conv import Conv1d

    x = jnp.zeros((1, 11, 8), jnp.float32)
    want = jax.tree_util.tree_map(
        jnp.shape,
        nn.Conv(12, kernel_size=(5,), padding="SAME").init(
            jax.random.PRNGKey(0), x
        )["params"],
    )
    for impl in ("xla", "unfold", "pallas"):
        got = jax.tree_util.tree_map(
            jnp.shape,
            Conv1d(12, kernel_size=5, impl=impl).init(
                jax.random.PRNGKey(0), x
            )["params"],
        )
        assert got == want, impl


@pytest.mark.parametrize("L,H,D", [(23, 4, 16), (130, 2, 8)])
def test_fused_mha_matches_einsum(L, H, D):
    """The fused attention kernel (interpret mode) matches the einsum
    reference — forward and q/k/v gradients — including padding-mask
    handling and the T -> multiple-of-128 internal padding."""
    import jax

    from speakingstyle_tpu.ops.pallas_attention import _reference_mha, fused_mha

    rng = np.random.default_rng(L + H + D)
    B = 2
    q = jnp.asarray(rng.standard_normal((B, L, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, L, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, L, H, D)), jnp.float32)
    lens = rng.integers(L // 2, L + 1, B)
    mask = jnp.asarray(np.arange(L)[None] >= lens[:, None])
    real = jnp.where(mask, 0.0, 1.0)[:, :, None, None]

    sm = 1.0 / np.sqrt(D)
    out = fused_mha(q, k, v, mask, interpret=True)
    ref = _reference_mha(q, k, v, mask, sm, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(out * real), np.asarray(ref * real), atol=1e-5
    )

    def loss(f):
        return lambda q_, k_, v_: jnp.sum(jnp.square(f(q_, k_, v_) * real))

    g_fused = jax.grad(
        loss(lambda q_, k_, v_: fused_mha(q_, k_, v_, mask, interpret=True)),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_ref = jax.grad(
        loss(lambda q_, k_, v_: _reference_mha(q_, k_, v_, mask, sm, jnp.float32)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_fused, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_fused_mha_unsupported_shapes_fall_back():
    """Head dim > 128 / not multiple of 8 and very long T use the einsum
    reference instead of the kernel (exact equality — same code path)."""
    from speakingstyle_tpu.ops.pallas_attention import (
        _reference_mha,
        fused_mha,
        supported,
    )

    assert not supported(600, 20)      # D % 8 != 0
    assert not supported(600, 256)     # D > lane width
    assert not supported(2000, 64)     # T too long for VMEM scores
    assert supported(600, 32) and supported(1000, 128)
    # sub-4-byte dtypes pack 2 rows/sublane: D must be a multiple of 16
    assert not supported(600, 24, jnp.bfloat16)
    assert not supported(600, 8, jnp.bfloat16)
    assert supported(600, 32, jnp.bfloat16)
    assert supported(600, 24)  # ...but f32 allows %8

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 9, 2, 20)), jnp.float32)
    mask = jnp.zeros((2, 9), bool)
    out = fused_mha(q, q, q, mask, interpret=True)
    ref = _reference_mha(q, q, q, mask, 1.0 / np.sqrt(20), jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=0)


def test_fused_mha_on_tpu_never_falls_back_silently(monkeypatch):
    """When the kernel would be COMPILED (a TPU backend, or
    interpret=False), a head dim it cannot tile raises at trace time —
    before any pallas_call — instead of quietly materialising
    [B, H, T, T]; only T > MAX_T keeps the quiet einsum path."""
    from speakingstyle_tpu.ops import pallas_attention as pa

    rng = np.random.default_rng(0)
    mask = jnp.zeros((2, 9), bool)
    q = jnp.asarray(rng.standard_normal((2, 9, 2, 20)), jnp.float32)
    monkeypatch.setattr(pa, "on_tpu", lambda: True)
    with pytest.raises(ValueError, match="cannot tile head dim 20"):
        pa.fused_mha(q, q, q, mask)
    with pytest.raises(ValueError, match="attention_kernel: einsum"):
        pa.fused_mha(q, q, q, mask, interpret=False)
    # past MAX_T the einsum reference IS the implementation, on any backend
    T = pa.MAX_T + 1
    q = jnp.asarray(rng.standard_normal((1, T, 1, 8)), jnp.float32)
    mask = jnp.zeros((1, T), bool)
    out = pa.fused_mha(q, q, q, mask)
    ref = pa._reference_mha(q, q, q, mask, 1.0 / np.sqrt(8), jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=0)


def test_model_attention_kernel_knob():
    """attention_kernel="fused" at the model level: same param tree as
    einsum (the kernel is parameter-free) and matching outputs on CPU
    (where the fused path falls back to the identical einsum reference)."""
    import dataclasses

    import jax

    from tests.test_models import make_batch, tiny_config
    from speakingstyle_tpu.models.fastspeech2 import FastSpeech2

    cfg_e = tiny_config(attention_kernel="einsum")  # default is now fused
    cfg_f = dataclasses.replace(
        cfg_e, model=dataclasses.replace(cfg_e.model, attention_kernel="fused")
    )
    texts, src_lens, mels, mel_lens, p, e, d = make_batch()
    speakers = jnp.zeros((2,), jnp.int32)
    kwargs = dict(
        mels=mels, mel_lens=mel_lens, max_mel_len=18,
        p_targets=p, e_targets=e, d_targets=d, deterministic=True,
    )
    outs = {}
    trees = {}
    for label, cfg in (("einsum", cfg_e), ("fused", cfg_f)):
        m = FastSpeech2(config=cfg, pitch_stats=(-2, 8), energy_stats=(-1, 9))
        variables = m.init(
            jax.random.PRNGKey(0), speakers, texts, src_lens, **kwargs
        )
        trees[label] = jax.tree_util.tree_map(jnp.shape, variables["params"])
        out, _ = m.apply(
            variables, speakers, texts, src_lens, **kwargs,
            mutable=["batch_stats"],
        )
        outs[label] = np.asarray(out["mel"])
    assert trees["einsum"] == trees["fused"]
    np.testing.assert_allclose(outs["einsum"], outs["fused"], atol=1e-5)
