"""Fused conv1d (+bias +ReLU +LayerNorm) Pallas TPU kernel.

The hot conv patterns of the model (SURVEY.md §2.1):
  * reference-encoder stack: conv k=3 @1024ch -> ReLU -> LayerNorm
    (reference: model/modules.py:361-379)
  * conv-FFN first half: conv k=9 256->1024 -> ReLU
    (reference: transformer/SubLayers.py:60-93)

One kernel serves both: a K-tap matmul accumulation in f32 over a VMEM
tile of the time axis, with the elementwise epilogue (bias, ReLU, and the
channel LayerNorm) applied in-register before the single HBM write-back.
Versus the unfold GEMM (ops/conv.py) this saves the im2col materialization
and the separate LN read-modify-write passes; versus XLA's conv emitter it
guarantees every FLOP is an MXU matmul.

The input rides in HBM/ANY and each grid step DMAs its (tile + halo) slice
into VMEM scratch — overlapping windows are not expressible as a blocked
``BlockSpec``. Weights/bias/affine are small enough to sit in VMEM whole
(max: k=9, 256->1024 bf16 = 4.7 MB).

Differentiation: ``fused_conv1d`` / ``fused_conv_relu_ln`` carry a
``jax.custom_vjp`` with an **analytic backward** (the r5 fix for why
conv=pallas lost the r4 training A/B — its old backward recomputed the
whole forward through the im2col reference path, itself 19% slower than
the conv emitter):

* epilogue backward (LayerNorm + ReLU) runs in plain jnp from a saved
  post-ReLU residual (the kernel's second output when ``ln`` is on;
  the primal output itself when only ReLU is on — ``y > 0`` IS the
  ReLU mask) — all elementwise/reduction work XLA fuses;
* dx/dw/db come from ``jax.vjp`` of the *linear* ``lax.conv`` — conv is
  linear in (x, w), so this stores nothing and recomputes nothing; XLA
  lowers the transposed convs with the same emitter the "xla" impl uses.

Gradient parity vs the composed reference:
tests/test_ops.py::test_conv1d_impl_parity,
::test_fused_conv_relu_ln_matches_composed. Pass ``bwd_mode="recompute"``
to the public functions (or set the ``BWD_MODE`` module-global default
before tracing) to A/B the old recompute path.

Set ``interpret=True`` (or run on a non-TPU backend, which selects it) to
emulate the kernel — CPU tests use this.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from speakingstyle_tpu.ops import on_tpu

LN_EPS = 1e-5


def _reference_fused_parts(x, kernel, bias, ln_scale, ln_bias, dilation,
                           relu):
    """Pure-jnp spec of the fused op. Returns (y, act) where act is the
    post-ReLU / pre-LayerNorm intermediate (== y when there is no LN) —
    the residual the analytic backward needs."""
    from speakingstyle_tpu.ops.conv import conv1d_unfold

    y = conv1d_unfold(x, kernel, bias, dilation=dilation)
    if relu:
        y = jnp.maximum(y, 0.0)
    act = y
    if ln_scale is not None:
        yf = y.astype(jnp.float32)
        mean = yf.mean(axis=-1, keepdims=True)
        var = yf.var(axis=-1, keepdims=True)
        yf = (yf - mean) * jax.lax.rsqrt(var + LN_EPS)
        y = (yf * ln_scale + ln_bias).astype(y.dtype)
    return y, act


def _reference_fused(x, kernel, bias, ln_scale, ln_bias, dilation, relu):
    """Pure-jnp spec of the fused op (also the recompute-mode backward)."""
    return _reference_fused_parts(
        x, kernel, bias, ln_scale, ln_bias, dilation, relu
    )[0]


def _kernel(x_hbm, w_ref, b_ref, s_ref, sb_ref, *refs,
            tile, copy_len, taps, dilation, relu, ln, want_act):
    if want_act:
        out_ref, act_ref, x_vmem, sem = refs
    else:
        out_ref, x_vmem, sem = refs
        act_ref = None
    b = pl.program_id(0)
    t = pl.program_id(1)
    # copy_len is (tile + span - 1) rounded up to the sublane tiling (8):
    # Mosaic requires DMA slice shapes aligned to the memref tiling. The
    # rows past tile+span-1 are junk halo and never read by the taps.
    copy = pltpu.make_async_copy(
        x_hbm.at[b, pl.ds(t * tile, copy_len), :], x_vmem, sem
    )
    copy.start()
    copy.wait()
    acc = jnp.zeros(out_ref.shape[1:], jnp.float32)
    for j in range(taps):  # static unroll: one MXU matmul per tap
        acc += jnp.dot(
            x_vmem[j * dilation : j * dilation + tile, :],
            w_ref[j],
            preferred_element_type=jnp.float32,
        )
    acc += b_ref[0]
    if relu:
        acc = jnp.maximum(acc, 0.0)
    if ln:
        # round to the storage dtype BEFORE the LN stats: this is exactly
        # what the unfused reference does (bf16 ReLU output -> f32 LN), and
        # it makes the backward's stats (recomputed from the saved act)
        # bit-consistent with the forward's
        acc = acc.astype(out_ref.dtype).astype(jnp.float32)
    if want_act:
        # post-ReLU / pre-LN residual for the analytic backward
        act_ref[0] = acc.astype(act_ref.dtype)
    if ln:
        mean = acc.mean(axis=-1, keepdims=True)
        var = ((acc - mean) ** 2).mean(axis=-1, keepdims=True)
        acc = (acc - mean) * jax.lax.rsqrt(var + LN_EPS)
        acc = acc * s_ref[0] + sb_ref[0]
    out_ref[0] = acc.astype(out_ref.dtype)


LANE = 128  # Mosaic lane tiling: channel dims in DMA slices must align


def _fused_fwd_pallas(x, kernel, bias, ln_scale, ln_bias, dilation, relu,
                      tile, interpret, want_act=False):
    B, T, cin = x.shape
    K, _, cout = kernel.shape
    span = (K - 1) * dilation + 1
    pad_lo = (span - 1) // 2
    n_t = pl.cdiv(T, tile)
    t_pad = n_t * tile
    # DMA slices must be sublane(8)-aligned in length; round the halo copy up
    copy_len = -(-(tile + span - 1) // 8) * 8
    # SAME padding plus right-fill so the last tile's copy_len DMA is in range
    right = (t_pad - tile + copy_len) - T - pad_lo
    xp = jnp.pad(x, ((0, 0), (pad_lo, right), (0, 0)))
    # Channel dims must be lane(128)-aligned for the manual HBM slice (cin)
    # and the output block (cout): zero-pad both — zeros contribute nothing
    # to the taps' dot products, and padded output columns are sliced off.
    # (The ln=True call sites are the 1024-channel ref-encoder stack, always
    # aligned; _fused falls back to the reference impl for unaligned-ln.)
    cin_p = -(-cin // LANE) * LANE
    cout_p = -(-cout // LANE) * LANE
    if cin_p != cin:
        xp = jnp.pad(xp, ((0, 0), (0, 0), (0, cin_p - cin)))
        kernel = jnp.pad(kernel, ((0, 0), (0, cin_p - cin), (0, 0)))
    if cout_p != cout:
        kernel = jnp.pad(kernel, ((0, 0), (0, 0), (0, cout_p - cout)))
        if bias is not None:
            bias = jnp.pad(bias, (0, cout_p - cout))
        if ln_scale is not None:
            ln_scale = jnp.pad(ln_scale, (0, cout_p - cout))
            ln_bias = jnp.pad(ln_bias, (0, cout_p - cout))
    cout_orig = cout
    cin, cout = cin_p, cout_p

    if bias is None:
        bias = jnp.zeros((cout,), x.dtype)
    ln = ln_scale is not None
    if not ln:
        ln_scale = jnp.zeros((cout,), x.dtype)
        ln_bias = jnp.zeros((cout,), x.dtype)

    # the act residual only differs from the output when LN runs after it
    want_act = want_act and ln
    kern = functools.partial(
        _kernel, tile=tile, copy_len=copy_len, taps=K, dilation=dilation,
        relu=relu, ln=ln, want_act=want_act,
    )
    vec = lambda v: v.reshape(1, cout)
    block = pl.BlockSpec((1, tile, cout), lambda b, t: (b, t, 0))
    shape = jax.ShapeDtypeStruct((B, t_pad, cout), x.dtype)
    out = pl.pallas_call(
        kern,
        grid=(B, n_t),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # x: manual halo DMA
            pl.BlockSpec((K, cin, cout), lambda b, t: (0, 0, 0)),
            pl.BlockSpec((1, cout), lambda b, t: (0, 0)),
            pl.BlockSpec((1, cout), lambda b, t: (0, 0)),
            pl.BlockSpec((1, cout), lambda b, t: (0, 0)),
        ],
        out_specs=[block, block] if want_act else block,
        out_shape=[shape, shape] if want_act else shape,
        scratch_shapes=[
            pltpu.VMEM((copy_len, cin), x.dtype),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(xp, kernel, vec(bias), vec(ln_scale), vec(ln_bias))
    if want_act:
        return tuple(o[:, :T, :cout_orig] for o in out)
    return out[:, :T, :cout_orig]


def _pick_tile(tile: int, T: int) -> int:
    """Clamp the time tile to the sequence and round up to the sublane
    tiling (8): Mosaic requires both block shapes and tile offsets
    (t * tile) to be 8-divisible on the second-minor dimension."""
    return min(-(-tile // 8) * 8, max(8, -(-T // 8) * 8))


def _use_reference(ln_scale, kernel) -> bool:
    """Take the pure-jnp reference for an in-kernel LayerNorm over a
    non-lane-aligned channel count (the kernel's mean/var would average
    the alignment padding). Single source of truth for BOTH the primal and
    the vjp fwd rule — they must agree or grad-time and inference-time
    forwards drift."""
    return ln_scale is not None and kernel.shape[-1] % LANE != 0


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9)
)
def _fused(x, kernel, bias, ln_scale, ln_bias, dilation, relu, tile,
           interpret, bwd_mode):
    if _use_reference(ln_scale, kernel):
        return _reference_fused(
            x, kernel, bias, ln_scale, ln_bias, dilation, relu
        )
    return _fused_fwd_pallas(
        x, kernel, bias, ln_scale, ln_bias, dilation, relu, tile, interpret
    )


# "analytic" (default): epilogue backward from the saved post-ReLU
# residual + linear-conv vjp for dx/dw. "recompute": the pre-r5 behavior
# (full forward recompute through the im2col reference) — kept for A/B.
# The module global is only the DEFAULT, resolved when the public
# functions are called (i.e. at trace time); pass ``bwd_mode=`` explicitly
# when A/B-ing so the mode is part of the traced function — flipping the
# global after a callable is jitted does NOT retrace it.
BWD_MODE = "analytic"


def _fused_fwd(x, kernel, bias, ln_scale, ln_bias, dilation, relu, tile,
               interpret, bwd_mode):
    if bwd_mode != "analytic":
        y = _fused(x, kernel, bias, ln_scale, ln_bias, dilation, relu,
                   tile, interpret, bwd_mode)
        return y, (x, kernel, bias, ln_scale, ln_bias, None)
    if _use_reference(ln_scale, kernel):
        y, act = _reference_fused_parts(
            x, kernel, bias, ln_scale, ln_bias, dilation, relu
        )
    elif ln_scale is not None:
        y, act = _fused_fwd_pallas(
            x, kernel, bias, ln_scale, ln_bias, dilation, relu, tile,
            interpret, want_act=True,
        )
    else:
        # without LN the primal output itself is the residual: y > 0 IS
        # the ReLU mask (and with no ReLU either, no residual is read)
        y = _fused_fwd_pallas(
            x, kernel, bias, ln_scale, ln_bias, dilation, relu, tile,
            interpret,
        )
        act = y
    return y, (x, kernel, bias, ln_scale, ln_bias, act)


def _fused_bwd(dilation, relu, tile, interpret, bwd_mode, res, g):
    x, kernel, bias, ln_scale, ln_bias, act = res
    if bwd_mode != "analytic":
        wrt = (x, kernel, bias, ln_scale, ln_bias)

        def f(x_, k_, b_, s_, sb_):
            return _reference_fused(x_, k_, b_, s_, sb_, dilation, relu)

        _, vjp = jax.vjp(f, *wrt)
        grads = vjp(g)
        if ln_scale is None:
            grads = grads[:3] + (None, None)
        return grads

    gf = g.astype(jnp.float32)
    if ln_scale is not None:
        # LayerNorm backward from the saved pre-LN input (stats recomputed
        # — two cheap fused reductions, no conv recompute)
        af = act.astype(jnp.float32)
        mean = af.mean(axis=-1, keepdims=True)
        var = af.var(axis=-1, keepdims=True)
        rstd = jax.lax.rsqrt(var + LN_EPS)
        norm = (af - mean) * rstd
        d_scale = (gf * norm).sum(axis=(0, 1)).astype(ln_scale.dtype)
        d_lnbias = gf.sum(axis=(0, 1)).astype(ln_bias.dtype)
        dnorm = gf * ln_scale.astype(jnp.float32)
        da = (
            dnorm
            - dnorm.mean(axis=-1, keepdims=True)
            - norm * (dnorm * norm).mean(axis=-1, keepdims=True)
        ) * rstd
    else:
        d_scale = d_lnbias = None
        da = gf
    if relu:
        # ReLU mask from the residual stored in x.dtype. The threshold is
        # the stored dtype's smallest positive NORMAL (finfo.tiny), not a
        # literal 0: accumulator values that round to a stored 0 or
        # subnormal (possible in bf16, where recompute mode would keep
        # their gradient) are cut off at a bound that is explicit in the
        # stored dtype rather than implicit in its rounding — and XLA
        # flushes subnormals to zero anyway, so a subnormal threshold
        # constant would itself collapse to 0 (observed on CPU). Every
        # normal positive stored value passes, so f32 parity with the old
        # ``act > 0`` mask is exact; see the bf16 parity test for the
        # low-precision tolerance note.
        if jnp.issubdtype(act.dtype, jnp.floating):
            relu_thresh = float(jnp.finfo(act.dtype).tiny)
            da = da * (act.astype(jnp.float32) >= relu_thresh)
        else:
            da = da * (act > 0)
    dz = da.astype(x.dtype)
    db = None if bias is None else da.sum(axis=(0, 1)).astype(bias.dtype)

    # conv is linear in (x, w): vjp through it stores nothing and
    # recomputes nothing; XLA emits the transposed convs directly.
    def conv_lin(x_, k_):
        return jax.lax.conv_general_dilated(
            x_, k_, window_strides=(1,), padding="SAME",
            rhs_dilation=(dilation,),
            dimension_numbers=("NWC", "WIO", "NWC"),
        )

    _, vjp = jax.vjp(conv_lin, x, kernel)
    dx, dw = vjp(dz)
    return dx, dw, db, d_scale, d_lnbias


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_conv1d(
    x,
    kernel,
    bias=None,
    *,
    dilation: int = 1,
    relu: bool = False,
    tile: int = 256,
    interpret: Optional[bool] = None,
    bwd_mode: Optional[str] = None,
):
    """SAME conv1d (+optional ReLU) via the fused kernel.

    x [B,T,Cin], kernel [K,Cin,Cout], bias [Cout]. Differentiable.
    """
    interpret = (not on_tpu()) if interpret is None else interpret
    tile = _pick_tile(tile, x.shape[1])
    return _fused(x, kernel, bias, None, None, dilation, relu, tile,
                  interpret, bwd_mode or BWD_MODE)


def fused_conv_relu_ln(
    x,
    kernel,
    bias,
    ln_scale,
    ln_bias,
    *,
    dilation: int = 1,
    tile: int = 256,
    interpret: Optional[bool] = None,
    bwd_mode: Optional[str] = None,
):
    """conv1d -> ReLU -> LayerNorm in one pass (the reference-encoder conv
    stack pattern, reference: model/modules.py:361-379). Differentiable."""
    interpret = (not on_tpu()) if interpret is None else interpret
    tile = _pick_tile(tile, x.shape[1])
    return _fused(x, kernel, bias, ln_scale, ln_bias, dilation, True, tile,
                  interpret, bwd_mode or BWD_MODE)
