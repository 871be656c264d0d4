"""Fused multi-head self-attention Pallas TPU kernel for short sequences.

The model's attention shapes (reference: transformer/SubLayers.py:8-57 at
the paper geometry) are tiny by flash-attention standards: T <= 1000
frames, head dims 32 (reference encoder, 8 heads) and 128 (en/decoder,
2 heads). The stock flash kernel is mistuned for this regime — measured
3.3x SLOWER than einsum attention at [48, 8, 600, 32] fwd+bwd, because its
online-softmax tiling and backward recomputation are built for sequences
that cannot fit in VMEM. Here they CAN: per (batch, head), the whole
[T, T] score matrix in f32 plus q/k/v is under 5 MB for T <= 1024.

So this kernel does the simplest possible thing: one grid step per
(batch, head), full K/V resident in VMEM, one-pass f32 softmax
in-register, no score materialization in HBM. The einsum path's HBM
traffic for the probability tensor ([B, H, T, T] written + read in fwd,
re-read twice in bwd — ~1 GB per reference-encoder layer at bench shapes)
disappears entirely; measured fwd+bwd at bench shapes: 3.4 ms vs 5.9 ms
(ref-encoder, 8 heads d32), 1.65 ms vs 2.3 ms (decoder, 2 heads d128).

Layout: everything rides as [B, H, D, T] — T on the lane (128) dimension,
D on sublanes (8) — so every Mosaic tiling constraint is satisfied for
D in {8, 16, ..., 128} without padding the head dimension. The host-side
transposes are fused by XLA into the surrounding projections.

Numerics match the einsum path with ``attention_softmax_dtype="float32"``
exactly in structure: f32 logits + additive finite mask bias + f32
softmax, probabilities cast to the compute dtype for the PV matmul.
The backward recomputes the probabilities in-kernel (same
rematerialization cost profile as flash attention) and computes exact
gradients for q, k, v.

Differentiation note: unlike ops/pallas_conv.py (whose backward re-runs
the jnp reference), both directions here are Pallas kernels — the
backward's score recomputation is the whole point, since materializing
probabilities for the VJP would reintroduce the HBM traffic being
eliminated.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.custom_partitioning import custom_partitioning
from jax.sharding import NamedSharding, PartitionSpec

from speakingstyle_tpu.ops import on_tpu

LANE = 128
# VMEM budget guard: f32 scores are Tp*Tp*4 bytes (+ ~3 same-size f32
# temporaries in bwd); 1024 keeps the worst case ~12 MB. Forward and
# backward at Tp = 1024 compile and run on a v5e under Mosaic's default
# scoped-VMEM limit (jax 0.9.0 / libtpu 0.0.34, chip_smoke.py), so no
# vmem_limit_bytes is passed.
MAX_T = 1024


def _softmax_rows(scores, sm_dtype):
    """Row softmax entirely in VMEM registers. ``sm_dtype`` is the
    exp/normalize dtype: f32 for reference parity, bf16 saves ~24% of the
    kernel's forward (the VPU exp over [T, T] is a large share of its
    time; the matmuls are small). The f32->bf16 cast happens after the
    scale+bias so the mask bias keeps its full magnitude."""
    scores = scores.astype(sm_dtype)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    return p / jnp.sum(p, axis=-1, keepdims=True)


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, out_ref, *, sm_scale,
                sm_dtype):
    q = q_ref[0, 0]  # [D, T]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    # scores[q, t] = sum_d q[d, q] * k[d, t]
    scores = jax.lax.dot_general(
        q, k, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    scores = scores * sm_scale + bias_ref[0, 0][None, :]
    p = _softmax_rows(scores, sm_dtype).astype(v.dtype)
    # outT[d, q] = sum_t v[d, t] * p[q, t]
    out_ref[0, 0] = jax.lax.dot_general(
        v, p, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(out_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref,
                dq_ref, dk_ref, dv_ref, *, sm_scale, sm_dtype):
    q = q_ref[0, 0]   # [D, T]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]  # [D, T] cotangent of outT
    scores = jax.lax.dot_general(
        q, k, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    scores = scores * sm_scale + bias_ref[0, 0][None, :]
    p = _softmax_rows(scores, sm_dtype).astype(jnp.float32)  # [Tq, Tk]
    p_lo = p.astype(v.dtype)
    # dv[d, t] = sum_q do[d, q] * p[q, t]
    dv_ref[0, 0] = jax.lax.dot_general(
        do, p_lo, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(dv_ref.dtype)
    # dp[q, t] = sum_d do[d, q] * v[d, t]
    dp = jax.lax.dot_general(
        do, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    # softmax vjp: ds = p * (dp - rowsum(dp * p)), with the sm_scale factor
    ds = (p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True)) * sm_scale
          ).astype(q.dtype)
    # dq[d, q] = sum_t k[d, t] * ds[q, t]
    dq_ref[0, 0] = jax.lax.dot_general(
        k, ds, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(dq_ref.dtype)
    # dk[d, t] = sum_q q[d, q] * ds[q, t]
    dk_ref[0, 0] = jax.lax.dot_general(
        q, ds, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(dk_ref.dtype)


def _bh_specs(D, Tp, n: int):
    # one (batch, head) per grid step: measured faster than a grid-over-
    # batch variant with the head loop unrolled in-kernel (2.2 ms vs
    # 1.65 ms for the 2-head d128 layers) — the deeper grid pipelines
    # DMA against compute better
    return [
        pl.BlockSpec((1, 1, D, Tp), lambda b, h: (b, h, 0, 0)) for _ in range(n)
    ]


def _bias_spec(Tp):
    # [B, 1, Tp] with block (1, 1, Tp): the middle axis keeps the block's
    # second-minor dim equal to the array dim (a Mosaic block-shape
    # requirement for dims < 8)
    return pl.BlockSpec((1, 1, Tp), lambda b, h: (b, 0, 0))


def _pallas_fwd(qT, kT, vT, bias, sm_scale, sm_dtype, interpret):
    B, H, D, Tp = qT.shape
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, sm_dtype=sm_dtype),
        grid=(B, H),
        in_specs=_bh_specs(D, Tp, 3) + [_bias_spec(Tp)],
        out_specs=_bh_specs(D, Tp, 1)[0],
        out_shape=jax.ShapeDtypeStruct((B, H, D, Tp), qT.dtype),
        interpret=interpret,
    )(qT, kT, vT, bias)


def _pallas_bwd(qT, kT, vT, bias, doT, sm_scale, sm_dtype, interpret):
    B, H, D, Tp = qT.shape
    shape = jax.ShapeDtypeStruct((B, H, D, Tp), qT.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, sm_scale=sm_scale, sm_dtype=sm_dtype),
        grid=(B, H),
        in_specs=_bh_specs(D, Tp, 3) + [_bias_spec(Tp)] + _bh_specs(D, Tp, 1),
        out_specs=tuple(_bh_specs(D, Tp, 3)),
        out_shape=(shape, shape, shape),
        interpret=interpret,
    )(qT, kT, vT, bias, doT)


def _batch_partitioned(fn, rule: str):
    """Wrap a per-batch-independent pallas entry in custom_partitioning so
    GSPMD shards it along the batch dim instead of all-gathering the
    operands (which it does for unannotated custom calls — verified in
    HLO). ``rule`` is a Shardy einsum-like sharding rule whose only shared
    factor is the batch dim ``b``; the partition callback forces every
    operand/result to batch-only sharding (replicated on H/D/T — the
    kernel needs whole sequences) and lowers the same pallas call on the
    shard's batch slice. Falls back to full replication when the batch
    axis doesn't divide the shard count."""

    cp = custom_partitioning(fn, static_argnums=())

    def _batch_axis(mesh, arg_infos):
        spec = getattr(arg_infos[0].sharding, "spec", None)
        b = spec[0] if spec and len(spec) > 0 else None
        if b is None:
            return None
        axes = (b,) if isinstance(b, str) else tuple(b)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        return b if arg_infos[0].shape[0] % n == 0 else None

    def _batch_only(mesh, b, infos):
        return tuple(
            NamedSharding(mesh, PartitionSpec(b, *(None,) * (len(i.shape) - 1)))
            for i in infos
        )

    def partition(mesh, arg_infos, result_infos):
        b = _batch_axis(mesh, arg_infos)
        arg_sh = _batch_only(mesh, b, arg_infos)
        if isinstance(result_infos, (list, tuple)):
            out_sh = _batch_only(mesh, b, result_infos)
        else:
            out_sh = _batch_only(mesh, b, (result_infos,))[0]
        return mesh, fn, out_sh, arg_sh

    cp.def_partition(partition=partition, sharding_rule=rule)
    return cp


_FWD_RULE = "b h d t, b h d t, b h d t, b i t -> b h d t"
_BWD_RULE = (
    "b h d t, b h d t, b h d t, b i t, b h d t "
    "-> b h d t, b h d t, b h d t"
)


def _call_fwd(qT, kT, vT, bias, sm_scale, sm_dtype, interpret):
    # custom_partitioning requires a purely positional callee
    def fn(qT, kT, vT, bias):
        return _pallas_fwd(qT, kT, vT, bias, sm_scale, sm_dtype, interpret)

    return _batch_partitioned(fn, _FWD_RULE)(qT, kT, vT, bias)


def _call_bwd(qT, kT, vT, bias, doT, sm_scale, sm_dtype, interpret):
    def fn(qT, kT, vT, bias, doT):
        return _pallas_bwd(qT, kT, vT, bias, doT, sm_scale, sm_dtype,
                           interpret)

    return _batch_partitioned(fn, _BWD_RULE)(qT, kT, vT, bias, doT)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fused(qT, kT, vT, bias, sm_scale, sm_dtype, interpret):
    return _call_fwd(qT, kT, vT, bias, sm_scale, sm_dtype, interpret)


def _fused_fwd(qT, kT, vT, bias, sm_scale, sm_dtype, interpret):
    out = _call_fwd(qT, kT, vT, bias, sm_scale, sm_dtype, interpret)
    return out, (qT, kT, vT, bias)


def _fused_bwd(sm_scale, sm_dtype, interpret, res, doT):
    qT, kT, vT, bias = res
    dq, dk, dv = _call_bwd(qT, kT, vT, bias, doT, sm_scale, sm_dtype,
                           interpret)
    return dq, dk, dv, None


_fused.defvjp(_fused_fwd, _fused_bwd)


def _reference_mha(q, k, v, pad_mask, sm_scale, softmax_dtype):
    """The einsum path (models/layers.py dense attention), used off-TPU."""
    from speakingstyle_tpu.ops.masking import attention_bias

    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * jnp.asarray(
        sm_scale, q.dtype
    )
    logits = logits.astype(softmax_dtype) + attention_bias(
        pad_mask, softmax_dtype
    )
    attn = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", attn, v)


# Test hook: when True, the auto path runs the kernel in interpret mode
# even off-TPU, so sharded-mesh CPU tests can exercise the pallas code
# path (tests/test_parallel.py::test_fused_attention_under_sharded_mesh)
# instead of silently falling back to einsum.
FORCE_INTERPRET = False


def _padded_len(T: int) -> int:
    return -(-T // LANE) * LANE


def _sublane(dtype) -> int:
    """D rides on sublanes in the kernel's [B, H, D, T] layout, so it must
    be a multiple of the dtype's sublane tiling: 8 for 4-byte dtypes, 16
    for bf16/f16, 32 for 1-byte dtypes (Mosaic packs 4/itemsize rows per
    sublane — a D of 8/24/40 in bf16 would pass an %8 gate yet fail
    lowering on real hardware)."""
    return max(8, 32 // jnp.dtype(dtype).itemsize)


def _head_dim_ok(D: int, dtype) -> bool:
    return D % _sublane(dtype) == 0 and D <= LANE


def supported(T: int, D: int, dtype=jnp.float32) -> bool:
    """Shapes this kernel handles: a head dim on the sublane tiling and a
    lane-padded sequence whose [T, T] score tile fits VMEM."""
    return _head_dim_ok(D, dtype) and _padded_len(T) <= MAX_T


def fused_mha(
    q,
    k,
    v,
    pad_mask,
    sm_scale: Optional[float] = None,
    softmax_dtype=jnp.float32,
    interpret: Optional[bool] = None,
):
    """Fused self-attention. q/k/v: [B, L, H, D] (the layout the model's
    QKV projections produce); pad_mask: [B, L] True at padding. Returns
    [B, L, H, D].

    ``interpret=None`` compiles the kernel on a TPU and takes the einsum
    reference on any other backend; ``True`` emulates the kernel (CPU
    parity tests); ``False`` compiles it unconditionally (raises off-TPU).
    The einsum reference is taken quietly only off-TPU and for sequences
    past ``MAX_T``; a compiled-kernel request whose head dim the kernel
    cannot tile raises at trace time instead of silently materialising
    [B, H, T, T] in HBM."""
    B, L, H, D = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    if interpret is None and FORCE_INTERPRET:
        interpret = True
    # will Mosaic compile the kernel (as opposed to emulating or skipping it)?
    compiled = on_tpu() if interpret is None else not interpret
    Tp = _padded_len(L)
    if not (compiled or interpret) or Tp > MAX_T:
        return _reference_mha(q, k, v, pad_mask, sm_scale, softmax_dtype)
    if not _head_dim_ok(D, q.dtype):
        if not compiled:
            return _reference_mha(q, k, v, pad_mask, sm_scale, softmax_dtype)
        raise ValueError(
            f"attention_kernel='fused' cannot tile head dim {D} in "
            f"{jnp.dtype(q.dtype).name} (needs a multiple of "
            f"{_sublane(q.dtype)} up to {LANE}); "
            "set model.attention_kernel: einsum for this geometry"
        )

    pad_t = Tp - L
    # [B, L, H, D] -> [B, H, D, Tp]: T on lanes, D on sublanes
    def to_t(x):
        x = x.transpose(0, 2, 3, 1)
        return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, pad_t)))

    qT, kT, vT = to_t(q), to_t(k), to_t(v)
    neg = jnp.asarray(jnp.finfo(jnp.float32).min / 2, jnp.float32)
    key_pad = jnp.pad(pad_mask, ((0, 0), (0, pad_t)), constant_values=True)
    # [B, 1, Tp]: the middle axis keeps the block's second-minor dim equal
    # to the array dim (a Mosaic block-shape requirement for dims < 8)
    bias = jnp.where(key_pad, neg, jnp.zeros((), jnp.float32))[:, None, :]

    outT = _fused(qT, kT, vT, bias, float(sm_scale), jnp.dtype(softmax_dtype),
                  not compiled)
    # [B, H, D, Tp] -> [B, L, H, D]
    return outT[..., :L].transpose(0, 3, 1, 2)
