"""Causal self-attention in blocks with an online softmax: Pallas TPU kernels
forward and backward, for sequences whose ``[T, T]`` scores must never stand
whole (ROADMAP B2).

``ops/pallas_attention.py`` holds a whole ``[T, T]`` tile per (batch, head)
and stops at 1,024 positions. Here a query block meets one key block at a
time and carries a running maximum, sum and output (the online softmax), so
nothing of size ``T x T`` exists anywhere, and the blocks a mask rules out
are never visited:

- **causal**: position ``i`` sees ``j <= i``; key blocks right of the
  diagonal are not on the grid's path;
- **window** ``W`` (optional): ``i - W < j <= i``; the key axis of the grid
  is only as long as a window is wide (``ceil((W - 1) / block) + 1`` blocks),
  whatever ``T`` is;
- **grouped queries**: ``H`` query heads share ``H_kv`` key-value heads,
  ``G = H / H_kv`` to one; the index maps send query head ``h`` to key-value
  head ``h // G``, and the key/value gradient kernel sums over the group.

Layout ``[B, H, T, D]`` (``D`` on lanes: 128 is one lane tile), scores and
softmax in float32, probabilities cast to the operands' dtype for the second
product. The backward is two kernels, as usual for this algorithm: one per
query block for ``dq`` (the forward's loop again), one per key block for
``dk`` and ``dv``, which works on transposed scores ``[keys, queries]`` so
that the per-query log-sum-exp and ``delta = rowsum(dO * O)`` ride as rows.
Only the mask-partial blocks (the diagonal, the window's far edge) pay for
the mask.

Off a TPU the plain ``einsum`` reference below runs instead
(``interpret=True`` emulates the kernels: the parity tests). The reference
carries no names and keeps nothing across a rematerialisation: it is
differentiated by JAX, and a policy that names ``OUT_NAME`` and ``LSE_NAME``
finds neither there.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from speakingstyle_tpu.ops import on_tpu

LANE = 128
BLOCK = 512
# finite, so that a row with nothing unmasked yet gives exp(0) and no NaN:
# the diagonal block comes last and wipes what such a row gathered
NEG = -0.7 * float(jnp.finfo(jnp.float32).max)
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
# what the forward kernel hands the backward, by name: a caller that
# rematerialises around the kernel keeps the two with
# ``jax.checkpoint_policies.save_only_these_names(OUT_NAME, LSE_NAME)`` and
# its backward runs the forward kernel no second time (models/mellum.py)
OUT_NAME = "blocked_attention_out"
LSE_NAME = "blocked_attention_lse"


def reference_attention(q, k, v, window: Optional[int], sm_scale: float):
    """Plain einsum attention with the same mask: q ``[B, H, T, D]``, k and
    v ``[B, H_kv, T, D]``. Float32 scores and softmax."""
    B, H, T, D = q.shape
    G = H // k.shape[1]
    qg = q.reshape(B, k.shape[1], G, T, D)
    s = jnp.einsum("bkgtd,bksd->bkgts", qg, k,
                   preferred_element_type=jnp.float32) * sm_scale
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    ok = j <= i
    if window is not None:
        ok = ok & (j > i - window)
    p = jax.nn.softmax(jnp.where(ok, s, NEG), axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgts,bksd->bkgtd", p, v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, T, D).astype(q.dtype)


def _span(window: Optional[int], blk: int, n_blocks: int) -> int:
    """Key blocks a query block can see (and query blocks that see a key
    block): all of them under the causal mask alone, a window's worth else."""
    if window is None:
        return n_blocks
    return min(n_blocks, -(-(window - 1) // blk) + 1)


def _first_key_block(i, window: Optional[int], blk: int):
    if window is None:
        return 0
    return jnp.maximum(i * blk - window + 1, 0) // blk


def _partial(i, kb, window: Optional[int], blk: int):
    """Whether block (query ``i``, key ``kb``) has masked pairs."""
    diag = kb == i
    if window is None:
        return diag
    return diag | (kb * blk <= i * blk + blk - 1 - window)


def _mask(i, kb, window, blk, transposed: bool):
    """``[blk, blk]`` bool of the pairs seen; rows are keys if transposed."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
    qpos = i * blk + (cols if transposed else rows)
    kpos = kb * blk + (rows if transposed else cols)
    ok = kpos <= qpos
    if window is not None:
        ok = ok & (kpos > qpos - window)
    return ok


def _both(cond, fn):
    """``fn(masked)`` under ``cond`` and again, without the mask, under its
    negation: whole blocks skip the iota compares."""
    pl.when(cond)(lambda: fn(True))
    pl.when(jnp.logical_not(cond))(lambda: fn(False))


# -- forward -----------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                sm_scale, blk, window, span):
    i, j = pl.program_id(2), pl.program_id(3)
    kb = _first_key_block(i, window, blk) + j

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, NEG, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def step(masked):
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32) * sm_scale
        if masked:
            s = jnp.where(_mask(i, kb, window, blk, False), s, NEG)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        acc_sc[...] = alpha[:, :1] * acc_sc[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(kb <= i)
    def _():
        _both(_partial(i, kb, window, blk), step)

    @pl.when(j == span - 1)
    def _():
        l = l_sc[...]
        o_ref[0, 0] = (acc_sc[...] / l[:, :1]).astype(o_ref.dtype)
        # the row's log-sum-exp, as a row: [blk, LANE] -> [LANE, blk] -> [1, blk]
        lse_ref[0, 0] = (m_sc[...] + jnp.log(l)).T[:1]


def _forward(q, k, v, window, sm_scale, blk, interpret):
    B, H, T, D = q.shape
    G = H // k.shape[1]
    nq = T // blk
    span = _span(window, blk, nq)

    def kv_map(b, h, i, j):
        kb = _first_key_block(i, window, blk) + j
        return (b, h // G, jnp.minimum(kb, i), 0)

    return pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, blk=blk,
                          window=window, span=span),
        grid=(B, H, nq, span),
        in_specs=[
            pl.BlockSpec((1, 1, blk, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, blk, D), kv_map),
            pl.BlockSpec((1, 1, blk, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, blk, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, 1, blk), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, T), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk, LANE), jnp.float32),
            pltpu.VMEM((blk, LANE), jnp.float32),
            pltpu.VMEM((blk, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# -- backward ----------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_sc,
               *, sm_scale, blk, window, span):
    i, j = pl.program_id(2), pl.program_id(3)
    kb = _first_key_block(i, window, blk) + j

    @pl.when(j == 0)
    def _():
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def step(masked):
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        lse = jnp.expand_dims(lse_ref[0, 0, 0], -1)      # [blk, 1]
        delta = jnp.expand_dims(delta_ref[0, 0, 0], -1)
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32) * sm_scale
        if masked:
            s = jnp.where(_mask(i, kb, window, blk, False), s, NEG)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
        acc_sc[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(kb <= i)
    def _():
        _both(_partial(i, kb, window, blk), step)

    @pl.when(j == span - 1)
    def _():
        dq_ref[0, 0] = acc_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, sm_scale, blk, window, span, nq, group):
    kb, g, t = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    i = kb + t  # the query block

    @pl.when((g == 0) & (t == 0))
    def _():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    def step(masked):
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        lse, delta = lse_ref[0, 0], delta_ref[0, 0]      # [1, blk] rows
        st = jax.lax.dot_general(k, q, _NT,
                                 preferred_element_type=jnp.float32) * sm_scale
        if masked:
            st = jnp.where(_mask(i, kb, window, blk, True), st, NEG)
        pt = jnp.exp(st - lse)                           # [keys, queries]
        dv_sc[...] += jnp.dot(pt.astype(do.dtype), do,
                              preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta) * sm_scale).astype(q.dtype)
        dk_sc[...] += jnp.dot(dst, q, preferred_element_type=jnp.float32)

    @pl.when(i <= nq - 1)
    def _():
        _both(_partial(i, kb, window, blk), step)

    @pl.when((g == group - 1) & (t == span - 1))
    def _():
        dk_ref[0, 0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def _backward(q, k, v, o, lse, do, window, sm_scale, blk, interpret):
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    nq = T // blk
    span = _span(window, blk, nq)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, :, None, :]              # [B, H, 1, T]

    def kv_map(b, h, i, j):
        kb = _first_key_block(i, window, blk) + j
        return (b, h // G, jnp.minimum(kb, i), 0)

    q_spec = pl.BlockSpec((1, 1, blk, D), lambda b, h, i, j: (b, h, i, 0))
    row_spec = pl.BlockSpec((1, 1, 1, blk), lambda b, h, i, j: (b, h, 0, i))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, blk=blk,
                          window=window, span=span),
        grid=(B, H, nq, span),
        in_specs=[q_spec, pl.BlockSpec((1, 1, blk, D), kv_map),
                  pl.BlockSpec((1, 1, blk, D), kv_map), q_spec, row_spec,
                  row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((blk, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    def q_of(b, hk, kb, g, t):
        return (b, hk * G + g, jnp.minimum(kb + t, nq - 1), 0)

    def row_of(b, hk, kb, g, t):
        return (b, hk * G + g, 0, jnp.minimum(kb + t, nq - 1))

    kv_spec = pl.BlockSpec((1, 1, blk, D), lambda b, hk, kb, g, t: (b, hk, kb, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, blk=blk,
                          window=window, span=span, nq=nq, group=G),
        grid=(B, Hkv, nq, G, span),
        in_specs=[pl.BlockSpec((1, 1, blk, D), q_of), kv_spec, kv_spec,
                  pl.BlockSpec((1, 1, blk, D), q_of),
                  pl.BlockSpec((1, 1, 1, blk), row_of),
                  pl.BlockSpec((1, 1, 1, blk), row_of)],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((blk, D), jnp.float32),
                        pltpu.VMEM((blk, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _blocked(q, k, v, window, sm_scale, blk, interpret):
    return _forward(q, k, v, window, sm_scale, blk, interpret)[0]


def _blocked_fwd(q, k, v, window, sm_scale, blk, interpret):
    o, lse = _forward(q, k, v, window, sm_scale, blk, interpret)
    # named before they enter the residuals: what the backward reads are the
    # named values (identities outside a ``jax.checkpoint`` whose policy
    # names them)
    o, lse = checkpoint_name(o, OUT_NAME), checkpoint_name(lse, LSE_NAME)
    return o, (q, k, v, o, lse)


def _blocked_bwd(window, sm_scale, blk, interpret, res, do):
    q, k, v, o, lse = res
    return _backward(q, k, v, o, lse, do, window, sm_scale, blk, interpret)


_blocked.defvjp(_blocked_fwd, _blocked_bwd)


def blocked_attention(q, k, v, window: Optional[int] = None,
                      sm_scale: Optional[float] = None, block: int = BLOCK,
                      interpret: Optional[bool] = None):
    """Causal (and, with ``window``, banded) attention. q ``[B, H, T, D]``;
    k, v ``[B, H_kv, T, D]`` with ``H`` a multiple of ``H_kv``. Returns
    ``[B, H, T, D]``.

    ``interpret=None`` compiles the kernels on a TPU and takes the einsum
    reference on any other backend; ``True`` emulates them (CPU parity
    tests); ``False`` compiles them unconditionally. Any ``T``: the sequence
    is padded to a whole number of blocks (the padded keys lie right of
    every real query's diagonal, so the causal mask already hides them)."""
    B, H, T, D = q.shape
    if H % k.shape[1]:
        raise ValueError(f"{H} query heads over {k.shape[1]} key-value heads")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    compiled = on_tpu() if interpret is None else not interpret
    if not (compiled or interpret):
        return reference_attention(q, k, v, window, float(sm_scale))
    blk = min(block, -(-T // LANE) * LANE)
    pad = -T % blk
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for x in (q, k, v))
    out = _blocked(q, k, v, window, float(sm_scale), blk, not compiled)
    return out[:, :, :T] if pad else out
