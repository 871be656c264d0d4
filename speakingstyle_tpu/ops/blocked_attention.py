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
- **block diffusion** (``mask=BlockDiffusion(c)``, in the triangle's place):
  the ``T = 2L`` positions are a noised copy of ``L`` tokens and then the
  clean copy, in blocks of ``c``; a noised block sees itself and the clean
  blocks before it, a clean block the clean blocks up to itself. A quarter
  of the square is seen. A query tile meets its own tile, the clean tiles
  before it and, if noised, the clean tile beside it: the key axis of the
  grid is ``L / block + 1`` long, and of the ``2 L / block`` square of tiles
  ``(L / block)^2 + 2 L / block`` are visited (80 where the triangle over
  ``2L`` has 136, at ``L`` 4,096);
- **grouped queries**: ``H`` query heads share ``H_kv`` key-value heads,
  ``G = H / H_kv`` to one; the index maps send query head ``h`` to key-value
  head ``h // G``, and the key/value gradient kernel sums over the group.

Layout ``[B, H, T, D]`` (``D`` on lanes: 128 is one lane tile), scores and
softmax in float32, probabilities cast to the operands' dtype for the second
product. The backward is two kernels, as usual for this algorithm: one per
query block for ``dq`` (the forward's loop again), one per key block for
``dk`` and ``dv``, which works on transposed scores ``[keys, queries]`` so
that the per-query log-sum-exp and ``delta = rowsum(dO * O)`` ride as rows.
Only the mask-partial blocks (the diagonal, the window's far edge, a
stream's own tile and the clean tile beside a noised one) pay for the mask.
Which tiles meet is a *geometry* (``_Causal``, ``_BlockDiffusion`` below):
the kernels and the index maps ask it from the query side (``key``) and
from the key side (``query``), and know no mask themselves.

Off a TPU the plain ``einsum`` reference below runs instead
(``interpret=True`` emulates the kernels: the parity tests). The reference
carries no names and keeps nothing across a rematerialisation: it is
differentiated by JAX, and a policy that names ``OUT_NAME`` and ``LSE_NAME``
finds neither there.
"""

import functools
import math
from typing import NamedTuple, Optional

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


class BlockDiffusion(NamedTuple):
    """The mask of block-diffusion training, a static description: ``T = 2L``
    positions, position ``i < L`` noised token ``i`` and ``i >= L`` clean
    token ``i - L``, in blocks of ``block_length`` tokens. With
    ``pos(i) = i mod L`` and ``blk(i) = pos(i) // block_length``, ``i`` sees
    ``j`` iff they are in one stream and ``blk(i) == blk(j)``, or ``i`` is
    noised, ``j`` clean and ``blk(i) > blk(j)``, or both are clean and
    ``blk(i) >= blk(j)``."""

    block_length: int

    def seen(self, T: int):
        """The whole ``[T, T]`` bool of the pairs seen, by ``arange``."""
        L, c = T // 2, self.block_length
        i = jnp.arange(T)[:, None]
        j = jnp.arange(T)[None, :]
        qn, kn = i < L, j < L
        qb, kb = (i % L) // c, (j % L) // c
        return ((qn == kn) & (qb == kb)) | (qn & ~kn & (qb > kb)) \
            | (~qn & ~kn & (qb >= kb))

    def tiles(self, T: int, blk: int) -> int:
        """Tile pairs a pass over one head visits: the mask's own arithmetic."""
        n = T // 2 // blk
        return n * n + 2 * n


def reference_attention(q, k, v, window: Optional[int], sm_scale: float,
                        mask: Optional[BlockDiffusion] = None):
    """Plain einsum attention with the same mask: q ``[B, H, T, D]``, k and
    v ``[B, H_kv, T, D]``. Float32 scores and softmax."""
    B, H, T, D = q.shape
    G = H // k.shape[1]
    qg = q.reshape(B, k.shape[1], G, T, D)
    s = jnp.einsum("bkgtd,bksd->bkgts", qg, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if mask is not None:
        ok = mask.seen(T)
    else:
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


# -- which tiles meet ----------------------------------------------------------
# A geometry answers, for ``nq`` tiles of ``blk`` positions: how many steps
# the grid's last axis has from either side (``q_steps``, ``k_steps``); the
# key tile that step ``j`` of query tile ``i`` names (``key``), whether the
# pair is visited (``key_seen``) and the tile to fetch for it (``fetch_key``:
# the last visited one again where the step is not, so that nothing moves);
# the same from a key tile's side (``query``, ``query_seen``,
# ``fetch_query``); whether a visited pair of tiles has masked pairs
# (``partial``) and which (``mask``).

class _Causal(NamedTuple):
    window: Optional[int]
    blk: int
    nq: int

    @property
    def q_steps(self):
        return _span(self.window, self.blk, self.nq)

    k_steps = q_steps

    def key(self, i, j):
        return _first_key_block(i, self.window, self.blk) + j

    def key_seen(self, i, j, kb):
        return kb <= i

    def fetch_key(self, i, j):
        return jnp.minimum(self.key(i, j), i)

    def query(self, kb, t):
        return kb + t

    def query_seen(self, kb, t, i):
        return i <= self.nq - 1

    def fetch_query(self, kb, t):
        return jnp.minimum(kb + t, self.nq - 1)

    def partial(self, i, kb):
        return _partial(i, kb, self.window, self.blk)

    def mask(self, i, kb, transposed: bool):
        return _mask(i, kb, self.window, self.blk, transposed)


class _BlockDiffusion(NamedTuple):
    """``n`` tiles a stream: query tile ``i`` (noised if ``i < n``, of rank
    ``r = i mod n`` in its stream) meets its own tile first, then the clean
    tiles ``n .. n + r - 1`` whole and, if noised, clean tile ``n + r``
    (blocks strictly before). Noised key tile ``kb`` is met by query tile
    ``kb`` alone; clean key tile ``n + m`` by noised tiles ``m ..`` and then
    clean tiles ``n + m ..``."""

    c: int
    blk: int
    n: int

    @property
    def q_steps(self):
        return self.n + 1

    @property
    def k_steps(self):
        return 2 * self.n

    def _last(self, i):
        """The last step query tile ``i`` visits a key tile at."""
        return jnp.where(i < self.n, i + 1, i - self.n)

    def key(self, i, j):
        return jnp.where(j == 0, i, self.n + j - 1)

    def key_seen(self, i, j, kb):
        return j <= self._last(i)

    def fetch_key(self, i, j):
        return self.key(i, jnp.minimum(j, self._last(i)))

    def _visits(self, kb):
        """How many query tiles visit key tile ``kb``."""
        return jnp.where(kb < self.n, 1, 2 * (2 * self.n - kb))

    def query(self, kb, t):
        m = kb - self.n
        return jnp.where(kb < self.n, kb,
                         jnp.where(t < self.n - m, m + t, 2 * m + t))

    def query_seen(self, kb, t, i):
        return t < self._visits(kb)

    def fetch_query(self, kb, t):
        return self.query(kb, jnp.minimum(t, self._visits(kb) - 1))

    def partial(self, i, kb):
        return (kb == i) | (kb == i + self.n)

    def mask(self, i, kb, transposed: bool):
        # a partial pair's tiles start at the same block of their streams,
        # so the blocks compare by the positions inside the tile
        rows = jax.lax.broadcasted_iota(jnp.int32, (self.blk, self.blk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (self.blk, self.blk), 1)
        ahead = (cols if transposed else rows) // self.c \
            - (rows if transposed else cols) // self.c    # blk(query) - blk(key)
        # own tile: the same block if noised, up to the same block if clean;
        # the clean tile beside a noised one: strictly before (bounds as
        # scalars: Mosaic selects no vector of booleans)
        own = kb == i
        least = jnp.where(own, 0, 1)
        most = jnp.where(own & (i < self.n), 0, self.blk)
        return (ahead >= least) & (ahead <= most)


def _both(cond, fn):
    """``fn(masked)`` under ``cond`` and again, without the mask, under its
    negation: whole blocks skip the iota compares."""
    pl.when(cond)(lambda: fn(True))
    pl.when(jnp.logical_not(cond))(lambda: fn(False))


# -- forward -----------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                sm_scale, geom):
    i, j = pl.program_id(2), pl.program_id(3)
    kb = geom.key(i, j)

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
            s = jnp.where(geom.mask(i, kb, False), s, NEG)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        acc_sc[...] = alpha[:, :1] * acc_sc[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(geom.key_seen(i, j, kb))
    def _():
        _both(geom.partial(i, kb), step)

    @pl.when(j == geom.q_steps - 1)
    def _():
        l = l_sc[...]
        o_ref[0, 0] = (acc_sc[...] / l[:, :1]).astype(o_ref.dtype)
        # the row's log-sum-exp, as a row: [blk, LANE] -> [LANE, blk] -> [1, blk]
        lse_ref[0, 0] = (m_sc[...] + jnp.log(l)).T[:1]


def _forward(q, k, v, geom, sm_scale, interpret):
    B, H, T, D = q.shape
    G = H // k.shape[1]
    blk = geom.blk

    def kv_map(b, h, i, j):
        return (b, h // G, geom.fetch_key(i, j), 0)

    return pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, geom=geom),
        grid=(B, H, T // blk, geom.q_steps),
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
               *, sm_scale, geom):
    i, j = pl.program_id(2), pl.program_id(3)
    kb = geom.key(i, j)

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
            s = jnp.where(geom.mask(i, kb, False), s, NEG)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
        acc_sc[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(geom.key_seen(i, j, kb))
    def _():
        _both(geom.partial(i, kb), step)

    @pl.when(j == geom.q_steps - 1)
    def _():
        dq_ref[0, 0] = acc_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, sm_scale, geom, group):
    kb, g, t = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    i = geom.query(kb, t)  # the query block

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
            st = jnp.where(geom.mask(i, kb, True), st, NEG)
        pt = jnp.exp(st - lse)                           # [keys, queries]
        dv_sc[...] += jnp.dot(pt.astype(do.dtype), do,
                              preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta) * sm_scale).astype(q.dtype)
        dk_sc[...] += jnp.dot(dst, q, preferred_element_type=jnp.float32)

    @pl.when(geom.query_seen(kb, t, i))
    def _():
        _both(geom.partial(i, kb), step)

    @pl.when((g == group - 1) & (t == geom.k_steps - 1))
    def _():
        dk_ref[0, 0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def _backward(q, k, v, o, lse, do, geom, sm_scale, interpret):
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    blk = geom.blk
    nq = T // blk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, :, None, :]              # [B, H, 1, T]

    def kv_map(b, h, i, j):
        return (b, h // G, geom.fetch_key(i, j), 0)

    q_spec = pl.BlockSpec((1, 1, blk, D), lambda b, h, i, j: (b, h, i, 0))
    row_spec = pl.BlockSpec((1, 1, 1, blk), lambda b, h, i, j: (b, h, 0, i))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, geom=geom),
        grid=(B, H, nq, geom.q_steps),
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
        return (b, hk * G + g, geom.fetch_query(kb, t), 0)

    def row_of(b, hk, kb, g, t):
        return (b, hk * G + g, 0, geom.fetch_query(kb, t))

    kv_spec = pl.BlockSpec((1, 1, blk, D), lambda b, hk, kb, g, t: (b, hk, kb, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, geom=geom, group=G),
        grid=(B, Hkv, nq, G, geom.k_steps),
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _blocked(q, k, v, geom, sm_scale, interpret):
    return _forward(q, k, v, geom, sm_scale, interpret)[0]


def _blocked_fwd(q, k, v, geom, sm_scale, interpret):
    o, lse = _forward(q, k, v, geom, sm_scale, interpret)
    # named before they enter the residuals: what the backward reads are the
    # named values (identities outside a ``jax.checkpoint`` whose policy
    # names them)
    o, lse = checkpoint_name(o, OUT_NAME), checkpoint_name(lse, LSE_NAME)
    return o, (q, k, v, o, lse)


def _blocked_bwd(geom, sm_scale, interpret, res, do):
    q, k, v, o, lse = res
    return _backward(q, k, v, o, lse, do, geom, sm_scale, interpret)


_blocked.defvjp(_blocked_fwd, _blocked_bwd)


def blocked_attention(q, k, v, window: Optional[int] = None,
                      sm_scale: Optional[float] = None, block: int = BLOCK,
                      interpret: Optional[bool] = None,
                      mask: Optional[BlockDiffusion] = None):
    """Causal (and, with ``window``, banded) attention, or attention under
    ``mask`` in the triangle's place. q ``[B, H, T, D]``; k, v
    ``[B, H_kv, T, D]`` with ``H`` a multiple of ``H_kv``. Returns
    ``[B, H, T, D]``.

    ``interpret=None`` compiles the kernels on a TPU and takes the einsum
    reference on any other backend; ``True`` emulates them (CPU parity
    tests); ``False`` compiles them unconditionally. Any ``T``: the sequence
    is padded to a whole number of blocks (the padded keys lie right of
    every real query's diagonal, so the causal mask already hides them).
    Under ``mask`` nothing is padded: a stream (``T / 2`` positions) is a
    whole number of tiles of 128 lanes or more and a tile a whole number of
    the mask's blocks, or the kernels refuse."""
    B, H, T, D = q.shape
    if H % k.shape[1]:
        raise ValueError(f"{H} query heads over {k.shape[1]} key-value heads")
    if mask is not None and (window is not None or T % (2 * mask.block_length)):
        raise ValueError(f"{mask} over {T} positions, window {window}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    compiled = on_tpu() if interpret is None else not interpret
    if not (compiled or interpret):
        return reference_attention(q, k, v, window, float(sm_scale), mask)
    if mask is not None:
        blk = min(block, T // 2)
        if blk % LANE or (T // 2) % blk or blk % mask.block_length:
            raise ValueError(f"{mask}: a stream of {T // 2} positions in tiles "
                             f"of {blk}")
        geom = _BlockDiffusion(mask.block_length, blk, T // 2 // blk)
        return _blocked(q, k, v, geom, float(sm_scale), not compiled)
    blk = min(block, -(-T // LANE) * LANE)
    pad = -T % blk
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for x in (q, k, v))
    out = _blocked(q, k, v, _Causal(window, blk, (T + pad) // blk),
                   float(sm_scale), not compiled)
    return out[:, :, :T] if pad else out
