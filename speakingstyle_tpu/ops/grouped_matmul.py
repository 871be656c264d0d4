"""Grouped matrix product over the experts a chip holds: Pallas TPU kernels
forward and backward, over rows sorted by expert (ROADMAP B3).

The rows of ``x`` ``[M, K]`` stand in tiles of ``tm``, and every tile
belongs to one expert: the dispatch (models/mellum.py) lays an expert's rows
out from a tile boundary on and leaves the rest of its last tile zero, and
gives every expert at least one tile. So the product is a plain tiled
matmul in which each row tile picks its expert's ``[K, N]`` weights by a
prefetched scalar, and no row is ever dropped: an expert that draws ten
times its share gets ten times the tiles. ``tile_expert`` ``[tiles]`` is
that map (ascending), ``n_used`` ``[1]`` how many tiles hold rows; the grid
is as long as the worst case needs (every pair held here) and the steps
past ``n_used`` neither compute nor move data (their index maps stay on
the last used block).

- ``gmm``: ``out[tile] = x[tile] @ w[expert(tile)]`` (or ``@ w[...].T``: the
  backward's ``dx``).
- ``tgmm``: ``dw[e] = sum over e's tiles of x[tile].T @ dy[tile]``, float32,
  accumulated in the output block while consecutive tiles share an expert.

An expert's whole ``[K, N]`` weight is one block, so consecutive tiles of
one expert fetch it once. Off a TPU the plain ``jnp`` reference runs
(``interpret=True`` emulates the kernels: the parity tests).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from speakingstyle_tpu.ops import on_tpu

TILE_ROWS = 256
# an expert's weight block twice (double-buffered) beside the row tiles:
# 2304 x 896 float32 out of tgmm is 8.3 MB a buffer
_VMEM_LIMIT = 96 * 1024 * 1024
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _gmm_kernel(te_ref, nu_ref, x_ref, w_ref, o_ref, *, transpose_w):
    @pl.when(pl.program_id(0) < nu_ref[0])
    def _():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], _NT if transpose_w else _NN,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _tgmm_kernel(te_ref, nu_ref, x_ref, dy_ref, o_ref):
    i, used = pl.program_id(0), nu_ref[0]
    last = jnp.minimum(i, used - 1)
    first = (i == 0) | (te_ref[last] != te_ref[jnp.maximum(last - 1, 0)])

    @pl.when((i < used) & first)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(i < used)
    def _():
        o_ref[0] += jax.lax.dot_general(x_ref[...], dy_ref[...], _TN,
                                        preferred_element_type=jnp.float32)


def _row_tile(width, tm):
    return pl.BlockSpec(
        (tm, width), lambda i, te, nu: (jnp.minimum(i, nu[0] - 1), 0))


def _expert_block(shape):
    return pl.BlockSpec(
        (1,) + shape, lambda i, te, nu: (te[jnp.minimum(i, nu[0] - 1)], 0, 0))


_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                               vmem_limit_bytes=_VMEM_LIMIT)


def gmm(x, w, tile_expert, n_used, tm: int, transpose_w: bool = False,
        interpret: Optional[bool] = None):
    """``[M, K] x [E, K, N] -> [M, N]`` (``w`` ``[E, N, K]`` if
    ``transpose_w``). Rows of tiles past ``n_used`` are left unwritten."""
    M, K = x.shape
    N = w.shape[1] if transpose_w else w.shape[2]
    compiled = on_tpu() if interpret is None else not interpret
    if not (compiled or interpret):
        wt = w[tile_expert].astype(x.dtype)
        out = jnp.einsum("tmk,tnk->tmn" if transpose_w else "tmk,tkn->tmn",
                         x.reshape(-1, tm, K), wt,
                         preferred_element_type=jnp.float32)
        return out.reshape(M, N).astype(x.dtype)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_w=transpose_w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(M // tm,),
            in_specs=[_row_tile(K, tm), _expert_block(w.shape[1:])],
            out_specs=_row_tile(N, tm)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=_PARAMS, interpret=not compiled,
    )(tile_expert, n_used, x, w.astype(x.dtype))


def tgmm(x, dy, tile_expert, n_used, tm: int, n_experts: int,
         interpret: Optional[bool] = None):
    """``[M, K], [M, N] -> [E, K, N]`` float32: each expert's tiles'
    ``x.T @ dy``. Every expert owns at least one tile."""
    (M, K), N = x.shape, dy.shape[1]
    compiled = on_tpu() if interpret is None else not interpret
    if not (compiled or interpret):
        used = (jnp.arange(M // tm) < n_used[0])[:, None, None]
        per_tile = jnp.einsum("tmk,tmn->tkn", x.reshape(-1, tm, K),
                              dy.reshape(-1, tm, N),
                              preferred_element_type=jnp.float32)
        return jax.ops.segment_sum(jnp.where(used, per_tile, 0.0), tile_expert,
                                   n_experts)
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(M // tm,),
            in_specs=[_row_tile(K, tm), _row_tile(N, tm)],
            out_specs=_expert_block((K, N))),
        out_shape=jax.ShapeDtypeStruct((n_experts, K, N), jnp.float32),
        compiler_params=_PARAMS, interpret=not compiled,
    )(tile_expert, n_used, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def grouped_matmul(x, w, tile_expert, n_used, tm: int = TILE_ROWS,
                   interpret: Optional[bool] = None):
    """``x[tile] @ w[expert(tile)]`` with its exact gradients for ``x`` and
    ``w`` (``w`` in any float dtype: it is cast to ``x``'s for the product,
    and its gradient comes back in its own)."""
    return gmm(x, w, tile_expert, n_used, tm, interpret=interpret)


def _gm_fwd(x, w, tile_expert, n_used, tm, interpret):
    return (gmm(x, w, tile_expert, n_used, tm, interpret=interpret),
            (x, w, tile_expert, n_used))


def _gm_bwd(tm, interpret, res, dy):
    x, w, tile_expert, n_used = res
    dx = gmm(dy, w, tile_expert, n_used, tm, transpose_w=True,
             interpret=interpret)
    dw = tgmm(x, dy, tile_expert, n_used, tm, w.shape[0], interpret=interpret)
    return dx, dw.astype(w.dtype), None, None


grouped_matmul.defvjp(_gm_fwd, _gm_bwd)
