"""Rows to the experts held here and back, with no token dropped.

A sparse-expert layer routes every token over all of the model's experts
and computes the part of the result that the experts it holds give
(``[lo, lo + n_held)``: one chip's share of an expert-parallel deployment).
``plan`` turns the router's choices ``[N, k]`` into the row layout that
``ops/grouped_matmul.py`` multiplies: the (token, choice) pairs whose expert
is held, grouped by expert in token order, each expert's rows starting on a
tile boundary, every expert owning at least one tile. The layout's *shapes*
are sized for the worst case (all ``N * k`` pairs held), so whatever the
imbalance nothing is cut and no shape depends on the data; the *work* is
not: the used tiles all stand at the front (rows from ``n_used * tm`` on
hold no pair), the products skip the tiles past ``n_used``, and every pass
over the sorted rows here (``on_used_rows``) is a loop over the used tiles
alone. Rows inside a used tile that hold no pair are zero; rows past the
last used tile may hold anything and are never read for a result.

``dispatch`` and ``combine`` are each other's transpose and say so to
autodiff. Rows are made by gathers; tokens are summed by adding the used
tiles' rows to them, a tile a step: on a v5e at the benchmark cell's shapes
(16 of 64 experts held, 67-80 of 272 tiles used) that takes 2.1 ms where
eight gathers of ``N`` rows, one a choice, take 3.3, and it costs by the
tile: past some 110 used tiles of 272 the gathers would be the faster.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from speakingstyle_tpu.ops import on_tpu

# rows one step of a pass over the sorted rows takes (whole tiles)
_CHUNK_ROWS = 2048


class Plan(NamedTuple):
    row_pair: jax.Array      # [M] the pair (token * k + choice) in a row, -1 in none
    pair_row: jax.Array      # [N, k] the row of a pair, M where its expert is not held
    tile_expert: jax.Array   # [M / tm] the (local) expert of a tile, ascending
    n_used: jax.Array        # [1] tiles that hold rows
    counts: jax.Array        # [n_held] pairs each held expert drew


def worst_tiles(pairs: int, n_held: int, tm: int) -> int:
    """Tiles the layout of ``pairs`` (token, choice) pairs is sized for:
    every pair held, every expert's last tile partly empty."""
    return -(-pairs // tm) + n_held


def tiles_of(counts, tm: int):
    """Tiles each held expert's pairs take: at least one."""
    return jnp.maximum(-(-counts // tm), 1)


def plan(expert_idx, lo: int, n_held: int, tm: int) -> Plan:
    N, k = expert_idx.shape
    P = N * k
    n_tiles = worst_tiles(P, n_held, tm)
    M = n_tiles * tm
    local = expert_idx.reshape(P) - lo
    held = (local >= 0) & (local < n_held)
    onehot = local[:, None] == jnp.arange(n_held, dtype=local.dtype)[None, :]
    seen = jnp.cumsum(onehot.astype(jnp.int32), axis=0)   # [P, n_held]
    counts = seen[-1]
    rank = jnp.sum(jnp.where(onehot, seen, 0), axis=1) - 1
    tiles = tiles_of(counts, tm)
    tile_end = jnp.cumsum(tiles)
    first_row = (tile_end - tiles) * tm
    pair_row = jnp.where(
        held, first_row[jnp.clip(local, 0, n_held - 1)] + rank, M)
    row_pair = jnp.full((M,), -1, jnp.int32).at[pair_row].set(
        jnp.arange(P, dtype=jnp.int32), mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(n_tiles), side="right"),
        n_held - 1).astype(jnp.int32)
    return Plan(row_pair, pair_row.reshape(N, k).astype(jnp.int32),
                tile_expert, tile_end[-1:].astype(jnp.int32), counts)


def _unfilled(like, xs):
    """Buffers (``like``: a tuple of shapes and dtypes) for a pass over the
    rows ``xs`` to write into. On a TPU nothing fills them (a kernel that
    writes nothing: filling ``[M, d]`` costs as much as the used rows' pass).
    The kernel takes the pass's own operands, untouched, so that the buffers
    are the pass's alone and made where it runs: buffers that hang on
    nothing (``lax.empty``) are shared between passes or lifted out of the
    loop over the batch rows, and then copied whole before each pass writes
    into them."""
    if not on_tpu():
        return tuple(jnp.zeros(o.shape, o.dtype) for o in like)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        lambda *refs: None, out_shape=like,
        in_specs=[anywhere] * len(xs), out_specs=[anywhere] * len(like))(*xs)


def _used_rows(fn, p: Plan, *xs):
    """``fn`` over the rows of ``xs`` (each ``[M, ...]``; ``fn`` takes and
    gives rows, one for one) in the tiles the plan uses: ``_CHUNK_ROWS`` at
    a time in whole tiles, as many steps as ``n_used`` asks for. The last
    step reaches back over rows already done rather than past the end.
    What ``fn`` gives, ``[M, ...]`` each; the rows no step reached are left
    as the buffer came (``_unfilled``)."""
    M = p.row_pair.shape[0]
    tm = M // p.tile_expert.shape[0]
    size = min(M, max(_CHUNK_ROWS // tm, 1) * tm)

    def on(start):
        return fn(*(lax.dynamic_slice_in_dim(x, start, size) for x in xs))

    def step(i, outs):
        start = jnp.minimum(i * size, M - size)
        return jax.tree.map(
            lambda out, rows: lax.dynamic_update_slice_in_dim(out, rows, start, 0),
            outs, on(start))

    chunks, tree = jax.tree.flatten(jax.eval_shape(on, 0))
    outs = _unfilled(tuple(jax.ShapeDtypeStruct((M,) + c.shape[1:], c.dtype)
                           for c in chunks), xs)
    return lax.fori_loop(0, -(-(p.n_used[0] * tm) // size), step,
                         jax.tree.unflatten(tree, outs))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def on_used_rows(fn, p: Plan, *xs):
    """A row-for-row ``fn`` of the sorted rows ``xs`` (``[M, ...]`` each),
    computed forward and backward over the used tiles only."""
    return _used_rows(fn, p, *xs)


def _on_used_rows_fwd(fn, p, *xs):
    return _used_rows(fn, p, *xs), (p, xs)


def _on_used_rows_bwd(fn, res, dout):
    p, xs = res

    def back(dout, *xs):
        return jax.vjp(fn, *xs)[1](dout)

    return (None,) + tuple(_used_rows(back, p, dout, *xs))


on_used_rows.defvjp(_on_used_rows_fwd, _on_used_rows_bwd)


@jax.custom_vjp
def twice(rows, p: Plan):
    """The sorted rows for two readers: autodiff would add their two
    gradients over all ``M`` rows, this adds the used tiles'."""
    return rows, rows


twice.defvjp(lambda rows, p: ((rows, rows), p),
             lambda p, douts: (_used_rows(jnp.add, p, *douts), None))


def _tokens_of(y, p: Plan, weights=None):
    """``[M, d] -> [N, d]``: the sum of a token's held pairs' rows, each
    times its weight, in float32. The transposed form, a used tile a step:
    the tile's rows are added to their tokens; rows that hold no pair are
    dropped, not added."""
    M = y.shape[0]
    N, k = p.pair_row.shape
    tm = M // p.tile_expert.shape[0]

    def step(i, out):
        row_pair = lax.dynamic_slice_in_dim(p.row_pair, i * tm, tm)
        rows = lax.dynamic_slice_in_dim(y, i * tm, tm).astype(jnp.float32)
        pair = jnp.maximum(row_pair, 0)
        if weights is not None:
            rows = rows * jnp.take(weights.reshape(-1), pair)[:, None]
        token = jnp.where(row_pair >= 0, pair // k, N)
        return out.at[token].add(rows, mode="drop")

    out = jnp.zeros((N, y.shape[1]), jnp.float32)
    return lax.fori_loop(0, p.n_used[0], step, out).astype(y.dtype)


@jax.custom_vjp
def dispatch(x, p: Plan):
    """Tokens ``[N, d]`` into the sorted, tile-padded rows ``[M, d]``: each
    used row's token, zero where no pair stands."""
    k = p.pair_row.shape[1]

    def rows(row_pair):
        took = jnp.take(x, jnp.maximum(row_pair, 0) // k, axis=0)
        return jnp.where((row_pair >= 0)[:, None], took, jnp.zeros((), x.dtype))

    return _used_rows(rows, p, p.row_pair)


def _dispatch_fwd(x, p):
    return dispatch(x, p), p


def _dispatch_bwd(p, dy):
    return _tokens_of(dy, p), None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(y, weights, p: Plan):
    """Rows ``[M, d]`` back to tokens ``[N, d]``: the sum over a token's
    held choices of ``weights[token, choice] * y[row]``."""
    return _tokens_of(y, p, weights)


def _combine_fwd(y, weights, p):
    return combine(y, weights, p), (y, weights, p)


def _combine_bwd(res, dout):
    y, weights, p = res
    M, k = y.shape[0], p.pair_row.shape[1]

    # one pass over the used rows gives both: a row's share of its token's
    # ``dout``, and the weight's gradient as the row's dot with it (float32)
    def rows(row_pair, y):
        pair = jnp.maximum(row_pair, 0)
        valid = row_pair >= 0
        g = jnp.take(dout, pair // k, axis=0).astype(jnp.float32)
        w = jnp.take(weights.reshape(-1), pair)
        dy = jnp.where(valid[:, None], g * w[:, None], 0.0).astype(y.dtype)
        dot = jnp.sum(y.astype(jnp.float32) * g, axis=1)
        return dy, jnp.where(valid, dot, 0.0)

    dy, dw_row = _used_rows(rows, p, p.row_pair, y)
    dw = jnp.where(p.pair_row < M,
                   jnp.take(dw_row, jnp.minimum(p.pair_row, M - 1)), 0.0)
    return dy, dw.astype(weights.dtype), None


combine.defvjp(_combine_fwd, _combine_bwd)
