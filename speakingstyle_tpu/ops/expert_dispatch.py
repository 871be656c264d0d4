"""Rows to the experts held here and back, with no token dropped.

A sparse-expert layer routes every token over all of the model's experts
and computes the part of the result that the experts it holds give
(``[lo, lo + n_held)``: one chip's share of an expert-parallel deployment).
``plan`` turns the router's choices ``[N, k]`` into the row layout that
``ops/grouped_matmul.py`` multiplies: the (token, choice) pairs whose expert
is held, grouped by expert in token order, each expert's rows starting on a
tile boundary, every expert owning at least one tile. The layout is sized
for the worst case (all ``N * k`` pairs held), so whatever the imbalance
nothing is cut; the tiles past ``n_used`` cost no product.

``dispatch`` and ``combine`` are each other's transpose and say so to
autodiff: both directions are gathers (a scatter-add of rows is the slow
way on a TPU).
"""

from typing import NamedTuple

import jax
import jax.numpy as jnp


class Plan(NamedTuple):
    row_pair: jax.Array      # [M] the pair (token * k + choice) in a row, -1 in none
    pair_row: jax.Array      # [N, k] the row of a pair, M where its expert is not held
    tile_expert: jax.Array   # [M / tm] the (local) expert of a tile, ascending
    n_used: jax.Array        # [1] tiles that hold rows
    counts: jax.Array        # [n_held] pairs each held expert drew


def plan(expert_idx, lo: int, n_held: int, tm: int) -> Plan:
    N, k = expert_idx.shape
    P = N * k
    n_tiles = -(-P // tm) + n_held
    M = n_tiles * tm
    local = expert_idx.reshape(P) - lo
    held = (local >= 0) & (local < n_held)
    onehot = local[:, None] == jnp.arange(n_held, dtype=local.dtype)[None, :]
    seen = jnp.cumsum(onehot.astype(jnp.int32), axis=0)   # [P, n_held]
    counts = seen[-1]
    rank = jnp.sum(jnp.where(onehot, seen, 0), axis=1) - 1
    tiles = jnp.maximum(-(-counts // tm), 1)
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


def _rows_of(x, p: Plan, k: int):
    """``[N, d] -> [M, d]``: each row's token, zero where no pair stands."""
    valid = p.row_pair >= 0
    rows = jnp.take(x, jnp.maximum(p.row_pair, 0) // k, axis=0)
    return jnp.where(valid[:, None], rows, jnp.zeros((), x.dtype))


def _tokens_of(y, p: Plan, weights=None):
    """``[M, d] -> [N, d]``: the sum of a token's held pairs' rows, each
    times its weight. Float32 sum, one choice at a time (``[N, k, d]`` never
    stands whole)."""
    M = y.shape[0]
    out = jnp.zeros((p.pair_row.shape[0], y.shape[1]), jnp.float32)
    for c in range(p.pair_row.shape[1]):
        row = p.pair_row[:, c]
        # a row that is not there reads as zero (where, not times zero:
        # the tiles past n_used are never written and may hold anything)
        part = jnp.where((row < M)[:, None],
                         jnp.take(y, jnp.minimum(row, M - 1), axis=0), 0)
        part = part.astype(jnp.float32)
        if weights is not None:
            part = part * weights[:, c, None]
        out = out + part
    return out.astype(y.dtype)


@jax.custom_vjp
def dispatch(x, p: Plan):
    """Tokens ``[N, d]`` into the sorted, tile-padded rows ``[M, d]``."""
    return _rows_of(x, p, p.pair_row.shape[1])


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
    w_row = jnp.take(weights.reshape(-1), jnp.maximum(p.row_pair, 0))
    dy = (_rows_of(dout, p, k).astype(jnp.float32)
          * w_row[:, None]).astype(y.dtype)
    dw = []
    for c in range(k):
        row = p.pair_row[:, c]
        part = jnp.take(y, jnp.minimum(row, M - 1), axis=0)
        dot = jnp.sum(part.astype(jnp.float32) * dout.astype(jnp.float32), axis=1)
        dw.append(jnp.where(row < M, dot, 0.0))
    return dy, jnp.stack(dw, axis=1).astype(weights.dtype), None


combine.defvjp(_combine_fwd, _combine_bwd)
