"""What lies between a query or key projection and the attention core, in
one pass each way: the head-wise RMSNorm (where the model has one), the
rotary rotation and the change of layout from the projection's
``[B, T, H * D]`` to the core's ``[B, H, T, D]``, as one Pallas TPU kernel
forward and one backward.

    n = x * rsqrt(mean_D(x^2) + eps) * scale        (left out without ``scale``)
    y = n * cos + rotate_half(n) * sin              rotate_half(n) = [-n_hi ; n_lo]

By parts these are float32 element-wise passes over the whole of ``q`` with
a transpose behind them, each differentiated on its own and all of it run
again under the attention half's rematerialisation. Here a grid step loads
the rows of a tile for a few heads by their column blocks, computes in
float32 (the statistics over a head's 128 lanes, the rotation as a lane
roll by half a head and a sign), rounds **once**, to ``dtype``, and writes
each head's block where the core reads it, through the output's index map.
The backward reads ``dy`` ``[B, H, T, D]`` and (under the norm) ``x`` again,
undoes rotation and norm in float32 and writes ``dx`` ``[B, T, H * D]`` in
``dy``'s dtype in one pass; ``scale``'s gradient leaves the kernel as
float32 partial sums, eight sublanes a grid step, which are added in float32
outside: no sum anywhere is carried in a narrower type. The tables get no
gradient.

``x`` may be wider than ``dtype``: a projection's float32 accumulators, not
yet rounded. By parts XLA ran it so on a TPU (its product wrote float32 and
the passes behind it read that: the rounding between them, written in the
program, was never made), and a program that rounds there reads otherwise
against a float32 reference where a router's choice hangs by a thread
(PERF.md, PR 35). So the one rounding here is the only one between the
product's accumulator and the core's operand, as it was.

One kernel, two static variants: with ``scale`` (the model's ``qk_norm``)
and without (the statistics left out of the same pass).

Off a TPU, and for a head size that is not a whole number of lane tiles or
a length that is not a whole number of sublane tiles, ``otherwise`` runs in
the kernel's place: the caller's passes by parts (``models/mellum.py``
hands its own ``heads_by_parts``). ``interpret=True`` emulates the kernels
(the parity tests).
"""

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from speakingstyle_tpu.ops import on_tpu

LANE = 128
SUBLANE = 16       # a bfloat16 tile's rows: what a length must divide by
TILE_ROWS = 512    # rows a grid step takes (fewer where the length is shorter)
HEADS = 4          # heads a grid step takes: 2 KB of a float32 projection's row
# the kernels' names in a program, by variant: ``launches`` counts them
NAMES = {"norm": "qk_prepare_norm", "plain": "qk_prepare_plain"}


def fits(T: int, D: int) -> bool:
    """Whether the kernels' tiles divide a length and a head size."""
    return D % LANE == 0 and T % SUBLANE == 0


def _sign(D):
    """-1 on a head's first half, +1 on its second: with a roll by half a
    head, rotate-half (and, the other way round, its transpose)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, D), 1)
    return jnp.where(lane < D // 2, -1.0, 1.0).astype(jnp.float32)


def _rrms(x, eps):
    """One over the root mean square of each row's lanes, ``[rows, 1]``."""
    return jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _fwd_kernel(x_ref, cos_ref, sin_ref, *refs, heads, eps, norm):
    """refs: ``scale`` (under the norm), then the output."""
    o_ref = refs[-1]
    D = cos_ref.shape[-1]
    cos = cos_ref[...]
    sin = sin_ref[...] * _sign(D)      # rotate_half(n) * sin = roll(n) * (sign * sin)
    for h in range(heads):
        n = x_ref[0, :, h * D:(h + 1) * D].astype(jnp.float32)
        if norm:
            n = n * _rrms(n, eps) * refs[0][...]
        y = n * cos + pltpu.roll(n, D // 2, 1) * sin
        o_ref[0, h] = y.astype(o_ref.dtype)


def _bwd_kernel(dy_ref, cos_ref, sin_ref, *refs, heads, eps, norm):
    """refs: with the norm ``x``, ``scale``, then ``dx`` and the partial sums
    of ``scale``'s gradient; without, ``dx`` alone (the rotation's transpose
    needs no ``x``)."""
    dx_ref = refs[2 if norm else 0]
    D = cos_ref.shape[-1]
    cos = cos_ref[...]
    sin = sin_ref[...] * _sign(D)
    if norm:
        x_ref, scale = refs[0], refs[1][...]
        ds = jnp.zeros((8, D), jnp.float32)
    for h in range(heads):
        dy = dy_ref[0, h].astype(jnp.float32)
        # the rotation's transpose: y = n cos + roll(n) s, s = sign * sin,
        # so dn = dy cos + roll(dy s) (a roll by half a head is its own inverse)
        dn = dy * cos + pltpu.roll(dy * sin, D // 2, 1)
        if norm:
            x = x_ref[0, :, h * D:(h + 1) * D].astype(jnp.float32)
            r = _rrms(x, eps)
            xr = x * r
            # eight sublanes of partial sums a step: vector adds, no reduce
            ds = ds + jnp.sum((dn * xr).reshape(-1, 8, D), axis=0)
            g = dn * scale
            dn = r * (g - xr * jnp.mean(g * xr, axis=-1, keepdims=True))
        dx_ref[0, :, h * D:(h + 1) * D] = dn.astype(dx_ref.dtype)
    if norm:
        refs[3][0, 0, 0] = ds


def _blocks(B, T, heads, D):
    """(batch, row tiles, head groups), the specs of a ``[B, T, H * D]``
    block, a ``[B, H, T, D]`` block, a ``[T, D]`` table's block and the
    ``[1, D]`` scale, and the heads a step takes. Head groups move fastest:
    a tile's tables are fetched once."""
    tile, hb = math.gcd(T, TILE_ROWS), math.gcd(heads, HEADS)
    return ((B, T // tile, heads // hb),
            pl.BlockSpec((1, tile, hb * D), lambda b, i, g: (b, i, g)),
            pl.BlockSpec((1, hb, tile, D), lambda b, i, g: (b, g, i, 0)),
            pl.BlockSpec((tile, D), lambda b, i, g: (i, 0)),
            pl.BlockSpec((1, D), lambda b, i, g: (0, 0)), hb)


def _params(grid):
    return pltpu.CompilerParams(dimension_semantics=("parallel",) * len(grid))


def _forward(x, cos, sin, scale, heads, eps, dtype, interpret):
    B, T, D = x.shape[0], *cos.shape
    grid, rows, by_head, table, one_row, hb = _blocks(B, T, heads, D)
    norm = scale is not None
    scales = [scale.reshape(1, D).astype(jnp.float32)] if norm else []
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=hb, eps=eps, norm=norm),
        grid=grid, in_specs=[rows, table, table] + [one_row] * norm,
        out_specs=by_head,
        out_shape=jax.ShapeDtypeStruct((B, heads, T, D), dtype),
        compiler_params=_params(grid), interpret=interpret,
        name=NAMES["norm" if norm else "plain"],
    )(x, cos, sin, *scales)


def _backward(x, cos, sin, scale, dy, eps, interpret):
    """(dx, dscale); without the norm ``x`` and ``scale`` are None, and
    ``dscale`` with them."""
    B, heads, T, D = dy.shape
    grid, rows, by_head, table, one_row, hb = _blocks(B, T, heads, D)
    norm = scale is not None
    call = functools.partial(
        pl.pallas_call,
        functools.partial(_bwd_kernel, heads=hb, eps=eps, norm=norm), grid=grid,
        compiler_params=_params(grid), interpret=interpret,
        name=NAMES["norm" if norm else "plain"] + "_bwd")
    dx = jax.ShapeDtypeStruct((B, T, heads * D), dy.dtype)
    if not norm:
        return call(in_specs=[by_head, table, table], out_specs=rows,
                    out_shape=dx)(dy, cos, sin), None
    dx, parts = call(
        in_specs=[by_head, table, table, rows, one_row],
        out_specs=[rows, pl.BlockSpec((1, 1, 1, 8, D),
                                      lambda b, i, g: (b, i, g, 0, 0))],
        out_shape=[dx, jax.ShapeDtypeStruct(grid + (8, D), jnp.float32)],
    )(dy, cos, sin, x, scale.reshape(1, D).astype(jnp.float32))
    return dx, jnp.sum(parts, axis=(0, 1, 2, 3)).astype(scale.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _prepare(x, cos, sin, scale, heads, eps, dtypes, interpret):
    return _forward(x, cos, sin, scale, heads, eps, dtypes[1], interpret)


def _prepare_fwd(x, cos, sin, scale, heads, eps, dtypes, interpret):
    # the rotation's transpose needs no ``x``: kept only under the norm
    return (_forward(x, cos, sin, scale, heads, eps, dtypes[1], interpret),
            (None if scale is None else x, cos, sin, scale))


def _prepare_bwd(heads, eps, dtypes, interpret, res, dy):
    x, cos, sin, scale = res
    dx, dscale = _backward(x, cos, sin, scale, dy, eps, interpret)
    return dx.astype(dtypes[0]), jnp.zeros_like(cos), jnp.zeros_like(sin), dscale


_prepare.defvjp(_prepare_fwd, _prepare_bwd)


def qk_prepare(x, cos, sin, scale=None, *, heads: int, eps: float = 0.0,
               dtype=None, otherwise: Optional[Callable] = None,
               interpret: Optional[bool] = None):
    """x ``[B, T, H * D]`` (a projection's output, in ``dtype`` or wider),
    cos and sin ``[T, D]`` float32, ``scale`` ``[D]`` or None (no norm) ->
    ``[B, H, T, D]`` in ``dtype`` (x's own if None): normed, rotated and laid
    out for the attention core.

    ``interpret=None`` compiles the kernels on a TPU and calls
    ``otherwise(x, cos, sin, scale, heads, eps, dtype)`` on any other
    backend; ``True`` emulates them; ``False`` compiles them
    unconditionally. Shapes the tiles do not divide (``fits``) go to
    ``otherwise`` whatever ``interpret`` says; with none given that is an
    error."""
    T, D = cos.shape
    if x.shape[1] != T or x.shape[2] != heads * D:
        raise ValueError(f"{x.shape} for {heads} heads under tables {cos.shape}")
    dtype = jnp.dtype(x.dtype if dtype is None else dtype)
    compiled = on_tpu() if interpret is None else not interpret
    if not (compiled or interpret) or not fits(T, D):
        if otherwise is None:
            raise ValueError(f"no kernel for {x.shape} on this backend, and "
                             "no passes by parts to run in its place")
        return otherwise(x, cos, sin, scale, heads, eps, dtype)
    return _prepare(x, cos, sin, scale, heads, float(eps),
                    (jnp.dtype(x.dtype), dtype), not compiled)


def launches(closed_jaxpr) -> dict:
    """The kernels' launches in a program by variant (``norm``, ``plain``),
    forward and backward together: 0 and 0 where ``otherwise`` ran. What is
    dead goes first: a rematerialised region lists its whole forward until
    what its backward never reads is taken out."""
    from jax.interpreters import partial_eval as pe

    jaxpr = closed_jaxpr.jaxpr
    live = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))[0]
    found = dict.fromkeys(NAMES, 0)

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params.get("name")
                for variant, stem in NAMES.items():
                    found[variant] += name in (stem, stem + "_bwd")
                continue
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) else [value]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(live)
    return found
