"""Ring attention: sequence-parallel exact attention over an ICI ring.

The long-context path the reference lacks entirely (SURVEY.md §5): queries
stay resident on their shard while key/value blocks rotate around the mesh
axis via `ppermute`; a streaming (flash-style) log-sum-exp accumulator makes
the result exactly equal to full softmax attention over the whole sequence.
Communication overlaps with compute in XLA's pipeline, and per-device memory
is O(L_local²·0 + L_local·d) — no [L, L] materialization anywhere.

Layout contract (under `shard_map` over axis ``axis_name``):
  q, k, v : [B, H, L_local, D]   (sequence axis sharded)
  bias    : [B, 1, 1, L_local]   additive key-padding bias, sharded like k

`ring_attention(...)` is the sharded kernel; `ring_self_attention(...)`
wraps it in shard_map over a mesh for direct use.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _block_attn(q, k, v, bias, scale):
    """One q-block × kv-block pass -> (unnormalized out, row max, row sumexp)."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if bias is not None:
        logits = logits + bias
    m = jnp.max(logits, axis=-1, keepdims=True)  # [B,H,Lq,1]
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return o, m, l


def ring_attention(q, k, v, bias=None, axis_name: str = "seq", scale: Optional[float] = None):
    """Exact attention with K/V rotating around `axis_name`.

    Call inside shard_map; every rank holds one sequence block of q/k/v.
    Returns the attention output for the local q block: [B, H, L_local, D].
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    o, m, l = _block_attn(q, k, v, bias, scale)

    def body(_, carry):
        o, m, l, k, v, bias = carry
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        if bias is not None:
            bias = jax.lax.ppermute(bias, axis_name, perm)
        o_new, m_new, l_new = _block_attn(q, k, v, bias, scale)
        # streaming softmax merge
        m_tot = jnp.maximum(m, m_new)
        alpha = jnp.exp(m - m_tot)
        beta = jnp.exp(m_new - m_tot)
        o = o * alpha + o_new * beta
        l = l * alpha + l_new * beta
        return o, m_tot, l, k, v, bias

    o, m, l, _, _, _ = jax.lax.fori_loop(0, n - 1, body, (o, m, l, k, v, bias))
    return o / jnp.maximum(l, 1e-30)


def ring_self_attention(
    q, k, v, bias=None, mesh: Optional[Mesh] = None, axis_name: str = "seq"
):
    """shard_map wrapper: q/k/v [B, H, L, D] (global), bias [B, 1, 1, L].

    Shards the L axis over `axis_name`, runs the ring, returns the global
    [B, H, L, D] output (sharded the same way).
    """
    if mesh is None:
        raise ValueError("ring_self_attention requires a mesh")
    qkv_spec = P(None, None, axis_name, None)
    bias_spec = P(None, None, None, axis_name)
    in_specs = (qkv_spec, qkv_spec, qkv_spec, bias_spec if bias is not None else None)
    fn = functools.partial(ring_attention, axis_name=axis_name)

    if bias is None:
        sharded = jax.shard_map(
            lambda q, k, v: fn(q, k, v, None),
            mesh=mesh, in_specs=in_specs[:3], out_specs=qkv_spec,
        )
        return sharded(q, k, v)
    sharded = jax.shard_map(
        lambda q, k, v, b: fn(q, k, v, b),
        mesh=mesh, in_specs=in_specs, out_specs=qkv_spec,
    )
    return sharded(q, k, v, bias)
