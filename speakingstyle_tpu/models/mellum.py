"""The ``decoder_lm`` family: a pre-norm decoder language model with
grouped-query rotary attention (a window or the full causal triangle, by
layer) and a sparse-expert feed-forward that is told which experts it holds
(Mellum2-12B-A2.5B's block, SDAR-30B-A3B's; ``configs/config.py:DecoderLMConfig``).

    x_0 = E[ids]
    h   = x + Attn(RMSNorm(x))          self_attn  (ops/blocked_attention.py)
    y   = h + MoE(RMSNorm(h))           moe        (ops/grouped_matmul.py)
    loss = mean cross-entropy of id t+1 given ids <= t, over the held
           vocabulary rows, after a final RMSNorm and an untied head

**The second objective, ``block_diffusion``** (Arriola et al.,
arXiv:2503.09573, as SDAR, arXiv:2510.06303, trains): the decoder learns to
fill in a block of ``block_length`` tokens given the clean blocks before it.
A row of ``L`` tokens goes through the layers twice at once, as
``ids = [noised ; tokens]`` of ``2L`` positions: the loader's noised copy
(``mask_id`` where a token is masked) and the clean copy, a token's two
copies at one rotary position, under one mask in which a noised block sees
itself and the clean blocks before it and a clean block the clean blocks up
to itself (``blocked_attention``'s ``BlockDiffusion``). Every layer runs
over ``2L`` positions; the head runs on the noised half only, with no shift:

    loss = 1 / (B L) sum_{b,i} weight_{b,i} CE(logits_{b,i}, tokens_{b,i})

``weight`` is the loader's: ``1 / t`` where position ``i`` is masked (``t``
its block's masking probability), else 0. ``qk_norm`` puts an RMSNorm with
one learned scale of ``head_dim`` on each head's query and key before the
rotation (a property of the model: ``qk_prepare`` takes the scale or none).

**Expert-parallel share.** The router scores all ``num_experts`` and takes
the ``num_experts_per_tok`` largest; the layer computes the part of the sum
that experts ``[expert_offset, expert_offset + experts_held)`` give, for
every (token, choice) pair that names one of them, whatever the imbalance
(no capacity, no drop). What the absent experts would add is left out and
the partial result goes on. On one chip there is no exchange and nothing
stands in for one. Embedding and head hold ``vocab_held`` rows; the ids
and the loss are over that slice.

**Memory.** Parameters are float32, compute is ``compute_dtype``; softmax,
router, norms' statistics and the loss are float32. Each
layer keeps two residual-stream arrays for the backward pass and recomputes
its two halves: attention as one block but for the core's two results, the
output ``[B, H, T, D]`` and the rows' log-sum-exp ``[B, H, 1, T]`` float32,
which are kept by name (``KEEP_CORE``), so the backward never runs the
forward kernel again (norm, projections and what lies between a projection
and the core are recomputed: ``q``, ``k``, ``v`` are not kept. For ``q`` and
``k`` that is one pass each way, ``ops/qk_prepare.py``: the head-wise norm
under ``qk_norm``, the rotation and the core's ``[B, H, T, D]`` layout in
one kernel forward, run again in the backward, and one kernel backward,
from the projections' float32 accumulators (``dot_wide``) with one rounding,
as XLA ran the passes by parts; ``v`` and the core's output are transposed
by XLA. Off a TPU the einsum
reference has no such names and everything is recomputed, and
``heads_by_parts`` stands in ``qk_prepare``'s place); the experts one batch
row at a time
(a row's worst case, every pair held here, sizes the dispatch buffers'
shapes; the work over them follows the tiles the row's plan uses:
``ops/expert_dispatch.py``). The logits stand ``LOSS_CHUNK`` positions at a
time.

The step's counters come back with the loss (``aux``): per layer, the pairs
each held expert drew, the pairs routed to held experts, the pairs that
got a row (equal, or something was dropped), and the row tiles the plans
used beside the worst case the buffers are sized for.
"""

import functools
import math
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from speakingstyle_tpu.configs.config import DecoderLMConfig, RopeConfig
from speakingstyle_tpu.ops import expert_dispatch
from speakingstyle_tpu.ops.blocked_attention import (
    LSE_NAME, OUT_NAME, BlockDiffusion, blocked_attention)
from speakingstyle_tpu.ops.grouped_matmul import TILE_ROWS, grouped_matmul
from speakingstyle_tpu.ops.qk_prepare import qk_prepare

# positions whose float32 logits stand at once (fewer where a batch has fewer)
LOSS_CHUNK = 4096


def rope_inv_freq(rope: RopeConfig, head_dim: int) -> Tuple[np.ndarray, float]:
    """(inverse frequencies ``[head_dim / 2]``, the factor on cos and sin)."""
    k = np.arange(0, head_dim, 2, dtype=np.float64)
    extrapolation = rope.rope_theta ** (-k / head_dim)
    if rope.rope_type == "default":
        return extrapolation, 1.0
    interpolation = extrapolation / rope.factor

    def correction_dim(rotations):
        return head_dim * math.log(
            rope.original_max_position_embeddings / (rotations * 2 * math.pi)
        ) / (2 * math.log(rope.rope_theta))

    low = max(math.floor(correction_dim(rope.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rope.beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return interpolation * ramp + extrapolation * (1 - ramp), rope.attention_factor


def rope_tables(rope: RopeConfig, head_dim: int, length: int):
    """cos and sin ``[length, head_dim]`` (each half repeated: rotate-half)."""
    inv_freq, factor = rope_inv_freq(rope, head_dim)
    angles = np.arange(length, dtype=np.float64)[:, None] * inv_freq[None, :]
    angles = np.concatenate([angles, angles], axis=1)
    return (jnp.asarray(np.cos(angles) * factor, jnp.float32),
            jnp.asarray(np.sin(angles) * factor, jnp.float32))


def apply_rope(x, cos, sin):
    """x ``[B, T, H, D]``; rotate-half, in float32."""
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos[None, :, None, :]
            + rotated * sin[None, :, None, :]).astype(x.dtype)


def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        return rms_norm(x, scale, self.eps)


class HeadScale(nn.Module):
    """The learned ``scale`` of a head-wise RMSNorm (``q_norm``, ``k_norm``):
    the norm itself is part of ``qk_prepare``'s pass."""

    @nn.compact
    def __call__(self, head_dim):
        return self.param("scale", nn.initializers.ones, (head_dim,), jnp.float32)


def heads_by_parts(x, cos, sin, scale, heads, eps, dtype):
    """What ``qk_prepare`` does in one pass, a pass each: the projection's
    own rounding to ``dtype``, the head-wise norm (under ``scale``), the
    rotation, the transpose into ``[B, H, T, D]``. Where the kernel does not
    run (off a TPU, a head size or a length its tiles do not divide) this
    does, with this module's ``rms_norm`` and ``apply_rope`` as they stand
    when it is called."""
    B, T, _ = x.shape
    x = x.astype(dtype).reshape(B, T, heads, -1)
    if scale is not None:
        x = rms_norm(x, scale, eps)
    return apply_rope(x, cos, sin).transpose(0, 2, 1, 3)


INIT_STD = 0.02


def writes_std(cfg: DecoderLMConfig) -> float:
    """The initial scale of the projections that write into the residual
    stream (``o_proj``, the experts' ``down``): ``INIT_STD`` over the root of
    twice the model's depth (the whole ``layer_types`` list, also where
    ``num_hidden_layers`` runs only its head), as GPT-2 and Megatron-LM start.
    With the embedding at unit scale the stream then carries the token and
    the router's input depends on it; at ``INIT_STD`` everywhere the first
    attention output, much the same at every position, swamps the embedding
    and every token of a layer chooses the same experts."""
    return INIT_STD / math.sqrt(2 * len(cfg.layer_types))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _dot_wide(lhs, rhs, dimension_numbers):
    """A product that hands on its float32 accumulators, not yet rounded to
    the operands' dtype; backward as the narrow product's: the cotangent is
    rounded to that dtype first (never a float32 operand on the MXU)."""
    return jax.lax.dot_general(lhs, rhs, dimension_numbers,
                               preferred_element_type=jnp.float32)


def _dot_wide_fwd(lhs, rhs, dimension_numbers):
    return _dot_wide(lhs, rhs, dimension_numbers), (lhs, rhs)


def _dot_wide_bwd(dimension_numbers, operands, ct):
    narrow = lambda l, r: jax.lax.dot_general(l, r, dimension_numbers)
    return jax.vjp(narrow, *operands)[1](ct.astype(operands[0].dtype))


_dot_wide.defvjp(_dot_wide_fwd, _dot_wide_bwd)


def dot_wide(lhs, rhs, dimension_numbers, precision=None,
             preferred_element_type=None):
    """``_dot_wide`` under ``jax.lax.dot_general``'s signature (``nn.Dense``'s
    ``dot_general``)."""
    return _dot_wide(lhs, rhs, dimension_numbers)


def _dense(features, name, dtype, std=INIT_STD, wide=False):
    """``wide``: the output is the product's float32 accumulators (for
    ``qk_prepare``, which rounds once behind the norm and the rotation)."""
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    param_dtype=jnp.float32, name=name,
                    kernel_init=nn.initializers.normal(std),
                    dot_general=dot_wide if wide else None)


class SelfAttention(nn.Module):
    """The layer's first half, its norm included: ``Attn(RMSNorm(x))``."""

    cfg: DecoderLMConfig
    window: int  # 0: the full causal triangle
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, cos, sin):
        c = self.cfg
        B, T, _ = x.shape
        H, Hkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        u = RMSNorm(c.rms_norm_eps, name="input_norm")(x)
        q = _dense(H * D, "q_proj", self.dtype, wide=True)(u)
        k = _dense(Hkv * D, "k_proj", self.dtype, wide=True)(u)
        v = _dense(Hkv * D, "v_proj", self.dtype)(u).reshape(B, T, Hkv, D)
        q_scale = HeadScale(name="q_norm")(D) if c.qk_norm else None
        k_scale = HeadScale(name="k_norm")(D) if c.qk_norm else None
        # head-wise norm, rotation and the core's ``[B, H, T, D]`` layout: one
        # pass each way on a TPU, ``heads_by_parts`` elsewhere
        with jax.named_scope("qk_prepare"):
            q = qk_prepare(q, cos, sin, q_scale, heads=H, eps=c.rms_norm_eps,
                           dtype=self.dtype, otherwise=heads_by_parts)
            k = qk_prepare(k, cos, sin, k_scale, heads=Hkv, eps=c.rms_norm_eps,
                           dtype=self.dtype, otherwise=heads_by_parts)
        # the attention core, under a name of its own (the per-module
        # readers hold on to ``self_attn/core``, whatever implements it)
        with jax.named_scope("core"):
            o = blocked_attention(
                q, k, v.transpose(0, 2, 1, 3), window=self.window or None,
                sm_scale=1.0 / math.sqrt(D),
                **({"mask": BlockDiffusion(c.block_length)}
                   if c.block_diffusion else {}))
            o = o.transpose(0, 2, 1, 3).reshape(B, T, H * D)
        return _dense(c.hidden_size, "o_proj", self.dtype, writes_std(c))(o)


def tile_rows(cfg: DecoderLMConfig, tokens: int) -> int:
    """A row tile no longer than an expert's even share of a row's pairs."""
    share = tokens * cfg.num_experts_per_tok // cfg.num_experts
    return min(TILE_ROWS, max(8, share // 8 * 8))


def _swiglu(gate, up):
    return (jax.nn.silu(gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(gate.dtype)


def moe_row(h, norm_scale, w_router, w_gate, w_up, w_down, *,
            cfg: DecoderLMConfig):
    """``MoE(RMSNorm(h))`` for one row ``[T, d]``: (the held experts' part of
    the result, the router's choices ``[T, k]``, per held expert the pairs
    it drew, pairs routed to held experts, pairs that got a row)."""
    tm = tile_rows(cfg, h.shape[0])
    u = rms_norm(h, norm_scale, cfg.rms_norm_eps)
    with jax.named_scope("router"):
        logits = jnp.dot(u.astype(jnp.float32), w_router,
                         precision=jax.lax.Precision.HIGHEST)
        weights, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                     cfg.num_experts_per_tok)
        if cfg.norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    with jax.named_scope("dispatch"):
        plan = expert_dispatch.plan(idx, cfg.expert_offset, cfg.n_experts_held, tm)
        rows = expert_dispatch.dispatch(u, plan)
    with jax.named_scope("experts"):
        args = (plan.tile_expert, plan.n_used, tm)
        rows, rows_again = expert_dispatch.twice(rows, plan)
        gate = grouped_matmul(rows, w_gate, *args)
        up = grouped_matmul(rows_again, w_up, *args)
        act = expert_dispatch.on_used_rows(_swiglu, plan, gate, up)
        out_rows = grouped_matmul(act, w_down, *args)
    with jax.named_scope("combine"):
        out = expert_dispatch.combine(out_rows, weights, plan)
    local = idx - cfg.expert_offset
    routed = jnp.sum((local >= 0) & (local < cfg.n_experts_held))
    placed = jnp.sum(plan.row_pair >= 0)
    return out, idx, plan.counts, routed, placed


class Router(nn.Module):
    n_experts: int

    @nn.compact
    def __call__(self, d):
        return self.param("kernel", nn.initializers.normal(INIT_STD),
                          (d, self.n_experts), jnp.float32)


class Experts(nn.Module):
    n_held: int
    width: int
    down_std: float

    @nn.compact
    def __call__(self, d):
        init = nn.initializers.normal(INIT_STD)
        shape = (self.n_held, d, self.width)
        return (self.param("gate", init, shape, jnp.float32),
                self.param("up", init, shape, jnp.float32),
                self.param("down", nn.initializers.normal(self.down_std),
                           (self.n_held, self.width, d), jnp.float32))


class SparseMoE(nn.Module):
    """The layer's second half, its norm included, one batch row at a time."""

    cfg: DecoderLMConfig

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        d = h.shape[-1]
        scale = self.param("norm_scale", nn.initializers.ones, (d,), jnp.float32)
        w_router = Router(c.num_experts, name="router")(d)
        w_gate, w_up, w_down = Experts(
            c.n_experts_held, c.moe_intermediate_size, writes_std(c),
            name="experts")(d)

        def row(h_row):
            return moe_row(h_row, scale, w_router, w_gate, w_up, w_down, cfg=c)

        out, idx, counts, routed, placed = jax.lax.map(jax.checkpoint(row), h)
        # the tiles the rows' plans used (``plan.n_used``, by the plan's own
        # arithmetic) and the worst case their buffers are sized for
        rows, tokens, k = idx.shape
        tm = tile_rows(c, tokens)
        used = jnp.sum(expert_dispatch.tiles_of(counts, tm))
        worst = rows * expert_dispatch.worst_tiles(tokens * k, c.n_experts_held, tm)
        return out, (jnp.sum(counts, axis=0), jnp.sum(routed), jnp.sum(placed),
                     used, jnp.asarray(worst, jnp.int32), idx)


KEEP_CORE = jax.checkpoint_policies.save_only_these_names(OUT_NAME, LSE_NAME)


class DecoderLayer(nn.Module):
    cfg: DecoderLMConfig
    window: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, cos, sin):
        # recomputed backward but for the core's output and log-sum-exp,
        # which are kept: the forward kernel runs once a step
        attn = nn.remat(SelfAttention, policy=KEEP_CORE)
        h = x + attn(self.cfg, self.window, self.dtype, name="self_attn")(
            x, cos, sin)
        out, aux = SparseMoE(self.cfg, name="moe")(h)
        return h + out, aux


def next_token_loss(hidden, head, tokens, chunk: int = LOSS_CHUNK):
    """Mean cross-entropy of ``tokens[:, t + 1]`` from ``hidden[:, t]``
    (``[B, T, d]``, already normed) under ``head`` ``[d, V]``, float32, the
    logits standing ``chunk`` positions at a time and recomputed backward."""
    B, T, d = hidden.shape
    n = B * T
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)], axis=1).reshape(n)
    weight = jnp.concatenate(
        [jnp.ones((B, T - 1), jnp.float32), jnp.zeros((B, 1), jnp.float32)],
        axis=1).reshape(n)
    return weighted_cross_entropy(hidden, head, targets, weight, chunk) \
        / (B * (T - 1))


def block_diffusion_loss(hidden, head, tokens, weight, chunk: int = LOSS_CHUNK):
    """``1 / (B L)`` times the sum over the noised half's positions of
    ``weight`` times the cross-entropy of that position's own token (no
    shift) from ``hidden`` ``[B, L, d]``."""
    B, L, _ = hidden.shape
    return weighted_cross_entropy(
        hidden, head, tokens.reshape(-1),
        weight.astype(jnp.float32).reshape(-1), chunk) / (B * L)


def weighted_cross_entropy(hidden, head, targets, weight, chunk: int):
    """The sum over the ``n = B T`` positions of ``weight`` times
    ``logsumexp(hidden W) - (hidden W)[target]``, in chunks."""
    B, T, d = hidden.shape
    n = B * T
    chunk = min(chunk, n)
    pad = -n % chunk
    flat = jnp.pad(hidden.reshape(n, d), ((0, pad), (0, 0)))
    targets, weight = jnp.pad(targets, (0, pad)), jnp.pad(weight, (0, pad))
    w = head.astype(hidden.dtype)

    @jax.checkpoint
    def part(args):
        x, t, m = args
        logits = jnp.dot(x, w, preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - picked) * m)

    sums = jax.lax.map(part, (flat.reshape(-1, chunk, d),
                              targets.reshape(-1, chunk),
                              weight.reshape(-1, chunk)))
    return jnp.sum(sums)


class LMHead(nn.Module):
    vocab: int

    @nn.compact
    def __call__(self, hidden, tokens, weight=None):
        head = self.param("kernel", nn.initializers.normal(INIT_STD),
                          (hidden.shape[-1], self.vocab), jnp.float32)
        if weight is None:
            return next_token_loss(hidden, head, tokens)
        return block_diffusion_loss(hidden, head, tokens, weight)


def noised_half(x):
    """What the final norm and the head see of the ``[B, 2L, d]`` stream
    under block diffusion: the head never runs on the clean half."""
    return x[:, :x.shape[1] // 2]


def batch_inputs(arrays) -> dict:
    """What ``DecoderLM`` takes of a batch's arrays, by name: the one place
    the training step and the validation loss read a ``TokenBatch``."""
    return {k: arrays[k] for k in ("tokens", "noised", "weight") if k in arrays}


def dummy_inputs(cfg: DecoderLMConfig) -> dict:
    """A row of a few ids: enough to run the initializers (under block
    diffusion one lane tile of whole blocks: the kernels pad no stream)."""
    if not cfg.block_diffusion:
        return {"tokens": jnp.zeros((1, 8), jnp.int32)}
    tokens = jnp.zeros((1, math.lcm(128, cfg.block_length)), jnp.int32)
    return {"tokens": tokens, "noised": tokens,
            "weight": jnp.ones(tokens.shape, jnp.float32)}


class DecoderLM(nn.Module):
    """tokens ``[B, T]`` int32 (under ``block_diffusion`` also ``noised``
    ``[B, T]`` int32 and ``weight`` ``[B, T]`` float32, the loader's) ->
    (loss, aux). ``aux``: ``expert_counts``
    ``[layers, experts_held]``, ``pairs_routed``, ``pairs_placed``,
    ``tiles_used`` and ``tiles_worst`` ``[layers]``, and the router's
    ``choices`` ``[layers, B, T, k]`` (``2T`` positions under
    ``block_diffusion``, which adds ``tokens_masked`` and ``loss_weight``:
    the batch's masked positions and the sum of their weights)."""

    cfg: DecoderLMConfig
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, tokens, noised=None, weight=None):
        c = self.cfg
        T = tokens.shape[1]
        if c.block_diffusion and (noised is None or weight is None):
            raise ValueError("block_diffusion needs the loader's noised and weight")
        ids = jnp.concatenate([noised, tokens], axis=1) if c.block_diffusion \
            else tokens
        x = nn.Embed(c.n_vocab_held, c.hidden_size, dtype=self.dtype,
                     param_dtype=jnp.float32, name="embed",
                     embedding_init=nn.initializers.normal(1.0))(ids)
        tables = {kind: rope_tables(getattr(c.rope_parameters, kind),
                                    c.head_dim, T)
                  for kind in set(c.layer_types[:c.num_hidden_layers])}
        if c.block_diffusion:  # a token's two copies carry one position
            tables = {kind: tuple(jnp.tile(t, (2, 1)) for t in pair)
                      for kind, pair in tables.items()}
        aux = []
        for i, kind in enumerate(c.layer_types[:c.num_hidden_layers]):
            window = c.sliding_window if kind == "sliding_attention" else 0
            x, a = DecoderLayer(c, window, self.dtype, name=f"layers_{i}")(
                x, *tables[kind])
            aux.append(a)
        counts, routed, placed, used, worst, choices = (
            jnp.stack(v) for v in zip(*aux))
        out = {"expert_counts": counts, "pairs_routed": routed,
               "pairs_placed": placed, "tiles_used": used,
               "tiles_worst": worst, "choices": choices}
        if c.block_diffusion:
            x = noised_half(x)
            out["tokens_masked"] = jnp.sum(weight > 0)
            out["loss_weight"] = jnp.sum(weight.astype(jnp.float32))
        hidden = RMSNorm(c.rms_norm_eps, name="final_norm")(x)
        loss = LMHead(c.n_vocab_held, name="lm_head")(
            hidden, tokens, weight if c.block_diffusion else None)
        return loss, out
