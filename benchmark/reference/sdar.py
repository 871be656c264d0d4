"""Plain reference of the ``decoder_lm`` block trained by block diffusion
(SDAR-30B-A3B-Chat, arXiv:2510.06303, which adopts the training of Arriola et
al., arXiv:2503.09573): forward, loss, gradients and the optimizer's first
steps in straightforward float32 ``jax.numpy`` at ``highest`` matmul
precision. No kernels, no sort, no dispatch, and the whole ``[2L, 2L]`` mask
from ``arange`` with nothing skipped. Imports nothing of the program; the tree
of parameters has the program's names, since the program restores it.

``L`` tokens a row, block length ``c``, ``n = 2L`` positions: position
``i < L`` is noised token ``i``, ``i >= L`` clean token ``i - L``;
``pos(i) = i mod L``, ``blk(i) = pos(i) // c``.

    ids = [noised ; tokens];  x = E[ids]                 (the held rows of E)
    u = RMSNorm(x);  q = u Wq (32 heads x 128), k = u Wk, v = u Wv (4 x 128)
    per head q = RMSNorm_128(q; g_q), k = RMSNorm_128(k; g_k)     eps 1e-6
    rotary by pos(i) (rotate-half, theta 1e6): a token's two copies carry
          one position
    s_ij = q_i . k_j / sqrt(128);  p = softmax_j over the pairs seen;
    h = x + (p v) Wo.  (i, j) is seen iff
          [i, j in one stream and blk(i) = blk(j)]  or
          [i noised, j clean, blk(i) > blk(j)]      or
          [i clean, j clean, blk(i) >= blk(j)]
    y = h + sum over e in top8(softmax_128(RMSNorm(h) Wr)) and held here of
          w_e down_e(silu(gate_e u') * up_e u'),  weights over their sum
    z = RMSNorm(x_out[:, :L]);  logits over the held vocabulary rows, no shift
    loss = 1 / (B L) sum_{b,i} weight_{b,i} CE(logits_{b,i}, tokens_{b,i})

``noised`` and ``weight`` are the loader's (each block draws ``t`` uniform on
[0.001, 1], masks each of its positions with probability ``t``; ``mask_id``
where masked, weight ``1 / t`` there and 0 elsewhere): the reference follows
the rows the program trained on and draws nothing.

What is ``mellum2.py``'s unchanged is imported from there: the products'
rounding hook ``mm``, ``rms_norm``, ``rotary`` (rotate-half, default type),
the seeded generator ``draw_tree``, Adam's program, the learning rate. The
expert share's few lines are written again: there they stand inside a layer
function that also holds that model's attention, in a file that is not this
PR's to edit.

**Departures from the equations, each without effect on a number** (as
``mellum2.py``'s): every layer, every block of 256 queries inside attention,
each held expert's pass and every 2,048 positions' logits under
``jax.checkpoint``; a step's rows followed in blocks whose gradients are
summed; gradient and parameters handed back as numpy.

**The seeded weights** (``init_params``; ``assumed`` in the configuration).
``mellum2.py``'s law: kernels normal(0.02), the embedding normal(1),
``o_proj`` and the experts' ``down`` normal(0.02 / sqrt(2 x 48)), norms 1
(the q/k norms' scales too). The routers are one draw, as there, and a
layer's is that draw with its outputs reordered, so that the 16 experts held
here stand for another sixteen of the draw's outputs in each layer. **Which
sixteen** (there, four layers of four shares close: rotated by 16 a layer,
every choice of a token falls to this chip in exactly one layer. Five layers
of eight shares do not: three eighths of the draw are held nowhere, and a
token's share of its eight choices that falls to the rest is a lottery of the
draw, 0 to 8, expected 5; a quarter of all positions are the mask token and
another sixth the five commonest ids, so the pairs a step holds would swing
by some 7% from seed to seed, and the step's time with them): the draw's 128
outputs are dealt into eight groups of 16 of even load, as an
expert-parallel deployment places its experts over its eight chips
(``placement``: an output's load under the traffic's token mix,
``token_mix`` of the configuration, a Zipf law over the ids and the share of
positions masked, routed by the embedding alone), and layer ``l`` holds group
``l``. It is computed from the seed's own draw. One more care: the stream
drifts from the embedding layer by layer, and where a commonest token's
eighth and ninth logits lie close its last choice flips in some layer of some
seeds; the mask token's one choice is 8,190 pairs of a layer's 32,768 (read on
the chip with the groups dealt by load alone: of nine seeds one lost them in
layer 3 and one gained as many in layer 1). So the outputs that lie within
``UNSURE`` standard deviations of that midpoint for a token of
``HEAVY_SHARE`` of the positions or more go to the three groups that no
layer holds here: whether such a token takes them or not, this chip's work is
the same. Every held group then draws one pair a position to within a
percent, whatever the seed, and a step does the expected work, which
``cycle_flops`` counts and ``pairs_held_gap`` holds it to.

``fault`` plants what the comparison must catch: ``causal_mask`` (the
triangle over ``2L``: a noised token sees its clean self), ``own_clean_block``
(``blk(i) >= blk(j)`` for noised rows), ``no_qk_norm``, ``unweighted``
(weight 1 where masked), ``clean_head`` (the loss also on the clean half).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.mellum2 import (  # noqa: F401
    HIGHEST, LOGIT_BLOCK, NEG, QUERY_BLOCK, _ident, _shapes, adam_program,
    draw_tree, flatten, init_batch_stats, learning_rate, mm, rms_norm, rotary)

FAULTS = ("causal_mask", "own_clean_block", "no_qk_norm", "unweighted",
          "clean_head")


def hyper(model: dict) -> dict:
    m = model["decoder_lm"]
    return {
        "vocab": m.get("vocab_held") or m["vocab_size"],
        "d": m["hidden_size"], "layers": m["num_hidden_layers"],
        "heads": m["num_attention_heads"], "kv_heads": m["num_key_value_heads"],
        "head_dim": m["head_dim"], "eps": m["rms_norm_eps"],
        "experts": m["num_experts"], "top_k": m["num_experts_per_tok"],
        "width": m["moe_intermediate_size"], "norm_topk": m["norm_topk_prob"],
        "lo": m.get("expert_offset", 0),
        "held": m.get("experts_held") or m["num_experts"],
        "rope": m["rope_parameters"]["full_attention"],
        "depth": len(m["layer_types"]),      # the published depth
        "block": m["block_length"], "mask_id": m["mask_id"],
        "qk_norm": m["qk_norm"], "mix": model["token_mix"],
    }


def token_mix(hp: dict):
    """(ids, shares of all ``2L`` positions) of the traffic's commonest
    tokens: the mask token, and the ``heavy`` first ids of the Zipf law over
    ``1 .. mask_id - 1`` on the positions that are not masked."""
    mix = hp["mix"]
    ranks = np.arange(1, hp["mask_id"], dtype=np.float64)
    p = ranks ** -float(mix["zipf_exponent"])
    p /= p.sum()
    heavy = min(int(mix["heavy"]), len(ranks))
    ids = np.concatenate([[hp["mask_id"]], np.arange(1, heavy + 1)])
    shares = np.concatenate([[mix["masked_share"]],
                             (1.0 - mix["masked_share"]) * p[:heavy]])
    return ids, shares


# a commonest token's share of all positions from which its choices are
# weighed one by one, and how near its eighth-and-ninth logits' midpoint (in
# standard deviations of its logits) an output is not surely in or out
HEAVY_SHARE, UNSURE = 0.015, 0.06


def placement(hp: dict, embedding, router) -> list:
    """The router's outputs dealt into ``experts / held`` groups of ``held``
    of even load (module docstring): an output's load is the share of all
    positions that choose it, under the token mix routed by the embedding
    alone. First the outputs that a commonest token may or may not choose
    (``unsure``) go to the groups no layer holds, the lightest group each;
    then the others to and fro over the groups with room, the heaviest
    first; then, over and over, the swap of two of those others between two
    groups that lowers the loads' sum of squares most, until none does.
    Returns the groups' output indices, each sorted."""
    ids, shares = token_mix(hp)
    rows = np.asarray(embedding[ids], np.float64)
    rows /= np.sqrt(np.mean(np.square(rows), -1, keepdims=True) + hp["eps"])
    logits = rows @ np.asarray(router, np.float64)
    ranked = np.argsort(-logits, axis=-1)
    k, n = hp["top_k"], hp["experts"] // hp["held"]
    load = np.zeros(hp["experts"])
    np.add.at(load, ranked[:, :k], shares[:, None])
    groups, sums = [[] for _ in range(n)], np.zeros(n)
    spare = list(range(min(hp["layers"], n), n))        # held by no layer
    unsure = []
    for t in np.argsort(-shares, kind="stable"):
        if shares[t] < HEAVY_SHARE or not spare:
            break
        edge = logits[t, ranked[t, k - 1: k + 1]].mean()
        near = np.abs(logits[t] - edge) < UNSURE * logits[t].std()
        unsure += [int(o) for o in ranked[t] if near[o] and o not in unsure]
    for o in unsure[: len(spare) * hp["held"]]:
        g = min((g for g in spare if len(groups[g]) < hp["held"]), key=lambda g: sums[g])
        groups[g].append(o)
        sums[g] += load[o]
    pinned = {o for g in groups for o in g}
    at, step = 0, 1
    for o in np.argsort(-load, kind="stable"):
        if int(o) in pinned:
            continue
        while len(groups[at]) == hp["held"]:            # to and fro, past the full
            at, step = (at + step, step) if 0 <= at + step < n else (at, -step)
        groups[at].append(int(o))
        at, step = (at + step, step) if 0 <= at + step < n else (at, -step)
    free = [[o for o in g if o not in pinned] for g in groups]
    for _ in range(64 * n):
        sums = [load[g].sum() for g in groups]
        gain, swap = -1e-12, None
        for g in range(n):
            for h in range(g + 1, n):
                d = load[free[g]][:, None] - load[free[h]][None, :]
                gains = 2 * d * (d - (sums[g] - sums[h]))
                if gains.size and gains.min() < gain:
                    i, j = np.unravel_index(np.argmin(gains), gains.shape)
                    gain, swap = gains[i, j], (g, free[g][i], h, free[h][j])
        if swap is None:
            break
        g, a, h, b = swap
        for group in (groups, free):
            group[g][group[g].index(a)], group[h][group[h].index(b)] = b, a
    return [sorted(g) for g in groups]


def init_params(hp: dict, seed: int) -> dict:
    """One numpy draw from ``seed`` (module docstring: the seeded weights)."""
    shapes = _shapes(hp)
    if hp["qk_norm"]:
        for i in range(hp["layers"]):
            for name in ("q_norm", "k_norm"):
                shapes[f"layers_{i}/self_attn/{name}/scale"] = ((hp["head_dim"],), "ones")
    tree = draw_tree(shapes, seed)
    first = tree["layers_0"]["moe"]["router"]["kernel"]
    groups = placement(hp, tree["embed"]["embedding"], first)
    for i in range(hp["layers"]):
        here = groups[i % len(groups)]
        rest = sorted(set(range(hp["experts"])) - set(here))
        tree[f"layers_{i}"]["moe"]["router"]["kernel"] = first[:, here + rest]
    return tree


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------

def seen(i, j, L: int, c: int, fault=None):
    """Whether position ``i`` sees position ``j`` (broadcasting)."""
    if fault == "causal_mask":
        return j <= i
    qn, kn = i < L, j < L
    qb, kb = (i % L) // c, (j % L) // c
    noised_to_clean = (qb >= kb) if fault == "own_clean_block" else (qb > kb)
    return ((qn == kn) & (qb == kb)) | (qn & ~kn & noised_to_clean) \
        | (~qn & ~kn & (qb >= kb))


def attention(q, query, key, value, c: int, fault=None):
    """query [R, 2L, H, D]; key, value [R, 2L, Hkv, D]. A block of queries
    at a time against all keys, under the whole mask's rows."""
    R, T, H, D = query.shape
    kv = key.shape[2]
    grouped = query.reshape(R, T, kv, H // kv, D)
    qb = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    j = jnp.arange(T)[None, :]

    @jax.checkpoint
    def block(start):
        rows = jax.lax.dynamic_slice_in_dim(grouped, start, qb, axis=1)
        s = jnp.einsum("rqkgd,rskd->rkgqs", q(rows), q(key),
                       precision=HIGHEST) / math.sqrt(D)
        ok = seen(start + jnp.arange(qb)[:, None], j, T // 2, c, fault)
        p = jax.nn.softmax(jnp.where(ok, s, NEG), axis=-1)
        return jnp.einsum("rkgqs,rskd->rqkgd", q(p), q(value), precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(0, T, qb))      # [T/qb, R, qb, kv, G, D]
    return jnp.moveaxis(out, 0, 1).reshape(R, T, H * D)


def two_streams(x, rope: dict):
    """Rotary by ``pos(i) = i mod L``: each stream by its own positions."""
    L = x.shape[1] // 2
    return jnp.concatenate([rotary(x[:, :L], rope), rotary(x[:, L:], rope)], axis=1)


def layer(hp, p, x, q, fault):
    a, m = p["self_attn"], p["moe"]
    R, T, _ = x.shape
    H, kv, D = hp["heads"], hp["kv_heads"], hp["head_dim"]
    u = rms_norm(x, a["input_norm"]["scale"], hp["eps"])
    query = mm(q, u, a["q_proj"]["kernel"]).reshape(R, T, H, D)
    key = mm(q, u, a["k_proj"]["kernel"]).reshape(R, T, kv, D)
    value = mm(q, u, a["v_proj"]["kernel"]).reshape(R, T, kv, D)
    if hp["qk_norm"] and fault != "no_qk_norm":
        query = rms_norm(query, a["q_norm"]["scale"], hp["eps"])
        key = rms_norm(key, a["k_norm"]["scale"], hp["eps"])
    query, key = two_streams(query, hp["rope"]), two_streams(key, hp["rope"])
    h = x + mm(q, attention(q, query, key, value, hp["block"], fault),
               a["o_proj"]["kernel"])

    # the expert share, as ``mellum2.layer`` has it
    u = rms_norm(h, m["norm_scale"], hp["eps"])
    probs = jax.nn.softmax(mm(q, u, m["router"]["kernel"]), axis=-1)
    weights, idx = jax.lax.top_k(probs, hp["top_k"])
    if hp["norm_topk"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)

    @jax.checkpoint
    def one_expert(out, packed):
        e, gate, up, down = packed
        w = jnp.sum(jnp.where(idx == hp["lo"] + e, weights, 0.0), -1)   # [R, T]
        y = mm(q, jax.nn.silu(mm(q, u, gate)) * mm(q, u, up), down)
        return out + w[..., None] * y, None

    e_p = m["experts"]
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        jnp.arange(hp["held"]), e_p["gate"], e_p["up"], e_p["down"]))
    return h + out, idx


def weighted_cross_entropy_sum(q, h, head, targets, weight):
    """The sum over positions of ``weight`` times logsumexp(h W) -
    (h W)[target], the logits standing ``LOGIT_BLOCK`` positions at a time
    (zero rows of weight 0 pad the last block)."""
    n = h.shape[0]
    block = min(LOGIT_BLOCK, n)
    pad = -n % block
    h = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, block, h.shape[1])
    targets = jnp.pad(targets, (0, pad)).reshape(-1, block)
    weight = jnp.pad(weight, (0, pad)).reshape(-1, block)

    @jax.checkpoint
    def part(args):
        x, t, w = args
        logits = mm(q, x, head)
        picked = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        return jnp.sum(w * (jax.nn.logsumexp(logits, axis=-1) - picked))

    return jnp.sum(jax.lax.map(part, (h, targets, weight)))


def forward(hp, params, rows, q=_ident, fault=None):
    """(the sum over the rows' noised positions of weight times
    cross-entropy, each layer's choices [R, 2L, k]) for ``rows`` = (tokens,
    noised, weight), each [R, L]."""
    tokens, noised, weight = rows
    L = tokens.shape[1]
    if fault == "unweighted":
        weight = (weight > 0).astype(jnp.float32)
    x = params["embed"]["embedding"][jnp.concatenate([noised, tokens], axis=1)]
    choices = []
    for i in range(hp["layers"]):
        x, idx = jax.checkpoint(lambda p, x: layer(hp, p, x, q, fault))(
            params[f"layers_{i}"], x)
        choices.append(idx)
    if fault == "clean_head":   # the clean half too, each token under its weight
        tokens, weight = (jnp.concatenate([a, a], axis=1) for a in (tokens, weight))
    else:
        x = x[:, :L]
    h = rms_norm(x, params["final_norm"]["scale"], hp["eps"])
    return weighted_cross_entropy_sum(
        q, h.reshape(-1, h.shape[-1]), params["lm_head"]["kernel"],
        tokens.reshape(-1), weight.reshape(-1)), choices


def block_gradient(hp, positions, q=_ident, fault=None):
    """The jitted program of one block of rows, as ``mellum2.block_gradient``:
    (accumulated gradient, parameters, (tokens, noised, weight)) -> (the
    block's part of the loss over ``positions`` = B L, the gradient with the
    block's added, the block's choices)."""
    def block(acc, p, rows):
        def mean_part(p):
            total, choices = forward(hp, p, rows, q, fault)
            return total / positions, choices

        (part, choices), g = jax.value_and_grad(mean_part, has_aux=True)(p)
        return part, jax.tree_util.tree_map(jnp.add, acc, g), choices

    return jax.jit(block, donate_argnums=(0,))


def loss_and_grads(hp, params, batch, block_rows, q=_ident, fault=None, step=None):
    """(loss, its gradient, each layer's choices [B, 2L, k] as numpy) for one
    batch {tokens, noised, weight}, the rows followed ``block_rows`` at a
    time."""
    tokens = np.asarray(batch["tokens"])
    step = step or block_gradient(hp, tokens.size, q, fault)
    acc = jax.tree_util.tree_map(jnp.zeros_like, params)
    total, parts = 0.0, []
    for r in range(0, tokens.shape[0], block_rows):
        rows = tuple(jnp.asarray(np.asarray(batch[k])[r:r + block_rows])
                     for k in ("tokens", "noised", "weight"))
        part, acc, choices = step(acc, params, rows)
        total += float(part)
        parts.append(jax.device_get(choices))
    return total, acc, [np.concatenate([p[i] for p in parts])
                        for i in range(hp["layers"])]


def train_steps(hp, opt, params, stats, batches, seed, block_rows=1, quant=None,
                clock=None, fault=None, choices=None):
    """Follow the first ``len(batches)`` optimizer steps, as
    ``mellum2.train_steps``: per-step losses, the first clipped gradient and
    the parameters after each step (numpy trees). ``seed`` draws nothing: the
    noise is in the batches. A list given as ``choices`` gets the first step's
    routing appended (each layer's [B, 2L, k])."""
    params = jax.tree_util.tree_map(lambda x: jnp.array(x, jnp.float32), params)
    mu = nu = None
    losses, first_grad, after = [], None, []
    gradient = block_gradient(hp, np.asarray(batches[0]["tokens"]).size,
                              quant or _ident, fault)
    adam, (b1, b2) = adam_program(opt), opt["betas"]
    if clock:
        clock("start")
    for i, batch in enumerate(batches):
        loss, grads, chosen = loss_and_grads(hp, params, batch, block_rows,
                                             step=gradient)
        if i == 0 and choices is not None:
            choices.extend(chosen)
        if mu is None:
            mu = jax.tree_util.tree_map(jnp.zeros_like, params)
            nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        numbers = (learning_rate(opt, i), 1 - b1 ** (i + 1), 1 - b2 ** (i + 1))
        params, mu, nu, clipped = adam(*(np.float32(x) for x in numbers),
                                       params, grads, mu, nu)
        if first_grad is None:
            first_grad = jax.device_get(clipped)
        del grads, clipped
        losses.append(loss)
        after.append(jax.device_get(params))
        if clock:
            clock(f"step{i + 1}")
    return losses, first_grad, after
