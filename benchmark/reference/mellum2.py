"""Plain reference of the ``decoder_lm`` block (Mellum2-12B-A2.5B): forward,
loss, gradients and the optimizer's first steps in straightforward float32
``jax.numpy`` at ``highest`` matmul precision. No kernels, no sort, no
dispatch: every held expert runs over every token and a mask picks its
pairs. Imports nothing of the program (``speakingstyle_tpu``); the tree of
parameters has the program's names, since the program restores it.

    x_0 = E[ids]                                  (the held rows of E)
    h   = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h))      eps 1e-6
    Attn: 32 query / 4 key-value heads of 128, no bias, rotary (rotate-half,
          theta 500000) on q and k, scores q.k/sqrt(128), causal, float32
          softmax. ``sliding_attention`` layers: default rotary, i sees j
          only where i - 1024 < j <= i. ``full_attention`` layers: YaRN
          (factor 16, original 8192, beta_fast 32, beta_slow 1; inverse
          frequencies blended between theta^(-2k/128) and that over 16 along
          the linear ramp between the correction dimensions, floor and ceil
          of them as Hugging Face's ``truncate`` default does), cos and sin
          times attention_factor.
    MoE:  p = softmax(W_r u) over all experts, the k largest, their weights
          over their sum; out = sum over the chosen experts e that are held
          here of w_e W_down,e(silu(W_gate,e u) * W_up,e u). No capacity.
    loss: final RMSNorm, logits over the held vocabulary rows (untied),
          mean cross-entropy of id t+1 given ids <= t over each row's T - 1
          targets.

**Departures from the equations above, each without effect on a number:**
(1) each layer, each block of 256 queries inside attention, each held
expert's pass and each block of 2,048 positions' logits is under
``jax.checkpoint``: float32 ``[32, 8192, 8192]`` scores are 8.6 GB a layer
and a row, and the same float32 operations run again give the same values;
(2) a step's rows are followed in blocks (``block_rows``) whose gradients
are summed, so that one block's activations stand at a time; (3) the first
gradient and the parameters after each step are handed back as numpy
arrays (three more copies of 2.4 GB do not fit beside the optimizer).
Left out, as the issue says and the configuration lists under ``assumed``:
an auxiliary router loss, a multi-token head, q/k norms.

**The seeded weights** (``init_params``; the published config gives
``initializer_range`` 0.02 and nothing else, so the rest is ``assumed``).
Every kernel is normal(0.02) but three kinds. The embedding is normal(1)
and ``o_proj`` and the experts' ``down`` are normal(0.02 / sqrt(2 x the
published depth)), the scaling of the projections that write into the
residual stream that GPT-2 and Megatron-LM start from: the stream then
carries the token, as a trained model's does, and the router's input is a
function of the token. (At 0.02 everywhere the first attention layer's
output, nearly the same vector at every position, is seven times the
embedding, and every token of a layer chooses the same eight experts:
measured on the chip, PERF.md section 6.) And the layers' routers are one
draw: layer ``l``'s is layer 0's with its outputs rotated by ``l`` times the
experts held. A token's choices depend on little but the token, a Zipf
law's first id is a tenth of all tokens, and the share of its eight choices
that falls to the sixteen experts held here is a lottery of the draw (0 to
8, expected 2); rotated, the held sixteen meet another quarter of the
router in each of a period's four layers, so over the period every token
brings this chip its even share of pairs, whatever the seed draws, and a
seed's step does the expected work (simulated at the cell's widths:
PERF.md section 4).

``fault`` plants what the comparison must catch: ``capacity`` (pairs past
``tokens * k / experts`` an expert, in token order, are dropped),
``no_window`` (the sliding layers see the whole causal triangle),
``no_yarn`` (the full layers take the default rotary tables).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.fs2 import flatten, learning_rate  # noqa: F401

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
LOGIT_BLOCK = 2048
NEG = -1e30


def hyper(model: dict) -> dict:
    m = model["decoder_lm"]
    n = m["num_hidden_layers"]
    return {
        "vocab": m.get("vocab_held") or m["vocab_size"],
        "d": m["hidden_size"], "layers": n,
        "kinds": list(m["layer_types"][:n]),
        "heads": m["num_attention_heads"], "kv_heads": m["num_key_value_heads"],
        "head_dim": m["head_dim"], "window": m["sliding_window"],
        "eps": m["rms_norm_eps"], "experts": m["num_experts"],
        "top_k": m["num_experts_per_tok"], "width": m["moe_intermediate_size"],
        "norm_topk": m["norm_topk_prob"], "lo": m.get("expert_offset", 0),
        "held": m.get("experts_held") or m["num_experts"],
        "rope": m["rope_parameters"],
        "depth": len(m["layer_types"]),      # the published depth
    }


def _shapes(hp: dict) -> dict:
    d, hd = hp["d"], hp["head_dim"]
    normal = ("normal", 0.02)
    writes = ("normal", 0.02 / math.sqrt(2 * hp["depth"]))
    out = {"embed/embedding": ((hp["vocab"], d), ("normal", 1.0)),
           "final_norm/scale": ((d,), "ones"),
           "lm_head/kernel": ((d, hp["vocab"]), normal)}
    for i in range(hp["layers"]):
        a, m = f"layers_{i}/self_attn", f"layers_{i}/moe"
        out[f"{a}/input_norm/scale"] = ((d,), "ones")
        out[f"{a}/q_proj/kernel"] = ((d, hp["heads"] * hd), normal)
        out[f"{a}/k_proj/kernel"] = ((d, hp["kv_heads"] * hd), normal)
        out[f"{a}/v_proj/kernel"] = ((d, hp["kv_heads"] * hd), normal)
        out[f"{a}/o_proj/kernel"] = ((hp["heads"] * hd, d), writes)
        out[f"{m}/norm_scale"] = ((d,), "ones")
        out[f"{m}/router/kernel"] = ((d, hp["experts"]), normal)
        out[f"{m}/experts/gate"] = ((hp["held"], d, hp["width"]), normal)
        out[f"{m}/experts/up"] = ((hp["held"], d, hp["width"]), normal)
        out[f"{m}/experts/down"] = ((hp["held"], hp["width"], d), writes)
    return out


def draw_tree(shapes: dict, seed: int) -> dict:
    """Every leaf from a generator of its own, seeded by ``seed`` and the
    leaf's place in the sorted names, the leaves drawn side by side on a few
    threads (numpy's generators let go of the GIL): 595M normals from one
    generator are a quarter of a minute of every run's set-up. Identical on
    every backend and in every process for a given seed."""
    from concurrent.futures import ThreadPoolExecutor

    names = sorted(shapes)

    def leaf(i):
        shape, rule = shapes[names[i]]
        if rule == "ones":
            return np.ones(shape, np.float32)
        out = np.random.default_rng([int(seed), 0x5EED, i]).standard_normal(
            shape, dtype=np.float32)
        out *= np.float32(rule[1])
        return out

    with ThreadPoolExecutor(8) as pool:
        leaves = list(pool.map(leaf, range(len(names))))
    tree = {}
    for n, value in zip(names, leaves):
        node, parts = tree, n.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value
    return tree


def init_params(hp: dict, seed: int) -> dict:
    """One numpy draw from ``seed`` (module docstring: the seeded weights)."""
    tree = draw_tree(_shapes(hp), seed)
    first = tree["layers_0"]["moe"]["router"]["kernel"]
    for i in range(1, hp["layers"]):
        tree[f"layers_{i}"]["moe"]["router"]["kernel"] = np.roll(
            first, -i * hp["held"], axis=1)
    return tree


def init_batch_stats(hp: dict) -> dict:
    return {}


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------

def _ident(x):
    return x


def mm(q, a, b):
    return jnp.matmul(q(a), q(b), precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def inv_freq(rope: dict, head_dim: int):
    """(inverse frequencies [head_dim / 2], the factor on cos and sin)."""
    theta = float(rope["rope_theta"])
    k = np.arange(0, head_dim, 2, dtype=np.float64)
    plain = theta ** (-k / head_dim)
    if rope.get("rope_type", "default") == "default":
        return plain, 1.0
    original = rope["original_max_position_embeddings"]

    def correction_dim(rotations):
        return head_dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return plain / rope["factor"] * ramp + plain * (1 - ramp), \
        float(rope["attention_factor"])


def rotary(x, rope: dict):
    """x [R, T, H, D], rotate-half."""
    T, D = x.shape[1], x.shape[3]
    freq, factor = inv_freq(rope, D)
    angles = np.arange(T, dtype=np.float64)[:, None] * freq[None, :]
    angles = np.concatenate([angles, angles], axis=1)
    cos = jnp.asarray(np.cos(angles) * factor, jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angles) * factor, jnp.float32)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + rotated * sin


def attention(q, query, key, value, window):
    """query [R, T, H, D]; key, value [R, T, Hkv, D]; causal, and banded if
    ``window``. A block of queries at a time against all keys."""
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
        i = start + jnp.arange(qb)[:, None]
        ok = j <= i
        if window:
            ok = ok & (j > i - window)
        p = jax.nn.softmax(jnp.where(ok, s, NEG), axis=-1)
        return jnp.einsum("rkgqs,rskd->rqkgd", q(p), q(value), precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(0, T, qb))      # [T/qb, R, qb, kv, G, D]
    return jnp.moveaxis(out, 0, 1).reshape(R, T, H * D)


def layer(hp, kind, p, x, q, fault):
    a, m = p["self_attn"], p["moe"]
    R, T, _ = x.shape
    H, kv, D = hp["heads"], hp["kv_heads"], hp["head_dim"]
    u = rms_norm(x, a["input_norm"]["scale"], hp["eps"])
    rope = hp["rope"][kind]
    if fault == "no_yarn":
        rope = {"rope_type": "default", "rope_theta": rope["rope_theta"]}
    query = rotary(mm(q, u, a["q_proj"]["kernel"]).reshape(R, T, H, D), rope)
    key = rotary(mm(q, u, a["k_proj"]["kernel"]).reshape(R, T, kv, D), rope)
    value = mm(q, u, a["v_proj"]["kernel"]).reshape(R, T, kv, D)
    window = hp["window"] if kind == "sliding_attention" and fault != "no_window" \
        else None
    h = x + mm(q, attention(q, query, key, value, window), a["o_proj"]["kernel"])

    u = rms_norm(h, m["norm_scale"], hp["eps"])
    probs = jax.nn.softmax(mm(q, u, m["router"]["kernel"]), axis=-1)
    weights, idx = jax.lax.top_k(probs, hp["top_k"])
    if hp["norm_topk"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    capacity = R * T * hp["top_k"] // hp["experts"]

    @jax.checkpoint
    def one_expert(out, packed):
        e, gate, up, down = packed
        chosen = idx == hp["lo"] + e                          # [R, T, k]
        w = jnp.sum(jnp.where(chosen, weights, 0.0), -1)      # [R, T]
        if fault == "capacity":
            rank = jnp.cumsum(jnp.any(chosen, -1).reshape(-1)).reshape(R, T)
            w = jnp.where(rank <= capacity, w, 0.0)
        y = mm(q, jax.nn.silu(mm(q, u, gate)) * mm(q, u, up), down)
        return out + w[..., None] * y, None

    # a loop over the held experts (a scan: one body, compiled once)
    e_p = m["experts"]
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        jnp.arange(hp["held"]), e_p["gate"], e_p["up"], e_p["down"]))
    return h + out, idx


def forward(hp, params, tokens, q=_ident, fault=None):
    """(the sum over the rows' targets of the cross-entropy, each layer's
    choices [R, T, k]) for rows ``tokens`` [R, T]."""
    x = params["embed"]["embedding"][tokens]
    choices = []
    for i, kind in enumerate(hp["kinds"]):
        x, idx = jax.checkpoint(
            lambda p, x, kind=kind: layer(hp, kind, p, x, q, fault))(
                params[f"layers_{i}"], x)
        choices.append(idx)
    h = rms_norm(x, params["final_norm"]["scale"], hp["eps"])
    return cross_entropy_sum(q, h[:, :-1].reshape(-1, h.shape[-1]),
                             params["lm_head"]["kernel"],
                             tokens[:, 1:].reshape(-1)), choices


def cross_entropy_sum(q, h, head, targets):
    """The sum over positions of logsumexp(h W) - (h W)[target], the logits
    standing ``LOGIT_BLOCK`` positions at a time (zero rows pad the last
    block and are masked out)."""
    n = h.shape[0]
    block = min(LOGIT_BLOCK, n)
    pad = -n % block
    h = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, block, h.shape[1])
    targets = jnp.pad(targets, (0, pad)).reshape(-1, block)
    real = (jnp.arange(n + pad) < n).reshape(-1, block)

    @jax.checkpoint
    def part(args):
        x, t, m = args
        logits = mm(q, x, head)
        picked = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(m, jax.nn.logsumexp(logits, axis=-1) - picked, 0.0))

    return jnp.sum(jax.lax.map(part, (h, targets, real)))


def block_gradient(hp, targets, q=_ident, fault=None):
    """The jitted program of one block of rows: (accumulated gradient,
    parameters, rows [R, T]) -> (the block's part of the mean cross-entropy
    over ``targets`` positions, the gradient with the block's added, the
    block's choices). Made once for all the blocks and steps of a run: one
    compile, or one executable read back from the cache."""
    def block(acc, p, t):
        def mean_part(p):
            total, choices = forward(hp, p, t, q, fault)
            return total / targets, choices

        (part, choices), g = jax.value_and_grad(mean_part, has_aux=True)(p)
        return part, jax.tree_util.tree_map(jnp.add, acc, g), choices

    return jax.jit(block, donate_argnums=(0,))


def loss_and_grads(hp, params, tokens, block_rows, q=_ident, fault=None,
                   step=None):
    """(mean cross-entropy over all rows' targets, its gradient, each layer's
    choices [B, T, k] as numpy), the rows followed ``block_rows`` at a time,
    each block's gradient added into the one tree that is handed on (its
    memory given to the sum)."""
    step = step or block_gradient(
        hp, tokens.shape[0] * (tokens.shape[1] - 1), q, fault)
    acc = jax.tree_util.tree_map(jnp.zeros_like, params)
    total, parts = 0.0, []
    for r in range(0, tokens.shape[0], block_rows):
        part, acc, choices = step(acc, params, jnp.asarray(tokens[r:r + block_rows]))
        total += float(part)
        parts.append(jax.device_get(choices))
    return total, acc, [np.concatenate([p[i] for p in parts])
                        for i in range(hp["layers"])]


def adam_program(opt: dict):
    """clip by global norm -> Adam -> -lr, as ``fs2.adam_step`` has it, as
    one jitted program that gives its inputs' memory to its outputs (seven
    trees of 2.4 GB do not stand side by side): (learning rate, the two bias
    corrections ``1 - beta ** steps``, params, grads, mu, nu) -> (params, mu,
    nu, clipped). The three numbers of a step are arguments, so every step
    runs the one program."""
    b1, b2 = opt["betas"]

    def step(lr, c1, c2, params, grads, mu, nu):
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                            for g in jax.tree_util.tree_leaves(grads)))
        scale = jnp.where(norm < opt["clip"], 1.0, opt["clip"] / norm)
        clipped = jax.tree_util.tree_map(lambda g: g * scale, grads)
        mu = jax.tree_util.tree_map(
            lambda m, g: b1 * m + (1 - b1) * g, mu, clipped)
        nu = jax.tree_util.tree_map(
            lambda v, g: b2 * v + (1 - b2) * jnp.square(g), nu, clipped)
        new = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + opt["eps"]),
            params, mu, nu)
        return new, mu, nu, clipped

    return jax.jit(step, donate_argnums=(3, 4, 5, 6))


def train_steps(hp, opt, params, stats, batches, seed, block_rows=1, quant=None,
                clock=None, fault=None, choices=None):
    """Follow the first ``len(batches)`` optimizer steps. Returns per-step
    losses, the first clipped gradient and the parameters after each step
    (numpy trees). ``seed`` draws nothing: the model has no dropout. A list
    given as ``choices`` gets the first step's routing appended (each
    layer's [B, T, k]): the same forward pass that the gradient is of."""
    # a copy on the device of its own: the steps give its memory away
    params = jax.tree_util.tree_map(lambda x: jnp.array(x, jnp.float32), params)
    mu = nu = None
    losses, first_grad, after = [], None, []
    shape = np.shape(batches[0]["tokens"])
    gradient = block_gradient(hp, shape[0] * (shape[1] - 1), quant or _ident, fault)
    adam, (b1, b2) = adam_program(opt), opt["betas"]
    if clock:
        clock("start")
    for i, batch in enumerate(batches):
        loss, grads, chosen = loss_and_grads(
            hp, params, np.asarray(batch["tokens"]), block_rows, step=gradient)
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
