"""The ``decoder_lm`` family at toy size on the CPU (hidden 64, one period of
four layers, window 8, rows of 32, 8 experts top 2, 2 held): the program
against the plain reference ``benchmark/reference/mellum2.py`` on seeded
weights, the expert-parallel shares against the uncut layer, the window, the
rotary tables by hand, and the two new kernels against plain ``jax.numpy``
in the Pallas interpreter."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import common  # noqa: E402
from benchmark.reference import mellum2 as ref  # noqa: E402
from speakingstyle_tpu.configs.config import (  # noqa: E402
    DecoderLMConfig, RopeConfig, _build, load_config)
from speakingstyle_tpu.models import mellum  # noqa: E402
from speakingstyle_tpu.ops import expert_dispatch  # noqa: E402
from speakingstyle_tpu.ops.blocked_attention import (  # noqa: E402
    blocked_attention, reference_attention)
from speakingstyle_tpu.ops.grouped_matmul import grouped_matmul  # noqa: E402


def toy_block() -> dict:
    cfg = common.sized(common.load_json("benchmark/configs/mellum2_12b_ep4share.json"),
                       True)
    return cfg["model"]["decoder_lm"]


@pytest.fixture(scope="module")
def toy():
    block = toy_block()
    hp = ref.hyper({"decoder_lm": block})
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 256))
    return block, hp, ref.init_params(hp, 7), tokens


def program_grads(block, params, tokens, dtype):
    model = mellum.DecoderLM(_build(DecoderLMConfig, block), dtype=dtype)
    (loss, aux), grads = jax.value_and_grad(
        lambda p: model.apply({"params": p}, tokens), has_aux=True)(params)
    return float(loss), ref.flatten(grads), aux


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def test_program_is_the_reference_in_float32(toy):
    block, hp, params, tokens = toy
    loss, grads, aux = program_grads(block, params, tokens, jnp.float32)
    ref_loss, ref_grads, _ = ref.loss_and_grads(
        hp, jax.tree_util.tree_map(jnp.asarray, params), tokens, 2)
    assert abs(loss - ref_loss) < 1e-5 * ref_loss
    ref_grads = ref.flatten(ref_grads)
    assert set(grads) == set(ref_grads)
    # every leaf, tightly: same equations, float32 on both sides
    assert max(rel(grads[k], ref_grads[k]) for k in ref_grads) < 2e-5
    assert int((aux["pairs_routed"] - aux["pairs_placed"]).sum()) == 0


def test_program_with_qk_prepare_emulated_is_the_program_by_parts(toy, monkeypatch):
    """At a head of 128 lanes the interpreted ``qk_prepare`` (no head norm in
    this model: the plain variant) stands where rotation and transpose stand
    by parts: same loss and gradients to the tolerance this file holds the
    reference to, and 4 layers x (q, k) x (forward, recomputed, backward)
    launches in the step."""
    from speakingstyle_tpu.ops import qk_prepare

    block = {**toy[0], "head_dim": 128}
    tokens = toy[3]
    params = ref.init_params(ref.hyper({"decoder_lm": block}), 7)
    loss, grads, _ = program_grads(block, params, tokens, jnp.float32)
    real = mellum.qk_prepare
    monkeypatch.setattr(mellum, "qk_prepare",
                        lambda *a, **kw: real(*a, interpret=True, **kw))
    fused_loss, fused, _ = program_grads(block, params, tokens, jnp.float32)
    assert abs(fused_loss - loss) < 1e-5 * loss
    assert max(rel(fused[k], grads[k]) for k in grads) < 2e-5
    model = mellum.DecoderLM(_build(DecoderLMConfig, block), dtype=jnp.float32)
    step = jax.make_jaxpr(jax.grad(
        lambda p: model.apply({"params": p}, tokens)[0]))(params)
    assert qk_prepare.launches(step) == {"norm": 0, "plain": 24}


def test_program_in_bfloat16_stays_within_the_cells_tolerances(toy):
    """bfloat16 compute against the float32 reference: the loss within a
    percent; every gradient leaf's norm within a tenth of the larger of its
    own and the median leaf's (8 bits of mantissa through four layers and a
    router whose near-ties flip: ``grad_norm_gap``'s measure)."""
    from benchmark.harness import train_compare

    block, hp, params, tokens = toy
    loss, grads, _ = program_grads(block, params, tokens, jnp.bfloat16)
    ref_loss, ref_grads, _ = ref.loss_and_grads(
        hp, jax.tree_util.tree_map(jnp.asarray, params), tokens, 4)
    assert abs(loss - ref_loss) < 1e-2 * ref_loss
    gaps = train_compare.leaf_gaps(grads, ref.flatten(ref_grads))
    assert train_compare.worst_leaf(gaps)[0] < 0.1


def test_the_four_shares_add_up_to_the_uncut_layer(toy):
    """Each share routes over all eight experts and computes its own two; the
    four partial results sum to what the reference gives with all eight."""
    block, hp, params, tokens = toy
    moe = params["layers_0"]["moe"]
    full = dict(hp, held=8, lo=0)
    rng = np.random.default_rng(3)
    wide = {k: rng.standard_normal((8,) + moe["experts"][k].shape[1:]).astype(
        np.float32) * 0.05 for k in ("gate", "up", "down")}
    h = jnp.asarray(rng.standard_normal((2, 32, hp["d"])), jnp.float32)
    layer_p = {"self_attn": params["layers_0"]["self_attn"],
               "moe": {**moe, "experts": wide}}
    # the reference's whole layer less its attention half is the MoE's part
    y, _ = ref.layer(full, "sliding_attention", layer_p, h, lambda x: x, None)
    none_held = {**layer_p, "moe": {**moe, "experts": {k: v[:0] for k, v in wide.items()}}}
    y0, _ = ref.layer(dict(full, held=0), "sliding_attention", none_held, h,
                      lambda x: x, None)
    uncut = y - y0
    # the MoE's input is the attention half's output
    total = jnp.zeros_like(uncut)
    for lo in (0, 2, 4, 6):
        cfg = _build(DecoderLMConfig, {**block, "expert_offset": lo})
        for r in range(2):
            out = mellum.moe_row(
                y0[r], moe["norm_scale"], moe["router"]["kernel"],
                wide["gate"][lo:lo + 2], wide["up"][lo:lo + 2],
                wide["down"][lo:lo + 2], cfg=cfg)[0]
            total = total.at[r].add(out)
    assert rel(total, uncut) < 1e-5


def test_all_tokens_to_one_expert_lose_none(toy):
    block, hp, params, _ = toy
    cfg = _build(DecoderLMConfig, block)
    moe = params["layers_0"]["moe"]
    h = jnp.abs(jnp.asarray(np.random.default_rng(0).standard_normal(
        (32, hp["d"])), jnp.float32)) + 0.1
    # every logit of expert 0 is the largest, by far; the second choice is a
    # tie among the rest, which top_k gives to expert 1: both are held
    router = jnp.zeros_like(moe["router"]["kernel"]).at[:, 0].set(10.0)
    out, idx, counts, routed, placed = mellum.moe_row(
        h, moe["norm_scale"], router, *(moe["experts"][k] for k in
                                        ("gate", "up", "down")), cfg=cfg)
    assert (np.asarray(idx)[:, 0] == 0).all()
    assert int(counts[0]) == 32 and int(routed) == int(placed) == 64
    u = ref.rms_norm(h, moe["norm_scale"], hp["eps"])
    w = np.asarray(jax.lax.top_k(jax.nn.softmax(u @ router), 2)[0])
    w = w / w.sum(-1, keepdims=True)
    want = sum(w[:, e:e + 1] * ((jax.nn.silu(u @ moe["experts"]["gate"][e])
                                 * (u @ moe["experts"]["up"][e]))
                                @ moe["experts"]["down"][e]) for e in (0, 1))
    assert rel(out, want) < 1e-5


@pytest.mark.parametrize("window,moves", [(8, False), (0, True)],
                         ids=["sliding", "full"])
def test_a_token_outside_the_window_moves_only_a_full_layer(toy, window, moves):
    block, hp, params, _ = toy
    cfg = _build(DecoderLMConfig, block)
    attn = mellum.SelfAttention(cfg, window, jnp.float32)
    cos, sin = mellum.rope_tables(cfg.rope_parameters.sliding_attention,
                                  cfg.head_dim, 32)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 32, hp["d"])),
                    jnp.float32)
    p = {"params": params["layers_0"]["self_attn"]}
    a = attn.apply(p, x, cos, sin)
    b = attn.apply(p, x.at[0, 2].add(1.0), cos, sin)
    # position 20 sees 13..20 through a window of 8, and 0..20 without one
    changed = float(jnp.max(jnp.abs(a[0, 20] - b[0, 20])))
    assert (changed > 1e-4) if moves else (changed == 0.0)


def test_seeded_weights_carry_the_token_and_rotate_the_router(toy):
    """The reference's seeded weights: the embedding at unit scale, what
    writes into the residual stream scaled by the depth, the layers' routers
    one draw rotated by the experts held; the program's own initialisers use
    the same scales. Over a period the held experts then meet every quarter
    of the router once: a token whose stream did not change between layers
    brings them ``k * held * layers / experts`` pairs, whatever the draw."""
    block, hp, params, tokens = toy
    writes = 0.02 / math.sqrt(2 * len(block["layer_types"]))
    assert hp["depth"] == len(block["layer_types"])
    assert np.std(params["embed"]["embedding"]) == pytest.approx(1.0, rel=0.05)
    first = params["layers_0"]["moe"]["router"]["kernel"]
    for i in range(hp["layers"]):
        layer = params[f"layers_{i}"]
        assert np.std(layer["self_attn"]["o_proj"]["kernel"]) == pytest.approx(writes, rel=0.1)
        assert np.std(layer["moe"]["experts"]["down"]) == pytest.approx(writes, rel=0.1)
        assert np.std(layer["self_attn"]["q_proj"]["kernel"]) == pytest.approx(0.02, rel=0.1)
        np.testing.assert_array_equal(layer["moe"]["router"]["kernel"],
                                      np.roll(first, -i * hp["held"], axis=1))
    u = np.random.default_rng(0).standard_normal((50, hp["d"])).astype(np.float32)
    held = 0
    for i in range(hp["layers"]):
        top = np.argsort(-(u @ params[f"layers_{i}"]["moe"]["router"]["kernel"]),
                         axis=1)[:, :hp["top_k"]]
        held += ((top >= hp["lo"]) & (top < hp["lo"] + hp["held"])).sum(1)
    assert (held == hp["top_k"] * hp["held"] * hp["layers"] // hp["experts"]).all()
    cfg = _build(DecoderLMConfig, block)
    mine = mellum.DecoderLM(cfg, dtype=jnp.float32).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    assert mellum.writes_std(cfg) == pytest.approx(writes)
    assert float(jnp.std(mine["embed"]["embedding"])) == pytest.approx(1.0, rel=0.05)
    assert float(jnp.std(mine["layers_2"]["self_attn"]["o_proj"]["kernel"])) == \
        pytest.approx(writes, rel=0.1)
    assert float(jnp.std(mine["layers_2"]["moe"]["experts"]["down"])) == \
        pytest.approx(writes, rel=0.1)
    assert float(jnp.std(mine["layers_2"]["moe"]["experts"]["gate"])) == \
        pytest.approx(0.02, rel=0.1)


def test_yarn_frequencies_and_factor_by_hand():
    published = RopeConfig(rope_type="yarn", rope_theta=500000.0, factor=16.0,
                           original_max_position_embeddings=8192, beta_fast=32.0,
                           beta_slow=1.0, attention_factor=1.2772588722239782)
    freq, factor = mellum.rope_inv_freq(published, 128)
    # correction dimensions: 128 ln(8192 / (32 * 2 pi)) / (2 ln 500000) = 18.08,
    # and with one rotation 34.98: the ramp runs from pair 18 to pair 35
    assert math.floor(128 * math.log(8192 / (64 * math.pi)) / (2 * math.log(5e5))) == 18
    assert math.ceil(128 * math.log(8192 / (2 * math.pi)) / (2 * math.log(5e5))) == 35
    plain = 500000.0 ** (-np.arange(0, 128, 2) / 128)
    np.testing.assert_allclose(freq[:19], plain[:19], rtol=1e-12)        # untouched
    np.testing.assert_allclose(freq[35:], plain[35:] / 16, rtol=1e-12)   # over 16
    ramp = (26 - 18) / 17
    np.testing.assert_allclose(freq[26], plain[26] / 16 * ramp + plain[26] * (1 - ramp),
                               rtol=1e-12)
    assert factor == pytest.approx(0.1 * math.log(16) + 1, abs=1e-12)
    default, one = mellum.rope_inv_freq(RopeConfig(), 128)
    np.testing.assert_allclose(default, plain, rtol=1e-12)
    assert one == 1.0
    # the reference computes its own tables: the same numbers
    theirs, f2 = ref.inv_freq({"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                               "original_max_position_embeddings": 8192,
                               "beta_fast": 32, "beta_slow": 1,
                               "attention_factor": 1.2772588722239782}, 128)
    np.testing.assert_allclose(theirs, freq, rtol=1e-12)
    assert f2 == factor


@pytest.mark.parametrize("length,window", [(200, None), (300, 100), (384, 128),
                                           (130, 129), (640, 257)])
def test_blocked_attention_is_einsum_attention(length, window):
    """The kernels in the interpreter against the plain einsum, forward and
    backward, grouped queries, at lengths that are no multiple of the block."""
    ks = jax.random.split(jax.random.PRNGKey(length), 4)
    q = jax.random.normal(ks[0], (2, 4, length, 32))
    k = jax.random.normal(ks[1], (2, 2, length, 32))
    v = jax.random.normal(ks[2], (2, 2, length, 32))
    w = jax.random.normal(ks[3], (2, 4, length, 32))
    scale = 1 / math.sqrt(32)

    def kernel(q, k, v):
        return jnp.sum(w * blocked_attention(q, k, v, window=window, block=128,
                                             interpret=True))

    def plain(q, k, v):
        return jnp.sum(w * reference_attention(q, k, v, window, scale))

    a, ga = jax.value_and_grad(kernel, (0, 1, 2))(q, k, v)
    b, gb = jax.value_and_grad(plain, (0, 1, 2))(q, k, v)
    assert abs(float(a - b)) < 1e-3
    for x, y in zip(ga, gb):
        assert float(jnp.max(jnp.abs(x - y))) < 1e-4


def _toy_layer(block, window, monkeypatch, interpret, keep):
    """A ``DecoderLayer`` at toy size under the model's own ``nn.remat`` call
    (grouped queries 4 to 2, 40 positions in a block of 128: a length that is
    no multiple of the block) -> (its loss over params and input, the two).
    ``keep`` false leaves the policy out (plain ``nn.remat``). Every call
    makes new function objects: ``jax.checkpoint`` caches a traced function
    by its identity."""
    real = blocked_attention
    monkeypatch.setattr(mellum, "blocked_attention",
                        lambda *a, **kw: real(*a, interpret=interpret, **kw))
    if not keep:
        monkeypatch.setattr(mellum, "KEEP_CORE", None)
    cfg = _build(DecoderLMConfig, block)
    layer = mellum.DecoderLayer(cfg, window, jnp.float32)
    cos, sin = mellum.rope_tables(cfg.rope_parameters.sliding_attention,
                                  cfg.head_dim, 40)
    x = jnp.asarray(np.random.default_rng(window).standard_normal(
        (2, 40, cfg.hidden_size)), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x, cos, sin)

    def loss(p, x):
        y, _ = layer.apply(p, x, cos, sin)
        return jnp.sum(jnp.square(y))

    return loss, params, x


def _count(jaxpr, name, rematerialised=False):
    """(equations of primitive ``name`` in ``jaxpr`` and all it holds, those
    of them inside a rematerialised region)."""
    total = inside = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            total += 1
            inside += rematerialised
        below = rematerialised or eqn.primitive.name == "remat2"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n, m = _count(sub, name, below)
                    total, inside = total + n, inside + m
    return total, inside


def _live_jaxpr(fn, *args):
    from jax.interpreters import partial_eval as pe

    closed = jax.make_jaxpr(fn)(*args)
    return pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))[0]


@pytest.mark.parametrize("keep,calls,again", [(True, 3, 2), (False, 4, 3)],
                         ids=["core_kept", "plain_remat"])
@pytest.mark.parametrize("window", [0, 8], ids=["full", "window_8"])
def test_a_layers_backward_runs_the_forward_kernel_once(toy, monkeypatch, window,
                                                        keep, calls, again):
    """With the core's output and log-sum-exp kept across the remat boundary
    a layer's loss and gradient launch three kernels (forward, ``dq``,
    ``dk``/``dv``) and the rematerialised region holds the two backward ones
    alone; with the policy left out (the control: the count can tell) the
    forward stands there a second time."""
    loss, params, x = _toy_layer(toy[0], window, monkeypatch, True, keep)
    live = _live_jaxpr(jax.value_and_grad(loss, (0, 1)), params, x)
    assert _count(live, "pallas_call") == (calls, again)
    # kept, the two names stand beside the first forward and nowhere else
    assert (_count(live, "name") == (2, 0)) == keep


@pytest.mark.parametrize("interpret", [True, None], ids=["interpreted", "jnp"])
@pytest.mark.parametrize("window", [0, 8], ids=["full", "window_8"])
def test_keeping_the_core_changes_no_gradient(toy, monkeypatch, window, interpret):
    """The kept values are what the second forward would have written, bit
    for bit: loss and every gradient with the policy equal those under plain
    ``nn.remat`` exactly. Off a TPU (``interpret=None`` here: the einsum
    reference) nothing carries a name, nothing is kept, and the same holds."""
    loss, params, x = _toy_layer(toy[0], window, monkeypatch, interpret, True)
    kept = jax.value_and_grad(loss, (0, 1))(params, x)
    if interpret is None:
        live = _live_jaxpr(jax.value_and_grad(loss, (0, 1)), params, x)
        assert _count(live, "name") == _count(live, "pallas_call") == (0, 0)
    loss, params, x = _toy_layer(toy[0], window, monkeypatch, interpret, False)
    plain = jax.value_and_grad(loss, (0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(kept), jax.tree_util.tree_leaves(plain)):
        np.testing.assert_array_equal(a, b)
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree_util.tree_leaves(kept[1]))


@pytest.mark.parametrize("interpret", [None, True], ids=["jnp", "interpreted"])
@pytest.mark.parametrize("lo", [0, 2, 5])
def test_grouped_product_is_a_loop_over_the_held_experts(interpret, lo):
    n, k, experts, d, f, held, tm = 50, 2, 8, 16, 24, 3, 8
    ks = jax.random.split(jax.random.PRNGKey(lo), 5)
    x = jax.random.normal(ks[0], (n, d))
    w = jax.random.normal(ks[1], (held, d, f))
    weights, idx = jax.lax.top_k(jax.nn.softmax(jax.random.normal(ks[2], (n, experts))), k)
    c = jax.random.normal(ks[3], (n, f))

    def sorted_rows(x, weights, w):
        p = expert_dispatch.plan(idx, lo, held, tm)
        y = grouped_matmul(expert_dispatch.dispatch(x, p), w, p.tile_expert,
                           p.n_used, tm, interpret)
        return jnp.sum(c * expert_dispatch.combine(y, weights, p))

    def loop(x, weights, w):
        out = 0.0
        for e in range(held):
            share = jnp.sum(jnp.where(idx == lo + e, weights, 0.0), axis=1)
            out = out + share[:, None] * (x @ w[e])
        return jnp.sum(c * out)

    a, ga = jax.value_and_grad(sorted_rows, (0, 1, 2))(x, weights, w)
    b, gb = jax.value_and_grad(loop, (0, 1, 2))(x, weights, w)
    assert abs(float(a - b)) < 1e-4
    for u, v in zip(ga, gb):
        assert float(jnp.max(jnp.abs(u - v))) < 1e-4


def _loads():
    """(name, choices ``[n, k]``, lo, held, tm) over eight experts."""
    rng = np.random.default_rng(5)
    top = np.argsort(rng.standard_normal((70, 8)), axis=1)[:, :2]   # two of eight
    # more pairs than one step of a pass takes, in tiles that leave the last
    # step partial: it reaches back over rows already done
    tm, k = 64, 4
    n = (expert_dispatch._CHUNK_ROWS + 5 * tm) // k
    assert expert_dispatch.worst_tiles(n * k, 2, tm) * tm % expert_dispatch._CHUNK_ROWS
    return [("none held", top % 4 + 4, 0, 2, 8),
            ("a quarter held", top, 2, 2, 8),
            ("all held", top % 3 + 1, 1, 3, 8),
            ("all pairs to one expert", np.full((70, 2), 6), 5, 3, 8),
            ("a partial last step", rng.integers(0, 2, (n, k)), 0, 2, tm)]


@pytest.mark.parametrize("load", _loads(), ids=lambda load: load[0])
def test_dispatch_and_combine_are_the_dense_form_at_every_load(load):
    """``out[t] = sum over t's held pairs of w[t, c] * f_e(x[t])``, with its
    gradients, whatever share of the worst case the plan uses."""
    _, idx, lo, held, tm = load
    idx = jnp.asarray(idx, jnp.int32)
    n, k = idx.shape
    ks = jax.random.split(jax.random.PRNGKey(n), 4)
    x = jax.random.normal(ks[0], (n, 8))
    weights = jax.nn.softmax(jax.random.normal(ks[1], (n, k)))
    scale = jax.random.normal(ks[2], (held,)) + 2.0    # f_e = scale[e] * tanh
    c = jax.random.normal(ks[3], (n, 8))

    def sorted_rows(x, weights, scale):
        p = expert_dispatch.plan(idx, lo, held, tm)
        by_row = jnp.repeat(scale[p.tile_expert], tm)[:, None]
        y = expert_dispatch.on_used_rows(lambda r, s: jnp.tanh(r) * s, p,
                                         expert_dispatch.dispatch(x, p), by_row)
        return jnp.sum(c * expert_dispatch.combine(y, weights, p))

    def dense(x, weights, scale):
        out = 0.0
        for e in range(held):
            share = jnp.sum(jnp.where(idx == lo + e, weights, 0.0), axis=1)
            out = out + share[:, None] * jnp.tanh(x) * scale[e]
        return jnp.sum(c * out)

    a, ga = jax.value_and_grad(sorted_rows, (0, 1, 2))(x, weights, scale)
    b, gb = jax.value_and_grad(dense, (0, 1, 2))(x, weights, scale)
    assert abs(float(a - b)) < 1e-4 * max(1.0, abs(float(b)))
    for u, v in zip(ga, gb):
        assert float(jnp.max(jnp.abs(u - v))) < 1e-4 * max(1.0, float(jnp.max(jnp.abs(v))))


def test_rows_past_the_plan_are_never_read():
    """The products leave the tiles past ``n_used`` unwritten: whatever stands
    there (NaN here, forward and backward) reaches no result and no
    gradient."""
    n, k, d, f, held, tm = 64, 2, 16, 24, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 7)
    x = jax.random.normal(ks[0], (n, d))
    weights, idx = jax.lax.top_k(jax.nn.softmax(jax.random.normal(ks[1], (n, 8))), k)
    w_gate, w_up, w_down = (jax.random.normal(ks[2], (held, d, f)),
                            jax.random.normal(ks[3], (held, d, f)),
                            jax.random.normal(ks[4], (held, f, d)))
    c = jax.random.normal(ks[5], (n, d))
    p = expert_dispatch.plan(idx, 3, held, tm)
    past = jnp.arange(p.row_pair.shape[0])[:, None] >= p.n_used[0] * tm
    assert 0 < int(p.n_used[0]) < p.tile_expert.shape[0] // 2

    @jax.custom_vjp
    def spoil(a):
        return jnp.where(past, jnp.nan, a)

    spoil.defvjp(lambda a: (spoil(a), None), lambda _, g: (spoil(g),))

    def layer(spoil, x, weights, w_gate, w_up, w_down):
        args = (p.tile_expert, p.n_used, tm)
        rows, again = expert_dispatch.twice(expert_dispatch.dispatch(x, p), p)
        gate = spoil(grouped_matmul(rows, w_gate, *args))
        up = spoil(grouped_matmul(again, w_up, *args))
        act = spoil(expert_dispatch.on_used_rows(mellum._swiglu, p, gate, up))
        y = spoil(grouped_matmul(act, w_down, *args))
        return jnp.sum(c * expert_dispatch.combine(y, weights, p))

    grad = jax.value_and_grad(layer, (1, 2, 3, 4, 5))
    want = grad(lambda a: a, x, weights, w_gate, w_up, w_down)
    got = grad(spoil, x, weights, w_gate, w_up, w_down)
    for u, v in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.isfinite(u).all()) and float(jnp.max(jnp.abs(u - v))) == 0.0


def test_plan_sizes_for_the_worst_case_and_drops_nothing():
    idx = jnp.zeros((40, 2), jnp.int32).at[:, 1].set(1)   # every pair held
    p = expert_dispatch.plan(idx, 0, 2, 8)
    assert p.row_pair.shape[0] == (80 // 8 + 2) * 8
    assert int((p.row_pair >= 0).sum()) == 80 and list(map(int, p.counts)) == [40, 40]
    assert int(p.n_used[0]) == 10 and sorted(set(map(int, p.tile_expert))) == [0, 1]
    nobody = expert_dispatch.plan(idx + 5, 0, 2, 8)       # none held: a tile each
    assert int((nobody.row_pair >= 0).sum()) == 0 and int(nobody.n_used[0]) == 2


def test_preset_is_the_published_configuration_uncut():
    import json

    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if "Mellum2-12B-A2.5B" in line) if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    cfg = load_config(preset="Mellum2-12B-A2.5B")
    lm = cfg.model.decoder_lm
    assert cfg.model.family == "decoder_lm"
    assert (lm.num_hidden_layers, lm.n_experts_held, lm.n_vocab_held) == (28, 64, 98304)
    if row is None:
        pytest.skip("the catalog is not on this machine")
    for key, value in row["config"].items():
        mine = getattr(lm, key)
        if key == "rope_parameters":
            for kind, group in value.items():
                for name, x in group.items():
                    assert getattr(getattr(mine, kind), name) == x, (kind, name)
        else:
            assert (list(mine) if isinstance(value, list) else mine) == value, key


@pytest.mark.parametrize("bad", [
    {"family": "diffusion"},
    {"family": "decoder_lm", "decoder_lm": {"expert_offset": 60, "experts_held": 16}},
    {"family": "decoder_lm", "decoder_lm": {"num_hidden_layers": 40}},
    {"family": "decoder_lm", "decoder_lm": {"layer_types": ["linear"] * 28}},
    {"family": "decoder_lm", "decoder_lm": {"vocab_held": 10 ** 6}},
])
def test_config_refuses_what_it_cannot_build(bad):
    from speakingstyle_tpu.configs.config import ModelConfig

    with pytest.raises(ValueError):
        _build(ModelConfig, bad)
