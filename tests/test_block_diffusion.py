"""The ``decoder_lm`` family's second objective, block diffusion, at toy size
on the CPU: (a) the block mask of ``blocked_attention`` against the einsum
reference and a dense ``arange`` mask, forward and gradients, in the Pallas
interpreter, with the tiles each kernel visits counted against the mask's own
arithmetic; (b) the toy model against ``benchmark/reference/sdar.py`` on
seeded weights, and the eight shares against the uncut layer; (c) the
loader's noise; and the preset."""

import collections
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import common, train_compare  # noqa: E402
from benchmark.reference import sdar as ref  # noqa: E402
from speakingstyle_tpu import obs  # noqa: E402
from speakingstyle_tpu.configs.config import (  # noqa: E402
    DecoderLMConfig, _build, load_config)
from speakingstyle_tpu.data import CacheBudget, PackedBatcher, TokenDataset  # noqa: E402
from speakingstyle_tpu.data.token_dataset import T_MIN, block_noise  # noqa: E402
from speakingstyle_tpu.models import mellum  # noqa: E402
from speakingstyle_tpu.ops import blocked_attention as ba  # noqa: E402
from speakingstyle_tpu.ops import qk_prepare  # noqa: E402

CONFIG = "benchmark/configs/sdar_30b_ep8share.json"


# -- (a) the mask in the kernels ----------------------------------------------

def dense_mask(L, c):
    """The issue's three clauses, pair by pair."""
    ok = np.zeros((2 * L, 2 * L), bool)
    for i in range(2 * L):
        for j in range(2 * L):
            qn, kn, qb, kb = i < L, j < L, (i % L) // c, (j % L) // c
            ok[i, j] = (qn == kn and qb == kb) or (qn and not kn and qb > kb) \
                or (not qn and not kn and qb >= kb)
    return ok


@pytest.mark.parametrize("L,c,kv_heads,path", [
    pytest.param(256, 4, 2, "fused", id="2_tiles_c4"),
    pytest.param(384, 32, 2, "fused", id="3_tiles_c32"),
    pytest.param(256, 64, 2, "fused", id="2_tiles_c64"),
    pytest.param(384, 4, 4, "fused", id="3_tiles_c4_one_query_head_a_kv_head"),
    pytest.param(384, 4, 2, "split", id="3_tiles_c4_split")])
def test_block_mask_is_the_dense_mask_forward_and_backward(monkeypatch, L, c, kv_heads,
                                                           path):
    """Loss and ``dq``, ``dk``, ``dv`` of the kernels in the interpreter
    against the einsum reference and a dense mask pair by pair: grouped
    queries (four over two) and a query head to a key-value head through
    the fused backward, and the split path's two kernels where the fast
    memory the fused one may ask for is cut to nothing."""
    if path == "split":
        monkeypatch.setattr(ba, "V5E_VMEM", 0)
    T = 2 * L
    G = 4 // kv_heads
    ks = jax.random.split(jax.random.PRNGKey(L + c), 4)
    q = jax.random.normal(ks[0], (2, 4, T, 32))
    k = jax.random.normal(ks[1], (2, kv_heads, T, 32))
    v = jax.random.normal(ks[2], (2, kv_heads, T, 32))
    w = jax.random.normal(ks[3], (2, 4, T, 32))
    mask, scale = ba.BlockDiffusion(c), 1 / math.sqrt(32)
    ok = dense_mask(L, c)
    assert (np.asarray(mask.seen(T)) == ok).all()
    assert ok.sum() == L * L + c * L          # a quarter of the square, and c L

    def kernel(q, k, v):
        return jnp.sum(w * ba.blocked_attention(q, k, v, block=128, interpret=True,
                                                mask=mask))

    def einsum(q, k, v):
        return jnp.sum(w * ba.reference_attention(q, k, v, None, scale, mask))

    def dense(q, k, v):
        s = jnp.einsum("bkgtd,bksd->bkgts", q.reshape(2, kv_heads, G, T, 32), k) * scale
        p = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
        return jnp.sum(w * jnp.einsum("bkgts,bksd->bkgtd", p, v).reshape(2, 4, T, 32))

    grad = jax.value_and_grad(kernel, (0, 1, 2))
    assert ba.launches(jax.make_jaxpr(grad)(q, k, v)) == (
        {"fused": 1, "split": 0} if path == "fused" else {"fused": 0, "split": 2})
    a, ga = grad(q, k, v)
    for other in (einsum, dense):
        b, gb = jax.value_and_grad(other, (0, 1, 2))(q, k, v)
        assert abs(float(a - b)) < 1e-3
        for x, y in zip(ga, gb):
            assert float(jnp.max(jnp.abs(x - y))) < 1e-4


@pytest.mark.parametrize("L,c,blk", [(256, 4, 128), (384, 32, 128), (4096, 4, 512)],
                         ids=["2_tiles", "3_tiles", "the_cell"])
def test_kernels_visit_the_tiles_that_hold_a_pair_and_no_other(L, c, blk):
    """From the query side (forward, ``dq``) and from the key side (``dk``,
    ``dv``): the same tile pairs, each once; those with a masked pair are the
    ones that pay for the mask; what is fetched for a step that visits
    nothing is the last tile visited."""
    n, mask = L // blk, ba.BlockDiffusion(c)
    geom = ba._BlockDiffusion(c, blk, n)
    block_of = lambda tile: (tile % n) * (blk // c)        # its first block
    def holds(i, kb):   # by the mask's clauses on the tiles' first and last blocks
        qb, kb_ = (block_of(i), block_of(i) + blk // c - 1), (block_of(kb), block_of(kb) + blk // c - 1)
        if (i < n) == (kb < n) and i < n:
            return i == kb
        if i < n and kb >= n:
            return qb[1] > kb_[0]
        return i >= n and kb >= n and qb[1] >= kb_[0]
    want = {(i, kb) for i in range(2 * n) for kb in range(2 * n) if holds(i, kb)}
    from_q = [(i, int(geom.key(i, j))) for i in range(2 * n)
              for j in range(geom.q_steps)
              if bool(geom.key_seen(i, j, geom.key(i, j)))]
    from_k = [(int(geom.query(kb, t)), kb) for kb in range(2 * n)
              for t in range(geom.k_steps)
              if bool(geom.query_seen(kb, t, geom.query(kb, t)))]
    assert len(from_q) == len(from_k) == len(want) == mask.tiles(2 * L, blk) \
        == n * n + 2 * n
    assert set(from_q) == set(from_k) == want
    assert geom.q_steps == n + 1                      # the longest row of tiles
    partial = {(i, kb) for i, kb in want if bool(geom.partial(i, kb))}
    assert partial == {(i, i) for i in range(2 * n)} | {(i, i + n) for i in range(n)}
    for i in range(2 * n):
        row = [int(geom.fetch_key(i, j)) for j in range(geom.q_steps)]
        seen = [kb for q_, kb in from_q if q_ == i]
        assert row == seen + [seen[-1]] * (geom.q_steps - len(seen))
    for kb in range(2 * n):
        col = [int(geom.fetch_query(kb, t)) for t in range(geom.k_steps)]
        seen = [q_ for q_, k_ in from_k if k_ == kb]
        assert col == seen + [seen[-1]] * (geom.k_steps - len(seen))
    if L == 4096:     # the cell: 80 tiles where the triangle over 2L has 136
        assert (len(want), (2 * n) * (2 * n + 1) // 2) == (80, 136)


def _primitives(jaxpr, out):
    for eqn in jaxpr.eqns:
        out[eqn.primitive.name] += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, out)
    return out


@pytest.mark.parametrize("window,T", [(None, 512), (200, 640)], ids=["causal", "window"])
def test_causal_and_window_calls_trace_to_what_they_traced_to(window, T):
    """The mask argument's default changes nothing: the same jaxpr with and
    without it. The forward kernel's equations are the parent's, counted
    from the parent commit's trace of the same call; the backward is one
    kernel where the parent had two (``dq``, ``dk``/``dv``), and forms each
    visited tile's scores once: two block products and one ``exp`` fewer in
    each of its two branches (the tiles with masked pairs and the whole
    ones), 5 products and 1 ``exp`` where the two kernels had 7 and 2."""
    q = jnp.zeros((1, 4, T, 128), jnp.bfloat16)
    k = jnp.zeros((1, 2, T, 128), jnp.bfloat16)

    def loss(**kw):
        return lambda q, k, v: jnp.sum(ba.blocked_attention(
            q, k, v, window=window, block=128, interpret=False, **kw).astype(jnp.float32))

    plain = jax.make_jaxpr(jax.grad(loss(), (0, 1, 2)))(q, k, k)
    named = jax.make_jaxpr(jax.grad(loss(mask=None), (0, 1, 2)))(q, k, k)
    assert str(plain) == str(named)
    counts = _primitives(plain.jaxpr, collections.Counter())
    parent = PARENT_COUNTS["window" if window else "causal"]
    assert counts["pallas_call"] == parent["grad"]["pallas_call"] - 1 == 2
    assert ba.launches(plain) == {"fused": 1, "split": 0}
    # the forward: the kernel whose results hold the float32 log-sum-exp
    forward, = [e for e in plain.jaxpr.eqns if e.primitive.name == "pallas_call"
                and any(o.aval.dtype == jnp.float32 for o in e.outvars)]
    assert dict(_primitives(forward.params["jaxpr"], collections.Counter(
        pallas_call=1))) == parent["forward"]
    assert counts["dot_general"] == parent["grad"]["dot_general"] - 2 * 2
    assert counts["exp"] == parent["grad"]["exp"] - 2 * 1


# equations inside grad(blocked_attention) at the parent commit, by
# primitive: the kernels' bodies, their index maps and the XLA around them
# (``grad``), and the forward kernel's alone (``forward``)
PARENT_COUNTS = json.load(open(os.path.join(
    ROOT, "tests", "data", "blocked_attention_parent_counts.json")))


def test_mask_refuses_what_the_tiles_cannot_hold():
    q = jnp.zeros((1, 2, 512, 32))
    with pytest.raises(ValueError):      # a window and the mask
        ba.blocked_attention(q, q, q, window=64, mask=ba.BlockDiffusion(4))
    with pytest.raises(ValueError):      # a stream that is no whole number of tiles
        ba.blocked_attention(q[:, :, :400], q[:, :, :400], q[:, :, :400],
                             interpret=True, mask=ba.BlockDiffusion(4))
    with pytest.raises(ValueError):      # a tile that is no whole number of blocks
        ba.blocked_attention(q, q, q, interpret=True, block=128,
                             mask=ba.BlockDiffusion(48))


# -- (b) the toy model against the reference ----------------------------------

def toy_block() -> dict:
    return common.sized(common.load_json(CONFIG), True)["model"]


@pytest.fixture(scope="module")
def toy():
    model = toy_block()
    block, hp = model["decoder_lm"], ref.hyper(model)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (4, 32), 1, 255),
                        np.int32)
    noised, weight, _ = block_noise(tokens, block["block_length"], block["mask_id"],
                                    5, 0, 0)
    batch = {"tokens": tokens, "noised": noised, "weight": weight}
    return block, hp, ref.init_params(hp, 7), batch


def program_grads(block, params, batch, dtype):
    model = mellum.DecoderLM(_build(DecoderLMConfig, block), dtype=dtype)
    (loss, aux), grads = jax.value_and_grad(
        lambda p: model.apply({"params": p}, **mellum.batch_inputs(batch)),
        has_aux=True)(params)
    return float(loss), ref.flatten(grads), aux


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def test_program_is_the_reference_in_float32(toy):
    block, hp, params, batch = toy
    loss, grads, aux = program_grads(block, params, batch, jnp.float32)
    ref_loss, ref_grads, ref_choices = ref.loss_and_grads(
        hp, jax.tree_util.tree_map(jnp.asarray, params), batch, 2)
    assert abs(loss - ref_loss) < 1e-5 * ref_loss
    ref_grads = ref.flatten(ref_grads)
    assert set(grads) == set(ref_grads)
    assert {"layers_0/self_attn/q_norm/scale", "layers_0/self_attn/k_norm/scale"} \
        <= set(grads)
    # every leaf, tightly: same equations, float32 on both sides
    assert max(rel(grads[k], ref_grads[k]) for k in ref_grads) < 2e-5
    # the choices over both streams' positions, layer by layer
    assert aux["choices"].shape == (hp["layers"], 4, 64, hp["top_k"])
    for mine, theirs in zip(np.asarray(aux["choices"]), ref_choices):
        assert (np.sort(mine, -1) == np.sort(theirs, -1)).all()
    assert int((aux["pairs_routed"] - aux["pairs_placed"]).sum()) == 0
    assert int(aux["tokens_masked"]) == int((batch["weight"] > 0).sum())
    assert float(aux["loss_weight"]) == pytest.approx(float(batch["weight"].sum()))


def test_program_with_qk_prepare_emulated_is_the_program_by_parts(toy, monkeypatch):
    """At a head of 128 lanes the interpreted ``qk_prepare`` (norm variant)
    stands where norm, rotation and transpose stand by parts: same loss, same
    gradients (the two norms' scales among them) to the tolerance this file
    holds the reference to, and 3 layers x (q, k) x (forward, recomputed,
    backward) launches in the step."""
    block, batch = {**toy[0], "head_dim": 128}, toy[3]
    params = ref.init_params(ref.hyper({**toy_block(), "decoder_lm": block}), 7)
    loss, grads, _ = program_grads(block, params, batch, jnp.float32)
    real = mellum.qk_prepare
    monkeypatch.setattr(mellum, "qk_prepare",
                        lambda *a, **kw: real(*a, interpret=True, **kw))
    fused_loss, fused, _ = program_grads(block, params, batch, jnp.float32)
    assert abs(fused_loss - loss) < 1e-5 * loss
    assert set(fused) == set(grads)
    assert max(rel(fused[k], grads[k]) for k in grads) < 2e-5
    model = mellum.DecoderLM(_build(DecoderLMConfig, block), dtype=jnp.float32)
    step = jax.make_jaxpr(jax.grad(lambda p: model.apply(
        {"params": p}, **mellum.batch_inputs(batch))[0]))(params)
    assert qk_prepare.launches(step) == {"norm": 18, "plain": 0}


def test_program_in_bfloat16_stays_near_the_reference(toy):
    block, hp, params, batch = toy
    loss, grads, _ = program_grads(block, params, batch, jnp.bfloat16)
    ref_loss, ref_grads, _ = ref.loss_and_grads(
        hp, jax.tree_util.tree_map(jnp.asarray, params), batch, 4)
    assert abs(loss - ref_loss) < 1e-2 * ref_loss
    gaps = train_compare.leaf_gaps(grads, ref.flatten(ref_grads))
    assert train_compare.worst_leaf(gaps)[0] < 0.1


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_fault_of_the_reference_moves_loss_or_gradient(toy, fault):
    """What the cell's controls plant is no no-op at toy size."""
    block, hp, params, batch = toy
    p = jax.tree_util.tree_map(jnp.asarray, params)
    if fault == "no_qk_norm":   # scales of 1 on nearly unit rows hide it: move them
        for i in range(hp["layers"]):
            p[f"layers_{i}"]["self_attn"]["q_norm"]["scale"] = jnp.full((16,), 3.0)
    loss, grads, _ = ref.loss_and_grads(hp, p, batch, 4)
    bad_loss, bad_grads, _ = ref.loss_and_grads(hp, p, batch, 4, fault=fault)
    g, b = ref.flatten(grads), ref.flatten(bad_grads)
    moved = max(rel(b[k], g[k]) for k in g)
    assert abs(bad_loss - loss) > 1e-3 * loss or moved > 1e-2, (bad_loss, loss, moved)


def test_a_noised_token_never_sees_its_clean_self_nor_a_clean_one_any_noise(toy):
    """Through the whole toy model: the logits at a noised position do not move
    when its own block's clean tokens change, and move when an earlier clean
    block does; the clean half's states move with no noised token."""
    block, hp, params, batch = toy

    def streams(tokens, noised):
        """The final states of both streams [1, 2L, d], by the reference's
        layers: the program is the reference to 1e-7 (the test above)."""
        p = jax.tree_util.tree_map(jnp.asarray, params)
        x = p["embed"]["embedding"][jnp.concatenate([noised, tokens], 1)]
        for i in range(hp["layers"]):
            x, _ = ref.layer(hp, p[f"layers_{i}"], x, lambda a: a, None)
        return np.asarray(x)

    tokens, noised = batch["tokens"][:1], batch["noised"][:1]
    base = streams(tokens, noised)
    c, L = block["block_length"], tokens.shape[1]
    own = tokens.copy()
    own[0, 2 * c:3 * c] = (own[0, 2 * c:3 * c] % 200) + 7      # block 2's clean tokens
    moved = streams(own, noised)
    assert np.allclose(moved[0, 2 * c:3 * c], base[0, 2 * c:3 * c], atol=1e-6)   # noised block 2
    assert not np.allclose(moved[0, 3 * c:4 * c], base[0, 3 * c:4 * c], atol=1e-6)  # noised block 3 sees it
    assert np.allclose(moved[0, :2 * c], base[0, :2 * c], atol=1e-6)             # earlier noised blocks
    other = noised.copy()
    other[0, :] = (other[0, :] % 200) + 3
    assert np.allclose(streams(tokens, other)[0, L:], base[0, L:], atol=1e-6)    # the clean half


def test_the_eight_shares_add_up_to_the_uncut_layer(toy):
    """Each share routes over all sixty-four experts and computes its own
    eight; the eight partial results sum to what the reference gives with
    all sixty-four held (``model-configs`` section 4)."""
    block, hp, params, _ = toy
    moe, attn = params["layers_0"]["moe"], params["layers_0"]["self_attn"]
    n, held = hp["experts"], hp["held"]
    assert n // held == 8
    full = dict(hp, held=n, lo=0)
    rng = np.random.default_rng(3)
    wide = {k: rng.standard_normal((n,) + moe["experts"][k].shape[1:]).astype(
        np.float32) * 0.05 for k in ("gate", "up", "down")}
    x = jnp.asarray(rng.standard_normal((2, 64, hp["d"])), jnp.float32)
    layer_p = {"self_attn": attn, "moe": {**moe, "experts": wide}}
    y, _ = ref.layer(full, layer_p, x, lambda a: a, None)
    none_held = {"self_attn": attn,
                 "moe": {**moe, "experts": {k: v[:0] for k, v in wide.items()}}}
    h, _ = ref.layer(dict(full, held=0), none_held, x, lambda a: a, None)
    uncut = y - h              # the reference's layer less its attention half
    total = jnp.zeros_like(uncut)
    for lo in range(0, n, held):
        cfg = _build(DecoderLMConfig, {**block, "expert_offset": lo})
        for r in range(2):
            total = total.at[r].add(mellum.moe_row(
                h[r], moe["norm_scale"], moe["router"]["kernel"],
                wide["gate"][lo:lo + held], wide["up"][lo:lo + held],
                wide["down"][lo:lo + held], cfg=cfg)[0])
    assert rel(total, uncut) < 1e-5


def test_placement_deals_the_routers_outputs_into_even_groups():
    """At the cell's widths (the embedding and one router: 156 MB): the eight
    groups' loads under the token mix lie within 2% of one pair a position,
    each layer's router is the first draw with its group moved to the held
    window, and the groups part the outputs."""
    cfg = common.load_json(CONFIG)
    hp = ref.hyper(cfg["model"])
    rng = np.random.default_rng(11)
    E = rng.standard_normal((hp["vocab"], hp["d"]), dtype=np.float32)
    W = 0.02 * rng.standard_normal((hp["d"], hp["experts"]), dtype=np.float32)
    groups = ref.placement(hp, E, W)
    assert sorted(o for g in groups for o in g) == list(range(128))
    assert [len(g) for g in groups] == [16] * 8
    ids, shares = ref.token_mix(hp)
    assert ids[0] == hp["mask_id"] == 18991 and shares[0] == 0.25
    assert shares.sum() == pytest.approx(1.0)
    u = E[ids] / np.sqrt((E[ids].astype(np.float64) ** 2).mean(-1, keepdims=True))
    chosen = np.argsort(-(u @ W), -1)[:, :8]
    loads = [float((shares[:, None] * np.isin(chosen, g)).sum()) for g in groups]
    assert max(abs(x - 1.0) for x in loads) < 0.02, loads
    toy_hp = ref.hyper(toy_block())
    tree = ref.init_params(toy_hp, 3)
    g = ref.placement(toy_hp, tree["embed"]["embedding"],
                      tree["layers_0"]["moe"]["router"]["kernel"])
    # layer 0's router is the draw with group 0 in front: undo it
    first = np.empty_like(tree["layers_0"]["moe"]["router"]["kernel"])
    first[:, g[0] + sorted(set(range(toy_hp["experts"])) - set(g[0]))] = \
        tree["layers_0"]["moe"]["router"]["kernel"]
    g = ref.placement(toy_hp, tree["embed"]["embedding"], first)
    for i in range(toy_hp["layers"]):
        here = tree[f"layers_{i}"]["moe"]["router"]["kernel"]
        assert (here[:, :toy_hp["held"]] == first[:, g[i]]).all()
        assert sorted(map(tuple, here.T)) == sorted(map(tuple, first.T))


# -- (c) the loader's noise ---------------------------------------------------

def test_noise_is_a_function_of_seed_epoch_and_row():
    tokens = np.random.default_rng(0).integers(1, 250, (8, 64)).astype(np.int32)
    a = block_noise(tokens, 4, 255, 9, 0, 0)
    b = block_noise(tokens, 4, 255, 9, 0, 0)
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all() and a[2] == b[2]
    assert a[2] == int((a[0] != tokens).sum()) == int((a[1] > 0).sum())
    assert ((a[0] == 255) == (a[1] > 0)).all()
    # rows 4.. alone, as a run resumed in mid-epoch draws them
    later = block_noise(tokens[4:], 4, 255, 9, 0, 4)
    assert (later[0] == a[0][4:]).all() and (later[1] == a[1][4:]).all()
    for other in (block_noise(tokens, 4, 255, 9, 1, 0),       # another epoch
                  block_noise(tokens, 4, 255, 10, 0, 0)):     # another seed
        assert (other[0] != a[0]).any()
    # a block's weight is one number, 1 / t, and t lies in [T_MIN, 1]
    w = a[1].reshape(8, 16, 4)
    for block in w.reshape(-1, 4):
        assert len(set(block[block > 0])) <= 1
    assert w.max() <= 1 / T_MIN and w[w > 0].min() >= 1.0


def test_masked_share_of_a_block_follows_its_t():
    tokens = np.ones((64, 4096), np.int32)
    noised, weight, masked = block_noise(tokens, 4, 7, 3, 0, 0)
    assert masked == int((noised == 7).sum())
    w = weight.reshape(-1, 4)
    t = 1.0 / w.max(-1, where=w > 0, initial=1.0)      # a block's t where one is masked
    share = (w > 0).mean(-1)
    known = (w > 0).any(-1)
    for lo, hi in ((0.0, 0.25), (0.25, 0.5), (0.5, 0.75), (0.75, 1.0)):
        band = known & (t > lo) & (t <= hi)
        # among blocks with a masked position, E[share | t] = t / (1 - (1-t)^4)
        tt = t[band]
        want = (tt / (1 - (1 - tt) ** 4)).mean()
        assert abs(share[band].mean() - want) < 0.01, (lo, hi)
    assert abs((w > 0).mean() - 0.5005) < 0.005            # E[t] = 0.5005
    assert abs(weight.mean() - 1.0) < 0.02                 # E[1[masked] / t] = 1


@pytest.fixture
def corpus_config(tmp_path):
    import yaml

    from speakingstyle_tpu.configs.config import PRESET_DIR

    corpus = tmp_path / "corpus"
    (corpus / "tokens").mkdir(parents=True)
    rng = np.random.default_rng(0)
    lengths = [5, 17, 40, 9, 31, 63, 12, 25] * 8
    lines = []
    for i, n in enumerate(lengths):
        np.save(corpus / "tokens" / f"d{i:04d}.npy", rng.integers(1, 250, n).astype(np.int32))
        lines.append(f"d{i:04d}|{n}")
    (corpus / "train.txt").write_text("\n".join(lines) + "\n")
    (corpus / "val.txt").write_text("\n".join(lines[:8]) + "\n")
    preset = os.path.join(PRESET_DIR, "SDAR-30B-A3B")
    bodies = {n: yaml.safe_load(open(os.path.join(preset, n + ".yaml")))
              for n in ("preprocess", "model", "train")}
    bodies["preprocess"]["path"]["preprocessed_path"] = str(corpus)
    bodies["model"] = {k: toy_block()[k] for k in ("family", "compute_dtype", "decoder_lm")}
    bodies["train"]["path"] = {k: str(tmp_path / k)
                               for k in ("ckpt_path", "log_path", "result_path")}
    bodies["train"]["step"].update(log_step=2, val_step=4, save_step=4, total_step=4)
    paths = {}
    for name, body in bodies.items():
        paths[name] = str(tmp_path / f"{name}.yaml")
        with open(paths[name], "w") as f:
            yaml.safe_dump(body, f)
    return load_config(**paths)


def test_packer_hands_noised_and_weight_beside_tokens(corpus_config):
    reg = obs.MetricsRegistry()
    ds = TokenDataset("train.txt", corpus_config, cache=CacheBudget())

    def batches(seed, epochs=1):
        b = PackedBatcher(ds, seq_len=32, eod_id=0, seed=seed, registry=reg,
                          noise=(4, 255))
        return [list(b.epoch()) for _ in range(epochs)]

    first, second = batches(3, 2)
    again = batches(3)[0]
    for x, y in zip(first, again):                       # same seed, same noise
        assert (x.tokens == y.tokens).all() and (x.noised == y.noised).all() \
            and (x.weight == y.weight).all()
    assert set(first[0].arrays()) == {"tokens", "noised", "weight"}
    assert first[0].noised.shape == first[0].weight.shape == (4, 32)
    assert first[0].weight.dtype == np.float32 and first[0].noised.dtype == np.int32
    assert first[0].frames_real == 128                   # corpus tokens, not 2L
    # the noise of a batch is its rows' own: drawn apart from the epoch's
    # earlier rows, as a resumed run would draw it
    at = 4 * 2
    n, w, _ = block_noise(first[2].tokens, 4, 255, 3, 0, at)
    assert (n == first[2].noised).all() and (w == first[2].weight).all()
    # another epoch, other noise: rows of the second epoch by its own index
    n, w, _ = block_noise(second[0].tokens, 4, 255, 3, 1, 0)
    assert (n == second[0].noised).all()
    assert any((a.weight != b.weight).any() for a, b in zip(first, second))
    # without noise the batch is what it was
    plain = next(iter(PackedBatcher(ds, seq_len=32, eod_id=0, seed=3,
                                    registry=reg).epoch()))
    assert set(plain.arrays()) == {"tokens"} and plain.noised is None


def test_run_training_counts_the_masked_tokens_and_their_weights(corpus_config):
    from speakingstyle_tpu.training.trainer import run_training

    reg = obs.MetricsRegistry()
    run_training(corpus_config, mesh=None, max_steps=4, registry=reg, log=True)
    with open(os.path.join(corpus_config.train.path.log_path, "events.jsonl")) as f:
        events = [json.loads(line) for line in f if line.strip()]
    steps = [e for e in events if e.get("event") == "train_step"]
    assert steps and all({"tokens_masked", "loss_weight", "moe_pairs_held"} <= set(e)
                         for e in steps)
    # per step of the window: about half of a step's 256 tokens masked
    assert all(40 < e["tokens_masked"] < 220 for e in steps)
    assert reg.value("train_tokens_masked_total") == sum(
        2 * e["tokens_masked"] for e in steps)
    assert reg.value("train_frames_real_total") == 4 * 256
    noise = [s for s in obs.trace.get_span_ring().spans() if s["name"] == "loader_noise"]
    assert noise and {"rows", "blocks", "masked"} <= set(noise[-1]["fields"])
    val = [e for e in events if e.get("event") == "val"] or [
        e for e in events if "val" in str(e.get("event"))]
    assert val, [e.get("event") for e in events]        # the validation loss ran on the arrays


@pytest.mark.parametrize("head_dim,launches", [(16, 0), (128, 18)],
                         ids=["by_parts", "emulated"])
def test_program_card_span_counts_qk_prepares_launches(corpus_config, monkeypatch,
                                                       head_dim, launches):
    """``train_program_card`` says how often ``qk_prepare``'s kernels stand
    in the step, by variant: 0 where the parts ran (a head of 16 lanes), and
    where they engage 3 layers x (q, k) x (forward, recomputed, backward)."""
    import dataclasses

    from speakingstyle_tpu.training.trainer import run_training

    real = mellum.qk_prepare
    monkeypatch.setattr(mellum, "qk_prepare",
                        lambda *a, **kw: real(*a, interpret=True, **kw))
    model = corpus_config.model
    cfg = dataclasses.replace(corpus_config, model=dataclasses.replace(
        model, decoder_lm=dataclasses.replace(model.decoder_lm, head_dim=head_dim)))
    run_training(cfg, mesh=None, max_steps=2, registry=obs.MetricsRegistry(), log=True)
    card = [s for s in obs.trace.get_span_ring().spans()
            if s["name"] == "train_program_card"][-1]
    assert card["fields"]["qk_prepare_launches"] == {"norm": launches, "plain": 0}
    # the core ran as the einsum reference: no backward kernel
    assert card["fields"]["attn_bwd_launches"] == {"fused": 0, "split": 0}


def test_program_card_span_counts_the_cores_backward_launches(corpus_config,
                                                              monkeypatch):
    """``train_program_card`` says how often the attention core's backward
    stands in the step, by path: with the kernels emulated, one fused kernel
    a layer. Rows of 128 tokens, so that a stream is a whole lane tile."""
    import dataclasses

    from speakingstyle_tpu.training.trainer import run_training

    real = mellum.blocked_attention
    monkeypatch.setattr(mellum, "blocked_attention",
                        lambda *a, **kw: real(*a, interpret=True, **kw))
    model = corpus_config.model
    block = dataclasses.replace(model.decoder_lm, seq_len=128)
    cfg = dataclasses.replace(corpus_config, model=dataclasses.replace(
        model, decoder_lm=block))
    run_training(cfg, mesh=None, max_steps=2, registry=obs.MetricsRegistry(), log=True)
    card = [s for s in obs.trace.get_span_ring().spans()
            if s["name"] == "train_program_card"][-1]
    assert card["fields"]["attn_bwd_launches"] == {
        "fused": block.num_hidden_layers, "split": 0}


def test_preset_is_the_published_configuration_uncut():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    cfg = load_config(preset="SDAR-30B-A3B")
    lm = cfg.model.decoder_lm
    assert cfg.model.family == "decoder_lm" and lm.model_type == "sdar_moe"
    assert (lm.num_hidden_layers, lm.n_experts_held, lm.n_vocab_held) == (48, 128, 151936)
    assert (lm.objective, lm.block_length, lm.qk_norm) == ("block_diffusion", 4, True)
    assert set(lm.layer_types) == {"full_attention"} and len(lm.layer_types) == 48
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    row = next(json.loads(line) for line in open(catalog) if "SDAR-30B-A3B-Chat" in line)
    elsewhere = {"rope_theta": lm.rope_parameters.full_attention.rope_theta,
                 "rope_scaling": None, "decoder_sparse_step": 1, "mlp_only_layers": [],
                 "sliding_window": None}      # model.yaml's head says where each went
    for key, value in row["config"].items():
        mine = elsewhere[key] if key in elsewhere else getattr(lm, key)
        assert (list(mine) if isinstance(value, list) else mine) == value, key
    assert lm.rope_parameters.full_attention.rope_type == "default"
    assert set(lm.mlp_layer_types) == {"sparse"} and not lm.use_sliding_window


@pytest.mark.parametrize("bad", [
    {"objective": "diffusion"},
    {"objective": "block_diffusion", "block_length": 5},
    {"objective": "block_diffusion", "mask_id": 10 ** 6},
    {"objective": "block_diffusion", "layer_types": ["sliding_attention"] * 28},
])
def test_config_refuses_an_objective_it_cannot_build(bad):
    with pytest.raises(ValueError):
        _build(DecoderLMConfig, bad)
