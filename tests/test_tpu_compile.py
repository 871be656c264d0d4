"""The decoder_lm family's two kernels compiled for a described v5e at the
benchmark cell's real widths, without a chip: what the interpreter cannot
show (a slice off the tiling, a kernel over its fast memory) the TPU's
compiler refuses here. Nothing runs; a compile that passes is not a chip
run. The topology is described inside a fixture (never at import), and this
is the only file that loads the TPU's library."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """Such a compile is written to the persistent cache and cannot be read
    back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("window,mask", [(None, None), (1024, None), (None, 4)],
                         ids=["full", "window_1024", "block_diffusion_4"])
def test_blocked_attention_compiles_at_the_cells_shapes(one_chip, no_compile_cache,
                                                        window, mask):
    """8,192 positions: a row of ``train_mellum2_8k_ep4share``, and the noised
    and the clean stream of a 4,096-token row of ``train_sdar_4k_bd4_ep8share``."""
    from speakingstyle_tpu.ops.blocked_attention import (
        BlockDiffusion, blocked_attention)

    q = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4, 8192, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(blocked_attention(
            q, k, v, window=window, interpret=False,
            mask=mask and BlockDiffusion(mask)).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3   # fwd, dq, dk/dv


@pytest.mark.parametrize("heads", [32, 4], ids=["q_32_heads", "k_4_heads"])
@pytest.mark.parametrize("normed", [True, False], ids=["norm", "plain"])
def test_qk_prepare_compiles_at_the_cells_shapes(one_chip, no_compile_cache, heads,
                                                 normed):
    """Both variants, forward and backward, at a step's ``q`` and ``k`` of
    both decoder cells, ``[4, 8192, 32 * 128]`` and ``[4, 8192, 4 * 128]``,
    from the projection's float32 to the core's bfloat16."""
    from speakingstyle_tpu.ops.qk_prepare import qk_prepare

    x = jax.ShapeDtypeStruct((4, 8192, heads * 128), jnp.float32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((8192, 128), jnp.float32, sharding=one_chip)
    scale = jax.ShapeDtypeStruct((128,), jnp.float32, sharding=one_chip)

    def loss(x, cos, sin, scale):
        return jnp.sum(qk_prepare(x, cos, sin, scale if normed else None, heads=heads,
                                  eps=1e-6, dtype=jnp.bfloat16,
                                  interpret=False).astype(jnp.float32))

    compiled = jax.jit(jax.value_and_grad(loss, (0, 3) if normed else (0,))).lower(
        x, table, table, scale).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2


def _transposes(jaxpr, sizes, found=None):
    """{size: ``transpose`` equations over an array of that many elements},
    in ``jaxpr`` and all it holds."""
    found = dict.fromkeys(sizes, 0) if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "transpose" and eqn.invars[0].aval.size in found:
            found[eqn.invars[0].aval.size] += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _transposes(sub, sizes, found)
    return found


@pytest.mark.parametrize("preset,window", [
    ("Mellum2-12B-A2.5B", 0), ("Mellum2-12B-A2.5B", 1024), ("SDAR-30B-A3B", 0)],
    ids=["full", "window_1024", "block_diffusion_qk_norm"])
def test_attention_half_keeps_the_core_at_the_cells_shapes(one_chip, no_compile_cache,
                                                           monkeypatch, capsys, preset,
                                                           window):
    """A cell's attention half (norm, projections, ``qk_prepare``, core,
    ``o_proj``) at ``[4, 8192, hidden]`` under the model's rematerialisation
    policy, forward and backward. Nine kernels in the compiled step: the
    core's three (its forward's two results are kept, so it is not launched
    again: ten otherwise) and ``qk_prepare``'s for ``q`` and for ``k``,
    forward, forward again in the backward, backward. What crosses from the
    forward to the backward beside the half's arguments is the core's output
    and log-sum-exp alone. No ``q`` or ``k`` is transposed any more: of their
    sizes only ``o`` and ``v`` still are (forward, recomputed, cotangent)."""
    import flax.linen as nn
    from jax.interpreters import partial_eval as pe

    from speakingstyle_tpu.configs.config import load_config
    from speakingstyle_tpu.models import mellum

    # the backend here is the CPU: compile the kernels, as the chip would
    core, prepare = mellum.blocked_attention, mellum.qk_prepare
    monkeypatch.setattr(mellum, "blocked_attention",
                        lambda *a, **kw: core(*a, interpret=False, **kw))
    monkeypatch.setattr(mellum, "qk_prepare",
                        lambda *a, **kw: prepare(*a, interpret=False, **kw))
    cfg = load_config(preset=preset).model.decoder_lm
    half = nn.remat(mellum.SelfAttention, policy=mellum.KEEP_CORE)(
        cfg, window, jnp.bfloat16)
    kind = "sliding_attention" if window else "full_attention"
    cos, sin = mellum.rope_tables(getattr(cfg.rope_parameters, kind),
                                  cfg.head_dim, 8192)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    x = jax.ShapeDtypeStruct((4, 8192, cfg.hidden_size), jnp.bfloat16)
    params = jax.eval_shape(half.init, jax.random.PRNGKey(0), x, cos, sin)

    def loss(p, x, cos, sin):
        return jnp.sum(half.apply(p, x, cos, sin).astype(jnp.float32))

    args = on_chip((params, x, cos, sin))
    step = jax.value_and_grad(loss, (0, 1))
    compiled = jax.jit(step).lower(*args).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 9
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(loss, *args)
    kept = [line.split()[0] for line in capsys.readouterr().out.splitlines()
            if " from the argument " not in line]
    H, D = cfg.num_attention_heads, cfg.head_dim
    assert sorted(kept) == [f"bf16[4,{H},8192,{D}]", f"f32[4,{H},1,8192]"]
    closed = jax.make_jaxpr(step)(*args)
    live = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))[0]
    q_size, k_size = 4 * 8192 * H * D, 4 * 8192 * cfg.num_key_value_heads * D
    assert _transposes(live, (q_size, k_size)) == {q_size: 3, k_size: 3}
    assert mellum.qk_prepare.__module__ == __name__     # the patch was in place


@pytest.mark.parametrize("k,n", [(2304, 896), (896, 2304)], ids=["gate_up", "down"])
def test_grouped_product_compiles_at_the_cells_shapes(one_chip, no_compile_cache, k, n):
    from speakingstyle_tpu.ops.grouped_matmul import TILE_ROWS, grouped_matmul

    held, tm = 16, TILE_ROWS
    rows = (8192 * 8 // tm + held) * tm      # a row's worst case: every pair held
    args = (jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((held, k, n), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((rows // tm,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip))

    def loss(x, w, tile_expert, n_used):
        return jnp.sum(grouped_matmul(x, w, tile_expert, n_used, tm,
                                      False).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, (0, 1))).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2   # dx and dw


def test_dispatch_and_combine_compile_at_the_cells_shapes(one_chip, no_compile_cache,
                                                          monkeypatch):
    """Rows out and back, forward and backward, for one batch row of the
    cell: 8,192 tokens of 2,304, 8 choices each, 16 of 64 experts held."""
    from speakingstyle_tpu.ops import expert_dispatch
    from speakingstyle_tpu.ops.grouped_matmul import TILE_ROWS

    # the backend here is the CPU: take the chip's branch, as the chip would
    monkeypatch.setattr(expert_dispatch, "on_tpu", lambda: True)
    x = jax.ShapeDtypeStruct((8192, 2304), jnp.bfloat16, sharding=one_chip)
    weights = jax.ShapeDtypeStruct((8192, 8), jnp.float32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((8192, 8), jnp.int32, sharding=one_chip)

    def loss(x, weights, idx):
        p = expert_dispatch.plan(idx, 0, 16, TILE_ROWS)
        rows = expert_dispatch.on_used_rows(jax.nn.silu, p,
                                            expert_dispatch.dispatch(x, p))
        return jnp.sum(expert_dispatch.combine(rows, weights, p).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, (0, 1))).lower(x, weights, idx).compile()
    # every pass over the sorted rows is a loop as long as the plan says,
    # into a buffer that nothing fills
    text = compiled.as_text()
    assert text.count(" while(") >= 4 and text.count("tpu_custom_call") >= 3
