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


@pytest.mark.parametrize("window", [0, 1024], ids=["full", "window_1024"])
def test_attention_half_keeps_the_core_at_the_cells_shapes(one_chip, no_compile_cache,
                                                           monkeypatch, window):
    """The cell's attention half (norm, projections, rotary, core, ``o_proj``)
    at ``[4, 8192, 2304]`` under the model's rematerialisation policy, forward
    and backward: three kernels in the compiled step, not four (the forward
    kernel's two results are kept, so it is not launched again)."""
    import flax.linen as nn

    from speakingstyle_tpu.configs.config import load_config
    from speakingstyle_tpu.models import mellum

    real = mellum.blocked_attention
    # the backend here is the CPU: compile the kernels, as the chip would
    monkeypatch.setattr(mellum, "blocked_attention",
                        lambda *a, **kw: real(*a, interpret=False, **kw))
    cfg = load_config(preset="Mellum2-12B-A2.5B").model.decoder_lm
    half = nn.remat(mellum.SelfAttention, policy=mellum.KEEP_CORE)(
        cfg, window, jnp.bfloat16)
    cos, sin = mellum.rope_tables(cfg.rope_parameters.sliding_attention,
                                  cfg.head_dim, 8192)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    x = jax.ShapeDtypeStruct((4, 8192, cfg.hidden_size), jnp.bfloat16)
    params = jax.eval_shape(half.init, jax.random.PRNGKey(0), x, cos, sin)

    def loss(p, x, cos, sin):
        return jnp.sum(half.apply(p, x, cos, sin).astype(jnp.float32))

    compiled = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        *on_chip((params, x, cos, sin))).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 3


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
