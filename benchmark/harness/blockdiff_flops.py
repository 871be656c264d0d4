"""Operations and bytes of the ``decoder_lm`` family's step under the
block-diffusion objective, computed from shapes, and the per-layer readings
of the cell that trains it (``train_sdar_4k_bd4_ep8share``). As
``lm_flops`` counts (matrix products only, 2 per multiply-add, a backward
pass twice its forward, recomputation nothing), with what the objective
changes: every layer runs over ``2L`` positions a row of ``L`` tokens (the
noised and the clean copy), the attention core over the pairs the block mask
lets see, ``L^2 + c L`` of the ``4 L^2`` a head and row, the head over the
noised half only. A *frame* of ``train_frames_per_s`` is a corpus token,
``L`` a row: the doubled stream is the method's cost.

``m`` is a configuration's ``model.decoder_lm`` block. Device events are
found by their module path, as ``lm_flops`` finds them.
"""

from . import lm_flops
from .lm_flops import cell_model, device_seconds, traced_steps, under


def seen_pairs(tokens: int, block_length: int) -> int:
    """Pairs (query, key) the mask lets see, a head and a row of ``tokens``:
    a noised position its block's ``c`` and the ``c blk(i)`` clean positions
    before its block, a clean position the ``c (blk(i) + 1)`` up to its own."""
    return tokens * tokens + block_length * tokens


def forward_flops_per_row(m: dict, tokens: int) -> dict:
    """Forward operations of one row of ``tokens`` corpus tokens, by part.
    The held experts at the expected share of a position's choices."""
    d, hd = m["hidden_size"], m["head_dim"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    layers, positions = m["num_hidden_layers"], 2 * tokens
    held = m.get("experts_held") or m["num_experts"]
    pairs = m["num_experts_per_tok"] * held / m["num_experts"]
    vocab = m.get("vocab_held") or m["vocab_size"]
    return {
        "projections": positions * layers * (2.0 * d * (h + 2 * kv) * hd
                                             + 2.0 * h * hd * d),
        "attention_core": layers * 4.0 * hd * h * seen_pairs(tokens, m["block_length"]),
        "router": positions * layers * 2.0 * d * m["num_experts"],
        "experts": positions * layers * pairs * lm_flops.expert_pair_flops(m),
        "head": tokens * 2.0 * d * vocab,
    }


def train_step_flops(m: dict, rows: int, tokens: int) -> float:
    """Forward and backward of ``rows`` rows of ``tokens`` corpus tokens."""
    return 3.0 * rows * sum(forward_flops_per_row(m, tokens).values())


def attention_core_step(m: dict, rows: int, tokens: int):
    """(operations, bytes) of the attention core of one step, forward and
    backward, over the seen pairs only: two products forward, four backward;
    q, k, v, o and their gradients over the ``2L`` positions once each, in
    bfloat16."""
    hd, h, kv = m["head_dim"], m["num_attention_heads"], m["num_key_value_heads"]
    ops = 3.0 * rows * forward_flops_per_row(m, tokens)["attention_core"]
    nbytes = m["num_hidden_layers"] * 2 * rows * 2 * tokens * (2 * h + 2 * kv) * hd * 2.0
    return ops, nbytes


# -- the cell's per-layer readings --------------------------------------------
# Written as readers (``read(ctx)``), found by module path, printed by
# ``benchmark/tools/blockdiff_layers.py`` beside the command's line, and not
# listed in ``BENCHMARK.json``: an entry that lists a new cell alone fails
# ``tests/perfbench/test_rehearsal.py``'s last assertion (PERF.md section 7).
# ``tests/perfbench/test_cell_sdar.py`` holds the five entries ready.

def attn_blockdiff_roofline_train(ctx):
    """The attention core's share of its roofline, forward and backward: the
    least time for the seen pairs alone over the device time of everything
    under ``self_attn/core`` in the traced steps. A kernel that visits tiles
    the mask rules out reads lower, not higher."""
    took = device_seconds(ctx["trace"], lambda op: under(op, "self_attn", "core"))
    steps = traced_steps(ctx["trace"])
    if not took or not steps or not ctx.get("peaks"):
        return None
    model, traffic = cell_model(ctx)
    ops, nbytes = attention_core_step(model, traffic["batch_size"], model["seq_len"])
    pk = ctx["peaks"]
    return 100.0 * steps * max(ops / pk["bf16_flops"],
                               nbytes / pk["hbm_bytes_per_s"]) / took


def attn_tiles_seen_pct(ctx):
    """The pairs the mask lets see over the pairs of the tiles the program's
    kernels visit (the program's own mask description says how many tiles a
    pass visits at its block size): what the tiles' granularity costs. None
    where the program has no such mask (the parent) or the core never ran on
    the device."""
    try:
        from speakingstyle_tpu.ops.blocked_attention import BLOCK, BlockDiffusion
    except ImportError:
        return None
    if not device_seconds(ctx["trace"], lambda op: under(op, "self_attn", "core")):
        return None
    model, _ = cell_model(ctx)
    tokens, c = model["seq_len"], model["block_length"]
    visited = BlockDiffusion(c).tiles(2 * tokens, BLOCK) * BLOCK * BLOCK
    return 100.0 * seen_pairs(tokens, c) / visited


def attn_step_share_pct(ctx):
    """Share of the device's busy time under ``self_attn``: norm, projections,
    q/k norm, rotary, the core, ``o_proj``, forward, recomputed and backward."""
    busy = ctx["device"].get("busy_s")
    attn = device_seconds(ctx["trace"], lambda op: under(op, "self_attn"))
    return 100.0 * attn / busy if busy and attn else None


LAYER_READINGS = {
    "attn_blockdiff_roofline.train": attn_blockdiff_roofline_train,
    "attn_tiles_seen_pct": attn_tiles_seen_pct,
    "moe_gmm_roofline.train": lm_flops.moe_gmm_roofline_train,
    "moe_step_share_pct": lm_flops.moe_step_share_pct,
    "attn_step_share_pct": attn_step_share_pct,
}
