"""Operations and bytes the ``decoder_lm`` family's step needs, computed
from shapes, and where its work stands in a device trace. Matrix products
only (2 per multiply-add); a backward pass counts twice its forward;
recomputation counts nothing. The counts read the same work whatever
implements it: device events are found by their module path in the program
(``tf_op``: flax's module names and the model's named scopes), never by
being a ``pallas_call``.

``m`` is a configuration's ``model.decoder_lm`` block.
"""

import re

from . import common, tracered

def cell_model(ctx: dict) -> tuple:
    """The ``decoder_lm`` block and the traffic of the cell that ``ctx`` was
    read in: its ``workload`` key, which ``train_cell`` does not give yet
    (``benchmark/tools/lm_layers.py`` adds it; PERF.md section 7)."""
    _, _, cfg, traffic = common.cell_files(ctx["workload"])
    return cfg["model"]["decoder_lm"], traffic


def unmasked_keys(t: int, window=None) -> float:
    """Mean number of keys a query sees over ``t`` positions: the causal
    triangle, or the band of ``window`` inside it."""
    if not window or window >= t:
        return (t + 1) / 2
    return (window * (window + 1) / 2 + (t - window) * window) / t


def layer_kinds(m: dict) -> list:
    return list(m["layer_types"][: m["num_hidden_layers"]])


def expert_pair_flops(m: dict) -> float:
    """Forward operations of one (token, choice) pair: gate, up and down."""
    return 3 * 2.0 * m["hidden_size"] * m["moe_intermediate_size"]


def forward_flops_per_token(m: dict, t: int) -> dict:
    """Forward operations of one position of a row of ``t``, by part. The
    held experts are counted at the expected share of a token's choices
    (``num_experts_per_tok * experts_held / num_experts`` pairs)."""
    d, hd = m["hidden_size"], m["head_dim"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    kinds = layer_kinds(m)
    held = m.get("experts_held") or m["num_experts"]
    pairs = m["num_experts_per_tok"] * held / m["num_experts"]
    vocab = m.get("vocab_held") or m["vocab_size"]
    core = sum(
        4.0 * hd * h * unmasked_keys(
            t, m["sliding_window"] if k == "sliding_attention" else None)
        for k in kinds)
    return {
        "projections": len(kinds) * (2.0 * d * (h + 2 * kv) * hd + 2.0 * h * hd * d),
        "attention_core": core,
        "router": len(kinds) * 2.0 * d * m["num_experts"],
        "experts": len(kinds) * pairs * expert_pair_flops(m),
        "head": 2.0 * d * vocab * (t - 1) / t,
    }


def train_step_flops(m: dict, rows: int, t: int) -> float:
    """Forward and backward of ``rows`` rows of ``t`` positions."""
    return 3.0 * rows * t * sum(forward_flops_per_token(m, t).values())


def attention_core_step(m: dict, rows: int, t: int):
    """(operations, bytes) of the attention core of one step, forward and
    backward, over the unmasked scores only: two products forward, four
    backward; q, k, v, o and their gradients once each, in bfloat16."""
    hd, h, kv = m["head_dim"], m["num_attention_heads"], m["num_key_value_heads"]
    ops = 3.0 * rows * t * forward_flops_per_token(m, t)["attention_core"]
    nbytes = len(layer_kinds(m)) * 2 * rows * t * (2 * h + 2 * kv) * hd * 2.0
    return ops, nbytes


def grouped_products_step(m: dict, pairs: float):
    """(operations, bytes) of the held experts' grouped products for
    ``pairs`` (token, choice) pairs summed over the layers of one step,
    forward and backward: the products of the pairs held; each pass reads
    the held experts' weights once (bfloat16) and the pairs' rows in and
    out."""
    held = m.get("experts_held") or m["num_experts"]
    weights = len(layer_kinds(m)) * held * 3 * m["hidden_size"] \
        * m["moe_intermediate_size"] * 2.0
    rows = pairs * 2 * m["hidden_size"] * 2.0
    return 3.0 * pairs * expert_pair_flops(m), 3.0 * (weights + rows)


# -- a device trace by module path -------------------------------------------

def under(tf_op: str, *names) -> bool:
    """Whether the module path has ``names`` as components, in this order
    (a component stands between slashes or inside a transform's brackets)."""
    at = 0
    for name in names:
        found = re.compile(r"(?:^|[/(])" + re.escape(name) + r"(?:[/):]|$)").search(
            tf_op, at)
        if not found:
            return False
        at = found.end() - 1
    return True


def leaf_events(trace: dict) -> list:
    """The first device's operations that span no other operation: a loop,
    a conditional or a call stands on the line as one event over its body's
    events, and only the body's are work of their own."""
    lines = tracered.device_lines(trace)
    events = sorted(lines[0] if lines else [], key=lambda e: (e[1], -e[2]))
    leaves, open_ = [], []          # open_: (end, index into leaves or None)
    for e in events:
        while open_ and open_[-1][0] <= e[1]:
            open_.pop()
        if open_ and open_[-1][1] is not None:
            leaves[open_[-1][1]] = None      # it holds this one: no leaf
            open_[-1] = (open_[-1][0], None)
        leaves.append(e)
        open_.append((e[1] + e[2], len(leaves) - 1))
    return [e for e in leaves if e is not None]


def device_seconds(trace: dict, wanted) -> float:
    """Seconds of the first device in operations of their own (``leaf_events``)
    whose module path ``wanted(tf_op)`` takes."""
    return sum(e[2] for e in leaf_events(trace)
               if len(e) > 3 and wanted(e[3].get("tf_op", ""))) / 1e9


def traced_steps(trace: dict):
    """How many steps the trace holds: the step loop's ``train_dispatch``
    spans on the host's lines (one a step; the profiler starts before the
    first traced step's dispatch and stops after the last's), or None."""
    count = sum(e[0] == "train_dispatch" for events in trace["host"].values()
                for e in events)
    return count or None


# -- the family's per-layer readings -----------------------------------------
# Each is written as a reader (``read(ctx)`` of ``benchmark/metrics/``) and is
# not listed in ``BENCHMARK.json`` yet: a per-layer entry that lists a new
# cell alone fails ``tests/perfbench/test_rehearsal.py``'s last assertion,
# which holds ``train_ljspeech_b200`` to every entry, and that file is not
# this PR's to edit (PERF.md section 7). ``benchmark/tools/lm_layers.py``
# prints them for a traced run; the next ``benchmark`` PR lists them and has
# ``train_cell`` put the cell's name into ``ctx`` (``workload``): the two
# rooflines read the cell's shapes by it.

def moe_step_share_pct(ctx):
    """Share of the device's busy time under the sparse-expert layers (module
    path ``moe``: router, dispatch, the experts' products, combine)."""
    busy = ctx["device"].get("busy_s")
    moe = device_seconds(ctx["trace"], lambda op: under(op, "moe"))
    return 100.0 * moe / busy if busy and moe else None


def moe_route_share_pct(ctx):
    """Share of the busy time under ``moe`` outside the experts' products:
    router, top-k, the layout of the rows, gather and combine: the
    memory-bound part."""
    busy = ctx["device"].get("busy_s")
    route = device_seconds(ctx["trace"], lambda op: under(op, "moe")
                           and not under(op, "moe", "experts"))
    return 100.0 * route / busy if busy and route else None


def moe_gmm_roofline_train(ctx):
    """The held experts' grouped products' share of their roofline, forward
    and backward: the least time the chip could take for the pairs the
    step's own counter says it computed (``moe_pairs_held`` of the window's
    events), over the device time of everything under ``moe/experts`` in the
    traced steps."""
    took = device_seconds(ctx["trace"], lambda op: under(op, "moe", "experts"))
    steps = traced_steps(ctx["trace"])
    pairs = [e["moe_pairs_held"] for e in ctx["events"] if "moe_pairs_held" in e]
    if not took or not steps or not pairs or not ctx.get("peaks"):
        return None
    ops, nbytes = grouped_products_step(cell_model(ctx)[0], sum(pairs) / len(pairs))
    pk = ctx["peaks"]
    return 100.0 * steps * max(ops / pk["bf16_flops"],
                               nbytes / pk["hbm_bytes_per_s"]) / took


def attn_blocked_roofline_train(ctx):
    """The attention core's share of its roofline, forward and backward: the
    least time for the unmasked scores alone (the causal triangle; the
    window's band on sliding layers), over the device time of everything
    under ``self_attn/core`` in the traced steps. A kernel that computes
    masked blocks reads lower, not higher."""
    took = device_seconds(ctx["trace"], lambda op: under(op, "self_attn", "core"))
    steps = traced_steps(ctx["trace"])
    if not took or not steps or not ctx.get("peaks"):
        return None
    model, traffic = cell_model(ctx)
    ops, nbytes = attention_core_step(model, traffic["batch_size"], model["seq_len"])
    pk = ctx["peaks"]
    return 100.0 * steps * max(ops / pk["bf16_flops"],
                               nbytes / pk["hbm_bytes_per_s"]) / took


def moe_load_max_over_mean(ctx):
    """How uneven the routing is over the held experts: the window's median,
    over its events, of the fullest held expert's pairs over the mean held
    expert's (each summed over the layers by the program). 1 is even."""
    import statistics

    ratios = [e["moe_expert_tokens_max"] / e["moe_expert_tokens_mean"]
              for e in ctx["events"] if e.get("moe_expert_tokens_mean")]
    return statistics.median(ratios) if ratios else None


LAYER_READINGS = {
    "moe_step_share_pct": moe_step_share_pct,
    "moe_gmm_roofline.train": moe_gmm_roofline_train,
    "attn_blocked_roofline.train": attn_blocked_roofline_train,
    "moe_route_share_pct": moe_route_share_pct,
    "moe_load_max_over_mean": moe_load_max_over_mean,
}
