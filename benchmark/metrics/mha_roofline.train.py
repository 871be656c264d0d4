"""The fused attention kernel's share of its roofline: the least time the
chip could take for the traced calls (operations over the bf16 peak, or
bytes over the HBM peak, whichever is larger, call by call, from each call's
own shapes) over the time its events took in the device trace."""
from benchmark.harness import flops, tracered


def read(ctx):
    calls = tracered.kernel_calls(ctx["trace"])
    if not calls:
        return None
    pk = ctx["peaks"]
    least = 0.0
    for b, h, d, t, backward, _ in calls:
        ops, nbytes = flops.mha_call(b, h, d, t, backward)
        least += max(ops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / sum(c[-1] for c in calls)
