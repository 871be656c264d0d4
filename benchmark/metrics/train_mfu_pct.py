"""Operations of forward and backward at the deck's real lengths, times
cycles finished, over the window and the chip's bf16 peak."""


def read(ctx):
    flops = ctx["flops_per_cycle"] * len(ctx["events"])
    return 100.0 * flops / ctx["window_s"] / ctx["peaks"]["bf16_flops"]
