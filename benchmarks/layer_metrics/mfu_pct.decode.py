"""Model flops of every token processed in the window over the window
times the chip's bf16 peak: the whole step's share."""


def compute(ctx):
    return ctx.mfu_pct()
