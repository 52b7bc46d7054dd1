"""1 - union of device-busy intervals over the traced window."""


def compute(ctx):
    return ctx.idle_pct()
