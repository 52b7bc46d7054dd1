"""Device time of the decode program per execution, from the trace, in a
saturated cell: there a decode step rides on every prefill tick, so it
moves `tokens_per_s`."""


def compute(ctx):
    return ctx.program_ms("decode")
