"""Device time of the decode program per execution, from the trace."""


def compute(ctx):
    return ctx.program_ms("decode")
