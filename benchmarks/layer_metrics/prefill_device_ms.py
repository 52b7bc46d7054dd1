"""Device time of the prefill-chunk program per execution, from the trace."""


def compute(ctx):
    return ctx.program_ms("prefill")
