"""Host time of one prefill tick of the scheduler."""


def compute(ctx):
    v = ctx.hist_mean("tpumlops_tick_seconds", kind="prefill")
    return None if v is None else 1e3 * v
